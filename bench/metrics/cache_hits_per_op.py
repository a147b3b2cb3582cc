"""Route and cached descent: cache hits per admitted op over the window,
from the engine's device counters (``STAT_HITS`` / ``STAT_OPS`` deltas).
A hit is counted per descent level and scan hop, so this can exceed 1."""


def read(ctx):
    ops = ctx.stats["ops"]
    if not ops:
        return None
    return ctx.stats["hits"] / ops
