"""Device time of one engine scope family that ``trace_reduce`` reduces to
its ``dex_other`` layer: ``dex/locate``, ``dex/offload``, ``dex/coherence``
and ``dex/lanes``.  The reduction keeps each op's time under the key
``engine:dex_other:<op_name>:<kind>``; an op belongs to the family of the
first ``dex/`` component of its op_name, as ``trace_reduce.layer_of``
reads it."""

import re

PREFIX = "engine:dex_other:"
_DEX = re.compile(r"(?:^|/)dex/([A-Za-z0-9_]+)")


def family_s(red, family: str) -> float:
    """Device seconds, summed over the chips, of the engine ops whose
    op_name's first ``dex/`` component is ``family``."""
    total = 0.0
    for key, s in red.ops_s.items():
        if key.startswith(PREFIX):
            m = _DEX.search(key[len(PREFIX):])
            if m and m.group(1) == family:
                total += s
    return total


def ms_per_batch(ctx, family: str):
    """The family's device milliseconds per traced client batch, averaged
    over the chips; ``None`` where the trace holds none of its ops (a
    program without the family's scopes)."""
    s = family_s(ctx.trace, family)
    if not s or not ctx.host["batches"]:
        return None
    return 1e3 * s / ctx.trace.chips / ctx.host["batches"]
