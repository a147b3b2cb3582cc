"""Offloaded walk: device milliseconds per client batch under the engine's
``dex/offload`` scopes (the per-column offload decision and the owner-side
block walk of offloaded and peeked lanes, ``peer_answer`` included), from
the profiler trace of the traced batches, averaged over the chips."""

import scope_family


def read(ctx):
    return scope_family.ms_per_batch(ctx, "offload")
