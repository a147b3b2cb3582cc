"""Lane plumbing: device milliseconds per client batch under the engine's
``dex/lanes`` scopes (the opcode mask, packing lanes into buckets and back
for the route and fused rounds, result and status assembly, counter and
miss-EMA updates, the scan window and the ``leaf_scan`` kernel's operand
padding and int64 split and join; the kernel counts with the scan hops),
from the profiler trace of the traced batches, averaged over the chips."""

import scope_family


def read(ctx):
    return scope_family.ms_per_batch(ctx, "lanes")
