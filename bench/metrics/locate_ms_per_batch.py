"""Route and cached descent, the part outside the fetch rounds: device
milliseconds per client batch under the engine's ``dex/locate`` scopes (the
route owner, the replicated top walk, the leaf-direct route-table probe and
fence, the cache admission dice, each lane's column, the child-slot and
leaf searches between descent levels and the scan hops' successor links),
from the profiler trace of the traced batches, averaged over the chips."""

import scope_family


def read(ctx):
    return scope_family.ms_per_batch(ctx, "locate")
