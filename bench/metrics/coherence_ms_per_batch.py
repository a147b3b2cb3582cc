"""Write round, its coherence step: device milliseconds per client batch
under the engine's ``dex/coherence`` scopes (the version bump, its
``pmax`` and the cache write-through and invalidate), from the profiler
trace of the traced batches, averaged over the chips."""

import scope_family


def read(ctx):
    return scope_family.ms_per_batch(ctx, "coherence")
