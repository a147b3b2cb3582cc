"""The step outside the named phases (pool planes, the X64 split, copies):
device milliseconds per client batch under engine ops under no ``dex/``
scope, from the profiler trace of the traced batches, averaged over the
chips (bench/trace_reduce.py)."""


def read(ctx):
    s = ctx.trace.scope_s.get("unscoped", 0.0)
    if not s or not ctx.host["batches"]:
        return None
    return 1e3 * s / ctx.trace.chips / ctx.host["batches"]
