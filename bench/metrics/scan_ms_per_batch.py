"""Scan hops: device milliseconds per client batch under the engine's
``dex/scan/h*`` scopes and the ``leaf_scan`` kernel, from the profiler trace
of the traced batches, averaged over the chips (bench/trace_reduce.py)."""


def read(ctx):
    s = ctx.trace.scope_s.get("scan", 0.0)
    if not s or not ctx.host["batches"]:
        return None
    return 1e3 * s / ctx.trace.chips / ctx.host["batches"]
