"""Write round: device milliseconds per client batch under the engine's
``dex/fused_a2a/*`` and ``dex/apply`` scopes, from the profiler trace of the
traced batches, averaged over the chips (bench/trace_reduce.py)."""


def read(ctx):
    s = ctx.trace.scope_s.get("write", 0.0)
    if not s or not ctx.host["batches"]:
        return None
    return 1e3 * s / ctx.trace.chips / ctx.host["batches"]
