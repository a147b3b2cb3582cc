"""Instrumentation: device milliseconds per client batch under the engine's
``dex/lat/*`` scopes, the modelled-cost ledger (its pricing, the latency
histogram and the offload cost audit), from the profiler trace of the
traced batches, averaged over the chips (bench/trace_reduce.py)."""


def read(ctx):
    s = ctx.trace.scope_s.get("lat", 0.0)
    if not s or not ctx.host["batches"]:
        return None
    return 1e3 * s / ctx.trace.chips / ctx.host["batches"]
