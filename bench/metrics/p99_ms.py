"""Client loop: the 99th percentile, over all ops of the window, of the
time from the dispatch that first carried an op to the end of the dispatch
that answered it (SMO settle included), on the host clock.

The 1024 ops of a dispatch share one latency, so this is a quantile over
the window's ≈117 dispatches: the second slowest.  That makes it a reading
of the machine's stops of the process as much as of the index, which is
why it is a per-layer metric and not an end-to-end one (PERF.md, §2)."""

import numpy as np


def p99(latencies):
    """Nearest-rank 99th percentile over ops: ``latencies`` holds
    ``(seconds, ops)`` per dispatch group, and every op of a group shares
    that group's latency."""
    lat = np.array([x for x, n in latencies if n > 0])
    w = np.array([n for _, n in latencies if n > 0])
    if not w.size:
        return None
    order = np.argsort(lat)
    cum = np.cumsum(w[order])
    rank = int(np.ceil(0.99 * cum[-1]))
    return float(lat[order][np.searchsorted(cum, rank)])


def read(ctx):
    s = p99(ctx.window.latencies)
    return None if s is None else 1e3 * s
