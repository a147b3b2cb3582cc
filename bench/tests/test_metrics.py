"""Every per-layer reader named in ``BENCHMARK.json`` reads its metric from
a :class:`harness.Context`: a hand-built trace, a small log and the
engine's counters by name."""

import numpy as np
import pytest

import harness
import reference
import roofline
import trace_reduce
from test_trace_reduce import HLO, _trace
from traffic import OP_INSERT, OP_LOOKUP, OP_SCAN, OP_UPDATE
from repro.core import dex


def _context(cell):
    keys = np.arange(1, 1001, dtype=np.int64) * 10
    opc = np.array([OP_SCAN, OP_LOOKUP, OP_UPDATE, OP_INSERT], np.int32)
    n = opc.size
    b = reference.LogBuilder(lanes=n, max_count=128)
    tk = np.array([10, 0, 0, 0], np.int32)
    b.add(opc, np.array([400, 20, 30, 35], np.int64), np.zeros(n, np.int64),
          np.zeros(n, bool), np.zeros(n, np.int64),
          np.array([0, 0, 1, 1], np.int32), np.ones(n, bool),
          scan_keys=np.zeros((n, 128), np.int64),
          scan_values=np.zeros((n, 128), np.int64), taken=tk)
    lg = b.build()
    delta = np.zeros(dex.N_STATS, np.int64)
    delta[dex.STAT_OPS], delta[dex.STAT_HITS] = 8, 3
    return harness.Context(
        cell=cell, keys=keys, log=lg, traced=np.ones(n, bool),
        host={"loop_s": 1.0, "wait_s": 0.9, "smo_s": 0.0, "batches": 2,
              "dispatches": 2},
        window=harness.Tally(), stats=harness.counters(delta),
        trace=trace_reduce.reduce(_trace(), {"engine": HLO}),
        peaks=roofline.peaks("TPU v5 lite"))


def test_counters_by_name():
    delta = np.arange(dex.N_STATS, dtype=np.int64)
    c = harness.counters(delta)
    assert c["ops"] == dex.STAT_OPS and c["hits"] == dex.STAT_HITS
    assert c["drains"] == dex.STAT_DRAINS and len(c) == dex.N_STATS


@pytest.mark.parametrize("cell", ["ycsb-a.zipf.1chip", "ycsb-c.uniform.1chip",
                                  "ycsb-e.zipf.1chip"])
def test_every_reader_reads_a_context(cell):
    c = harness.load_cell(cell)
    ctx = _context(c)
    ctx.window.latencies = [(0.44, 1000), (0.5, 24)]
    for m in c.metrics:
        v = harness.load_metric(m["name"]).read(ctx)
        assert v is not None and v > 0, m["name"]
    assert harness.load_metric("cache_hits_per_op").read(ctx) == 3 / 8
    assert harness.load_metric("host_ms_per_batch").read(ctx) == \
        pytest.approx(50.0)


def test_p99_is_nearest_rank_over_ops():
    # 99 ops at 1 s and one group of 2 ops at 5 s: the 99th percentile of
    # 101 ops is the 100th smallest
    p99 = harness.load_metric("p99_ms").p99
    assert p99([(1.0, 99), (5.0, 2)]) == 5.0
    assert p99([(1.0, 100), (5.0, 1)]) == 1.0
    assert p99([(2.0, 0), (1.0, 10)]) == 1.0
    assert p99([]) is None
