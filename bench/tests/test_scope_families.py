"""The readers of the engine's scope families (``dex/locate``,
``dex/offload``, ``dex/coherence``, ``dex/lanes``) and of the ledger
(``dex/lat/*``), on a hand-built trace whose answers are worked out by
hand."""

import types

import pytest

import harness
import scope_family
import trace_reduce as tr
from trace_reduce import Event as E, Line, Plane

HLO = """HloModule jit_engine, is_scheduled=true, entry_computation_layout={()}

ENTRY %main.1 () -> s32[4] {
  %fusion.1 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f1, metadata={op_name="jit(engine)/dex/locate/jit(searchsorted)/while"}
  %fusion.2 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f2, metadata={op_name="jit(engine)/dex/offload/gather"}
  %fusion.3 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f3, metadata={op_name="jit(engine)/dex/coherence/scatter-max"}
  %fusion.4 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f4, metadata={op_name="jit(engine)/dex/lanes/concatenate"}
  %leaf_scan.1 = (s32[8,128]{1,0}) custom-call(s32[8,320]{1,0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(engine)/dex/lanes/jit(leaf_scan)/pallas_call"}
  %fusion.5 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f5, metadata={op_name="jit(engine)/dex/lat/audit/sort"}
  %fusion.6 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f6, metadata={op_name="jit(engine)/dex/lat/price/add"}
  %fusion.7 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f7, metadata={op_name="jit(engine)/dex/descent/l0/gather"}
  %copy.8 = s64[8,64]{1,0} copy(s64[8,64]{1,0} %x)
  ROOT %fusion.9 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f9, metadata={op_name="jit(engine)/pipe/back/dex/lanes/dex/locate/add"}
}
"""


def _trace(ops):
    host = Plane("/host:CPU", [Line("python", [E("bench/window", 0, 100000)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [E("jit_engine(3)", 1000, 98000)]),
        Line("XLA Ops", [E(n, 1000 + 10000 * i, d)
                         for i, (n, d) in enumerate(ops)]),
    ])
    return [host, dev]


OPS = [("fusion.1", 1000), ("fusion.2", 2000), ("fusion.3", 3000),
       ("fusion.4", 4000), ("leaf_scan.1", 5000), ("fusion.5", 600),
       ("fusion.6", 700), ("fusion.7", 8000), ("copy.8", 900),
       ("fusion.9", 100)]


def _ctx(ops=OPS, batches=2):
    red = tr.reduce(_trace(ops), {"engine": HLO})
    return types.SimpleNamespace(trace=red, host={"batches": batches})


@pytest.mark.parametrize("metric,ns", [
    ("locate_ms_per_batch", 1000),
    ("offload_ms_per_batch", 2000),
    ("coherence_ms_per_batch", 3000),
    # the first dex/ component names the family, as layer_of reads it; the
    # leaf_scan kernel counts with the scan hops by its name
    ("lanes_ms_per_batch", 4000 + 100),
    ("lat_ledger_ms_per_batch", 600 + 700),
])
def test_each_family_reads_its_ops(metric, ns):
    v = harness.load_metric(metric).read(_ctx())
    assert v == pytest.approx(1e3 * ns * 1e-9 / 2)


def test_the_layers_and_families_sum_to_the_busy_time():
    red = _ctx().trace
    fams = sum(scope_family.family_s(red, f)
               for f in ("locate", "offload", "coherence", "lanes"))
    assert fams == pytest.approx(red.scope_s["dex_other"])
    assert sum(red.scope_s.values()) == pytest.approx(red.busy_s)
    assert red.scope_s["scan"] == pytest.approx(5000e-9)
    assert red.scope_s["unscoped"] == pytest.approx(900e-9)


@pytest.mark.parametrize("metric", [
    "locate_ms_per_batch", "offload_ms_per_batch", "coherence_ms_per_batch",
    "lanes_ms_per_batch", "lat_ledger_ms_per_batch",
])
def test_a_program_without_the_scopes_reads_nothing(metric):
    # the older program: only the descent and unscoped ops in the trace
    ctx = _ctx([("fusion.7", 8000), ("copy.8", 900)])
    assert harness.load_metric(metric).read(ctx) is None
    assert harness.load_metric(metric).read(_ctx(batches=0)) is None
