"""The reduction from a trace to device seconds per layer and kernel, on a
hand-built trace whose answers are worked out by hand."""

import numpy as np
import pytest

import trace_reduce as tr
from trace_reduce import Event as E, Line, Plane

HLO = """HloModule jit_engine, is_scheduled=true, entry_computation_layout={()}

ENTRY %main.1 () -> s32[4] {
  %fusion.1 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f1, metadata={op_name="jit(engine)/dex/descent/l0/gather" stack_frame_id=1}
  %while.2 = (s32[]) while((s32[]) %t), condition=%c, body=%wide.body.9, metadata={op_name="jit(engine)/dex/scan/h1/while"}
  %fusion.3 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f3, metadata={op_name="jit(engine)/dex/scan/h1/lt"}
  %leaf_scan.1 = (s32[8,128]{1,0}) custom-call(s32[8,320]{1,0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(engine)/jit(leaf_scan)/pallas_call"}
  %copy.4 = s64[8,64]{1,0} copy(s64[8,64]{1,0} %x), metadata={op_name="jit(engine)/convert_element_type"}
  %fusion.5 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f5, metadata={op_name="jit(engine)/dex/apply/jit(floor_divide)/rem"}
  ROOT %leaf_write.1 = (s32[8,64]{1,0}) custom-call(s32[8,64]{1,0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(engine)/dex/apply/jit(leaf_write)/pallas_call"}
  %fusion.6 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f6, metadata={op_name="jit(engine)/dex/lat/bin/add"}
}

%wide.body.9 (wide.param.1: (s32[])) -> (s32[]) {
  %dynamic-update-slice.9 = u32[8,64]{1,0} dynamic-update-slice(u32[8,64]{1,0} %x, u32[1,64]{1,0} %y, s32[] %i, s32[] %j)
}
"""


def _trace():
    host = Plane("/host:CPU", [Line("python", [
        E("bench/window", 1000, 10000),
        E("bench/dispatch", 1000, 100), E("bench/wait", 1100, 3900),
        E("bench/generate", 5000, 200), E("bench/dispatch", 5200, 100),
        E("bench/wait", 5300, 3700), E("bench/generate", 9000, 2000),
    ])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [E("jit_engine(3)", 1100, 3800),
                             E("jit_engine(3)", 5300, 3600)]),
        Line("XLA Ops", [
            E("fusion.1", 1100, 1000),
            E("while.2", 2100, 2000),
            E("fusion.3", 2200, 500),
            E("leaf_scan.1", 2800, 1000),
            E("copy.4", 4100, 800),
            E("%fusion.5 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop", 5300, 1000),
            E("leaf_write.1", 6300, 500),
            E("fusion.6", 6800, 2100),
            E("fusion.1", 12000, 500),          # after the window
        ]),
    ])
    return [host, dev]


def test_reduce_by_hand():
    red = tr.reduce(_trace(), {"engine": HLO})
    assert red.window_s == pytest.approx(10000e-9)
    assert red.busy_s == pytest.approx(7400e-9)
    assert red.coverage == pytest.approx(1.0)
    ns = {k: round(v * 1e9) for k, v in red.scope_s.items()}
    # while.2's own time is 2000 - 500 - 1000; the leaf_scan kernel counts
    # with the scan hops
    assert ns == {"descent": 1000, "scan": 2000, "unscoped": 800,
                  "write": 1500, "lat": 2100}
    assert {k: round(v * 1e9) for k, v in red.kernel_s.items()} == {
        "leaf_scan": 1000, "leaf_write": 500}
    gaps = {k: round(v * 1e9) for k, v in red.gaps_s.items()}
    assert gaps == {"bench/dispatch": 100, "bench/generate": 2500}
    assert red.top_ops(1) == [["engine:lat:jit(engine)/dex/lat/bin/add:fusion",
                              pytest.approx(2100e-9)]]


def test_loop_bodies_take_the_loops_scope():
    # the compiler's loop body carries no op_name of its own
    index = tr.hlo_index(HLO)
    assert index.op_name["dynamic-update-slice.9"] == \
        "jit(engine)/dex/scan/h1/while"
    assert index.kind["dynamic-update-slice.9"] == "dynamic-update-slice"
    assert index.kernel == {"leaf_scan.1": "leaf_scan",
                            "leaf_write.1": "leaf_write"}


def test_layers_of_scopes():
    assert tr.layer_of("jit(engine)/dex/route_back/x") == "descent"
    assert tr.layer_of("jit(engine)/dex/fused_a2a/request/y") == "write"
    assert tr.layer_of("jit(engine)/jit(_where)/select_n") == "unscoped"
    assert tr.layer_of("") == "unscoped"


def test_no_window_or_no_device_is_an_error():
    host, dev = _trace()
    with pytest.raises(ValueError):
        tr.reduce([dev], {"engine": HLO})
    with pytest.raises(ValueError):
        tr.reduce([host], {"engine": HLO})


XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 2000000 duration_ps: 3000000 }
  }
  event_metadata { key: 7 value { id: 7 name: "%fusion.1 = s32[4]{0} fusion(s32[4]{0} %p)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python3"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 2000 duration_ps: 1000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
  event_metadata { key: 2 value { id: 2 name: "$harness.py:214 run_batch" } }
}
"""


def test_load_reads_an_xspace_file(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    device, host = tr.load(str(path))
    (line,) = device.lines
    (ev,) = line.events
    # an op event is kept by its instruction name; of the host's events,
    # only the benchmark's own annotations
    assert (device.name, line.name, ev.name) == ("/device:TPU:0", "XLA Ops",
                                                 "fusion.1")
    assert (ev.start_ns, ev.dur_ns) == (3000, 3000)
    assert [e.name for e in host.lines[0].events] == ["bench/window"]


def test_lost_op_events_lower_the_coverage():
    planes = _trace()
    ops = planes[1].lines[1]
    # the trace loses the 800 ns copy that ends the first program
    ops.events = [e for e in ops.events if e.name != "copy.4"]
    red = tr.reduce(planes, {"engine": HLO})
    assert red.busy_s == pytest.approx(6600e-9)
    assert red.coverage == pytest.approx(6600 / 7400)
