"""Unit tests for the telemetry plane (repro/obs): registry round-trip,
snapshot/delta math, batch timelines, Chrome trace export, drift checks.

These tests are deliberately mesh-free: the registry and drift modules are
numpy-only, and the timeline is fed host arrays shaped like the forced
8-device ``DexState.stats`` so the math is exact and fast.
"""

import dataclasses
import json

import numpy as np
import pytest

from repro.core import dex as dex_mod
from repro.core.sim import Counters, SimConfig
from repro.obs import drift, latency, registry, trace
from repro.obs.timeline import BatchTimeline, obs_phase, timed_call


# ---------------------------------------------------------------------------
# Registry round-trip
# ---------------------------------------------------------------------------


def test_every_stat_constant_derives_from_registry():
    consts = registry.stat_constants()
    assert len(consts) == registry.N_STATS
    for const_name, slot in consts.items():
        assert getattr(dex_mod, const_name) == slot
    assert dex_mod.N_STATS == registry.N_STATS


def test_mesh_slots_dense_and_unique():
    slots = [m.slot for m in registry.MESH_SLOTS]
    assert slots == list(range(registry.N_STATS))
    names = [m.name for m in registry.METRICS]
    assert len(names) == len(set(names))


def test_every_sim_counters_field_mapped_exactly_once():
    sim_fields = [m.sim_field for m in registry.METRICS if m.sim_field]
    assert len(sim_fields) == len(set(sim_fields)), "sim field mapped twice"
    counter_fields = {f.name for f in dataclasses.fields(Counters)}
    assert set(sim_fields) == counter_fields, (
        "registry sim_field set must cover sim.Counters exactly"
    )


def test_paired_metrics_live_on_both_planes():
    for m in registry.PAIRED:
        assert m.slot is not None and m.sim_field is not None
    # mesh-only metrics are the SPMD artifacts called out in the docstring
    mesh_only = {m.name for m in registry.MESH_SLOTS if m.sim_field is None}
    assert mesh_only == {"drops", "splits", "drains"}


def test_registry_validation_rejects_bad_metrics():
    with pytest.raises(ValueError):
        registry.Metric("x", "events", "nonsense")
    with pytest.raises(ValueError):
        registry.Metric("x", "ratio", "derived")  # derived without compute
    with pytest.raises(ValueError):
        registry.Metric("x", "events", "counter")  # maps to neither plane


# ---------------------------------------------------------------------------
# Snapshot / delta math on forced-8-device-shaped arrays
# ---------------------------------------------------------------------------


def _stats(n_dev=8, **named):
    arr = np.zeros((n_dev, registry.N_STATS), np.int64)
    for name, vec in named.items():
        arr[:, registry.SLOT_OF[name]] = vec
    return arr


def test_snapshot_fleet_and_derived():
    arr = _stats(ops=np.arange(8) * 100, hits=np.arange(8) * 50,
                 drops=np.full(8, 7))
    snap = registry.snapshot(arr)
    assert snap.n_devices == 8
    assert snap.fleet["ops"] == 2800
    assert snap.fleet["hits"] == 1400
    assert snap.derived["hit_rate"] == pytest.approx(0.5)
    assert snap.derived["drops_per_op"] == pytest.approx(56 / 2800)
    assert np.array_equal(snap.per_device["drops"], np.full(8, 7))
    # __getitem__ resolves counters and derived alike
    assert snap["ops"] == 2800
    assert snap["hit_rate"] == pytest.approx(0.5)


def test_snapshot_accepts_state_like_and_1d():
    class FakeState:
        stats = _stats(ops=np.full(8, 10))

    assert registry.snapshot(FakeState()).fleet["ops"] == 80
    one = registry.snapshot(np.zeros(registry.N_STATS, np.int64))
    assert one.n_devices == 1
    with pytest.raises(ValueError):
        registry.snapshot(np.zeros((8, registry.N_STATS + 3), np.int64))


def test_delta_recomputes_derived():
    before = registry.snapshot(_stats(ops=np.full(8, 100), hits=np.full(8, 90)))
    after = registry.snapshot(_stats(ops=np.full(8, 200), hits=np.full(8, 120)))
    d = registry.delta(after, before)
    assert d.fleet["ops"] == 800
    assert d.fleet["hits"] == 240
    assert d.derived["hit_rate"] == pytest.approx(240 / 800)


def test_sim_view_reads_counters_and_partial_fakes():
    c = Counters(ops=100, rdma_read=40, local_accesses=55, bytes=4096)
    named = registry.sim_view(c)
    assert named["ops"] == 100
    assert named["fetches"] == 40
    assert named["hits"] == 55
    assert named["bytes_per_op"] == pytest.approx(40.96)

    class Partial:
        rdma_write = 9

    assert registry.sim_view(Partial())["writes"] == 9


# ---------------------------------------------------------------------------
# Timeline
# ---------------------------------------------------------------------------


def _timeline_with_batches():
    tl = BatchTimeline("unit", meta={"devices": 8})
    tl.prime(_stats())
    for i in range(3):
        ob = tl.batch(f"b{i}")
        with ob:
            with ob.phase("engine") as ph:
                ph.fence(np.arange(4))
            with ob.phase("retry/r1"):
                pass
            ob.counters(_stats(ops=np.full(8, 100 * (i + 1)),
                               hits=np.full(8, 40 * (i + 1))))
            ob.retry("insert", i + 1)
    return tl


def test_timeline_counter_and_phase_totals():
    tl = _timeline_with_batches()
    assert len(tl.batches) == 3
    # per-batch deltas: 800, 800, 800 fleet ops
    for rec in tl.batches:
        assert rec.counters.fleet["ops"] == 800
        assert rec.counters.fleet["hits"] == 320
    totals = tl.counter_totals()
    assert totals["ops"] == 2400
    assert totals["hit_rate"] == pytest.approx(0.4)
    phases = tl.phase_totals()
    assert phases["engine"]["count"] == 3
    assert phases["retry/r1"]["count"] == 3
    rl = tl.retry_latency()
    assert rl["insert"]["count"] == 3
    assert rl["insert"]["mean_rounds"] == pytest.approx(2.0)
    assert rl["insert"]["max_rounds"] == 3


def test_timeline_json_roundtrip():
    tl = _timeline_with_batches()
    payload = json.loads(json.dumps(tl.to_json()))
    assert payload["name"] == "unit"
    assert payload["n_batches"] == 3
    assert len(payload["batches"]) == 3
    b0 = payload["batches"][0]
    assert b0["counters"]["ops"] == 800
    assert {p["name"] for p in b0["phases"]} == {"engine", "retry/r1"}
    assert b0["retries"] == {"insert": 1}


def test_instrument_wraps_state_returning_callable():
    tl = BatchTimeline("wrap")
    tl.prime(_stats())

    class FakeState:
        def __init__(self, n):
            self.stats = _stats(ops=np.full(8, n))

    def engine(state, n):
        return FakeState(n), "aux"

    engine.plan = {"phases": ("dex/route",)}
    wrapped = tl.instrument(engine, label="engine")
    assert wrapped.plan == {"phases": ("dex/route",)}
    out = wrapped(None, 50)
    assert out[1] == "aux"
    assert tl.batches[0].counters.fleet["ops"] == 400
    wrapped(None, 75)
    assert tl.batches[1].counters.fleet["ops"] == 200  # delta, not total


def test_timed_call_and_obs_phase_nullcontext():
    out, secs = timed_call(lambda x: x + 1, 41)
    assert out == 42 and secs >= 0.0
    with obs_phase(None, "anything"):
        pass  # no-op without an observer
    tl = BatchTimeline("hook")
    ob = tl.batch("b")
    with ob:
        with obs_phase(ob, "smo/drain"):
            pass
    assert tl.batches[0].phase_seconds().keys() == {"smo/drain"}


def test_smo_rounds_land_in_a_profiler_trace(tmp_path):
    """Each SMO round opens a ``dex/smo/round`` profiler span whose argument
    is the round's number, and is still a timeline phase of its own."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.core import smo
    from repro.core.nodes import KEY_MAX
    from repro.core.write import STATUS_OK, STATUS_SPLIT

    class _State:
        def __init__(self, stats):
            self.stats = stats

    def fake_smo(state, keys, values):
        # the first round only splits; the second settles every lane
        splits_so_far = state.stats[0, dex_mod.STAT_SMO_SPLITS]
        stats = state.stats.copy()
        stats[0, dex_mod.STAT_SMO_SPLITS] += 1
        live = np.asarray(keys) != KEY_MAX
        st = STATUS_SPLIT if splits_so_far == 0 else STATUS_OK
        return _State(stats), np.where(live, st, 0).astype(np.int32)

    keys = np.array([5, KEY_MAX, 9], np.int64)
    tl = BatchTimeline("smo")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tl.batch("b") as ob:
            _, status, rounds = smo.run_smo(
                fake_smo, _State(np.zeros((1, dex_mod.N_STATS), np.int64)),
                keys, keys, obs=ob,
            )
    finally:
        jax.profiler.stop_trace()
    assert rounds == 2 and list(status[[0, 2]]) == [STATUS_OK, STATUS_OK]
    assert tl.batches[0].phase_seconds().keys() == {"smo/round0",
                                                    "smo/round1"}
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [dict(e.stats)["round"]
             for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events
             if e.name == "dex/smo/round"]
    assert sorted(spans) == [0, 1]


# ---------------------------------------------------------------------------
# Trace export
# ---------------------------------------------------------------------------


def test_trace_events_schema(tmp_path):
    tl = _timeline_with_batches()
    doc = trace.to_trace_events(tl)
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert events, "no events emitted"
    kinds = {e["ph"] for e in events}
    assert {"M", "X", "C"} <= kinds
    for e in events:
        assert isinstance(e["name"], str) and "pid" in e
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0 and "tid" in e
        if e["ph"] == "C":
            assert isinstance(e["args"], dict) and e["args"]
    # every batch contributes one top-level X span plus its phases
    batch_spans = [e for e in events
                   if e["ph"] == "X" and e.get("cat") == "batch"]
    assert len(batch_spans) == 3
    phase_spans = {e["name"] for e in events
                   if e["ph"] == "X" and e.get("cat") == "phase"}
    assert phase_spans == {"engine", "retry/r1"}
    # counter tracks cover the fleet-derived metrics
    counter_names = {e["name"] for e in events if e["ph"] == "C"}
    assert "hit_rate" in counter_names

    path = tmp_path / "unit.trace.json"
    trace.write_trace(tl, str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk["traceEvents"]


def test_profiler_annotations_is_reentrant_noop_when_disabled():
    # the host phases' profiler annotations (obs_phase) nest, and with the
    # profiler off they record nothing and hand the block no phase
    with obs_phase(None, "smo/drain") as outer:
        with obs_phase(None, "smo/round0", span="dex/smo/round",
                       round=0) as inner:
            pass
    assert outer is None and inner is None


# ---------------------------------------------------------------------------
# Drift
# ---------------------------------------------------------------------------


def test_drift_pass_and_fail_and_report_format(capsys):
    mesh = {"ops": 1000, "fetches": 410, "writes": 300}
    sim = {"ops": 1000, "fetches": 400, "writes": 300}
    rep = drift.assert_plane_agreement(
        mesh, sim,
        {"fetches": drift.rel(0.05), "writes": drift.rel(0.01)},
        label="unit",
    )
    assert rep.ok and not rep.failures
    out = capsys.readouterr().out
    assert "plane agreement [unit]: OK" in out
    assert "[ok  ]" in out

    with pytest.raises(drift.PlaneDriftError) as ei:
        drift.assert_plane_agreement(
            mesh, sim, {"fetches": drift.rel(0.01)}, label="unit",
            verbose=False,
        )
    report = ei.value.report
    assert not report.ok and len(report.failures) == 1
    assert "DRIFT" in report.format()
    assert "fetches" in str(ei.value)


def test_drift_per_op_normalisation():
    # 0.41 vs 0.40 fetches/op: 2.5% relative error despite 10x more mesh ops
    mesh = {"ops": 10_000, "fetches": 4100}
    sim = {"ops": 1_000, "fetches": 400}
    rep = drift.compare(mesh, sim, {"fetches": drift.rel(0.05, per_op=True)})
    assert rep.ok
    assert rep.entries[0].measured == pytest.approx(0.025)
    assert not drift.compare(
        mesh, sim, {"fetches": drift.rel(0.05)}
    ).ok, "without per_op the raw counts disagree 10x"


def test_drift_ratio_band_and_min_count_skip():
    rep = drift.compare({"smo_splits": 30}, {"smo_splits": 20},
                        {"smo_splits": drift.ratio(0.4, 2.5)})
    assert rep.ok and rep.entries[0].measured == pytest.approx(1.5)
    skipped = drift.compare({"smo_splits": 3}, {"smo_splits": 0},
                            {"smo_splits": drift.ratio(0.4, 2.5, min_count=10)})
    assert skipped.ok and skipped.entries[0].skipped
    assert "SKIP" in skipped.format()


def test_drift_absolute_gauge():
    rep = drift.compare({"moved_fraction": 0.31}, {"moved_fraction": 0.27},
                        {"moved_fraction": drift.absolute(0.10)})
    assert rep.ok and rep.entries[0].measured == pytest.approx(0.04)
    assert not drift.compare(
        {"moved_fraction": 0.31}, {"moved_fraction": 0.05},
        {"moved_fraction": drift.absolute(0.10)},
    ).ok


def test_drift_rejects_unregistered_metric():
    with pytest.raises(KeyError):
        drift.compare({"ops": 1}, {"ops": 1}, {"tpyo": drift.rel(0.1)})


def test_drift_coerces_all_counter_carriers():
    snap = registry.snapshot(_stats(ops=np.full(8, 50), hits=np.full(8, 25)))
    counters = Counters(ops=400, local_accesses=200)
    rep = drift.compare(snap, counters, {"hits": drift.rel(0.0, per_op=True)})
    assert rep.ok, rep.format()
    tl = _timeline_with_batches()
    rep2 = drift.compare(tl, {"ops": 2400}, {"ops": drift.rel(0.0)})
    assert rep2.ok

    class FakeState:
        stats = _stats(ops=np.full(8, 50))

    assert drift.compare(FakeState(), {"ops": 400},
                         {"ops": drift.rel(0.0)}).ok
    with pytest.raises(TypeError):
        drift._named(object())


# ---------------------------------------------------------------------------
# Latency ledger (obs/latency): bucket schema, percentiles, audit, timeline
# ---------------------------------------------------------------------------


def test_latency_constants_mirror_sim_config():
    # the ledger prices lanes with literal copies of the SimConfig defaults
    # (no import cycle); if either side moves, the planes silently diverge —
    # so this equality is load-bearing, not cosmetic
    cfg = SimConfig(name="unit")
    assert latency.T_CACHED == cfg.t_cached_access
    assert latency.T_READ == cfg.t_rdma_read
    assert latency.T_WRITE == cfg.t_rdma_write
    assert latency.T_RPC == cfg.t_rpc_base
    assert latency.T_MEM == cfg.t_mem_search
    assert latency.T_LOCAL == cfg.t_local_search


def test_latency_bucket_schema():
    edges = latency.bucket_edges()
    assert len(edges) == latency.N_BUCKETS + 1
    assert np.all(np.diff(edges) > 0)
    # underflow clamps to bucket 0, overflow to the last bucket
    assert latency.bucket_index(0.0) == 0
    assert latency.bucket_index(latency.T0 / 2) == 0
    assert latency.bucket_index(1.0) == latency.N_BUCKETS - 1
    # a bucket's left edge lands in that bucket (half-open intervals)
    for i in (0, 1, 5, latency.N_BUCKETS - 1):
        assert latency.bucket_index(float(edges[i])) == i
    # vectorised form agrees with scalars
    xs = np.array([0.0, latency.T0, 3e-6, 1.0])
    assert list(latency.bucket_index(xs)) == [
        int(latency.bucket_index(float(x))) for x in xs
    ]


def test_latency_percentile_from_bucket_cdf():
    assert latency.percentile(np.zeros(latency.N_BUCKETS), 99.0) == 0.0
    h = np.zeros(latency.N_BUCKETS)
    h[3] = 10
    mid = latency.T0 * 2.0**3 * 2.0**0.5
    assert latency.percentile(h, 50.0) == pytest.approx(mid)
    assert latency.percentile(h, 99.0) == pytest.approx(mid)
    # 90 lanes in bucket 2, 10 in bucket 9: p50 low, p99 in the tail
    h2 = np.zeros(latency.N_BUCKETS)
    h2[2], h2[9] = 90, 10
    assert latency.percentile(h2, 50.0) == pytest.approx(
        latency.T0 * 4 * 2**0.5)
    assert latency.percentile(h2, 99.0) == pytest.approx(
        latency.T0 * 512 * 2**0.5)


def test_latency_section_and_ledger_conservation():
    rng = np.random.default_rng(0)
    hist = rng.integers(
        0, 50,
        size=(latency.N_CLASSES, latency.N_PATHS, latency.N_BUCKETS))
    sec = latency.latency_section(hist)
    assert sec["total"] == int(hist.sum())
    assert sec["op_classes"] == list(latency.OP_CLASSES)
    assert sec["paths"] == list(latency.PATHS)
    nested = sum(sum(sum(cell) for cell in cls) for cls in sec["hist"])
    assert nested == sec["total"]
    for led in sec["ledger"].values():
        assert led["count"] == sum(
            led["paths"][p]["count"] for p in latency.PATHS)
        shares = sum(led["paths"][p]["share"] for p in latency.PATHS)
        assert shares == pytest.approx(1.0 if led["count"] else 0.0)


def test_audit_report_excludes_unrealized_cells():
    pred = np.array([[100.0, 50.0], [0.0, 7.0]])
    real = np.array([[200.0, 0.0], [0.0, 7.0]])
    rep = latency.audit_report(pred, real)
    # the (0,1) cell predicted bytes but realized none: reported in cells,
    # excluded from the fleet ratio (no fetch-side decision to audit)
    assert rep["predicted_bytes"] == pytest.approx(107.0)
    assert rep["realized_bytes"] == pytest.approx(207.0)
    assert rep["mispricing_ratio"] == pytest.approx(107.0 / 207.0)
    cells = {(c["column"], c["level"]) for c in rep["cells"]}
    assert cells == {(0, 0), (0, 1), (1, 1)}  # all-zero (1,0) dropped
    empty = latency.audit_report(np.zeros((1, 1)), np.zeros((1, 1)))
    assert empty["mispricing_ratio"] == 0.0 and empty["cells"] == []


def test_percentile_gauges_skip_empty_and_filter_classes():
    hist = np.zeros(
        (latency.N_CLASSES, latency.N_PATHS, latency.N_BUCKETS), np.int64)
    hist[0, 0, 2] = 5  # lookups only
    g = latency.percentile_gauges(hist)
    assert set(g) == {"lat_p50_lookup", "lat_p99_lookup"}
    hist[3, 1, 8] = 2  # scans now sampled too, but filtered out
    g2 = latency.percentile_gauges(hist, classes=("lookup",))
    assert set(g2) == {"lat_p50_lookup", "lat_p99_lookup"}
    # every gauge name must be drift-gateable
    for name in latency.percentile_gauges(hist):
        assert name in registry.BY_NAME


class _LatState:
    """Minimal DexState stand-in carrying the two latency planes."""

    def __init__(self, dev=2):
        self.lat_hist = np.zeros(
            (dev, latency.N_CLASSES, latency.N_PATHS, latency.N_BUCKETS),
            np.int64)
        self.lat_audit = np.zeros((dev, 2, 4, 3), np.float32)


def test_timeline_latency_prime_capture_delta():
    st = _LatState()
    st.lat_hist[:, 0, 0, 1] = 7  # warmup lanes, fenced out by prime
    st.lat_audit[:, 0, 0, 0] = 3.0
    tl = BatchTimeline("lat")
    tl.prime_latency(st)
    st.lat_hist[0, 1, 3, 4] += 11  # measured window
    st.lat_audit[1, 1, 2, 1] += 5.0
    hist = tl.capture_latency(st)
    assert hist.shape == (
        latency.N_CLASSES, latency.N_PATHS, latency.N_BUCKETS)
    assert int(hist.sum()) == 11 and hist[1, 3, 4] == 11
    summ = tl.summary()
    assert summ["latency"]["total"] == 11
    audit = summ["cost_audit"]
    assert audit["realized_bytes"] == pytest.approx(5.0)
    assert audit["predicted_bytes"] == pytest.approx(0.0)
    # never primed -> lifetime totals
    tl2 = BatchTimeline("lat2")
    assert int(tl2.capture_latency(st).sum()) == int(st.lat_hist.sum())


def test_timeline_capture_accepts_bare_histogram():
    hist = np.zeros(
        (latency.N_CLASSES, latency.N_PATHS, latency.N_BUCKETS), np.int64)
    hist[2, 1, 5] = 4
    tl = BatchTimeline("raw")
    assert int(tl.capture_latency(hist).sum()) == 4
    summ = tl.summary()
    assert summ["latency"]["total"] == 4
    assert "cost_audit" not in summ  # no audit plane on a bare histogram


def test_retry_latency_zero_retry_and_interleaving():
    tl = BatchTimeline("retries")
    tl.prime(_stats())
    with tl.batch("b0") as ob:
        ob.retry("insert", 3)
    with tl.batch("b1"):
        pass  # a batch where nothing shed
    with tl.batch("b2") as ob:
        ob.retry("insert", 1)
        ob.retry("scan", 2)
    rl = tl.retry_latency()
    # a class that never sheds is absent, not zero-filled
    assert "lookup" not in rl
    assert rl["insert"] == {"count": 2, "mean_rounds": 2.0, "max_rounds": 3}
    assert rl["scan"] == {"count": 1, "mean_rounds": 2.0, "max_rounds": 2}
    assert tl.batches[1].retries == {}


def test_trace_counter_tracks_on_empty_timeline():
    tl = BatchTimeline("empty")
    doc = trace.to_trace_events(tl)
    assert all(e["ph"] != "C" for e in doc["traceEvents"])
    # capturing a ledger on a zero-batch timeline anchors the latency
    # counter tracks at t=0 instead of crashing on max() of no spans
    hist = np.zeros(
        (latency.N_CLASSES, latency.N_PATHS, latency.N_BUCKETS), np.int64)
    hist[0, 1, 4] = 9
    tl.capture_latency(hist)
    tracks = [e for e in trace.to_trace_events(tl)["traceEvents"]
              if e["ph"] == "C" and e.get("cat") == "latency"]
    assert {e["name"] for e in tracks} == {"lat_p50_lookup",
                                           "lat_p99_lookup"}
    for e in tracks:
        assert e["ts"] == 0.0
        assert e["args"][e["name"]] > 0.0


def test_trace_emits_mispricing_track_with_audit():
    st = _LatState()
    st.lat_hist[0, 0, 1, 2] = 3
    st.lat_audit[0, 0, 0, 0] = 10.0  # predicted
    st.lat_audit[0, 1, 0, 0] = 5.0   # realized
    tl = BatchTimeline("aud")
    tl.capture_latency(st)
    ev = trace.to_trace_events(tl)["traceEvents"]
    g = {e["name"]: e["args"] for e in ev if e["ph"] == "C"}
    assert g["offload_mispricing"]["offload_mispricing"] == pytest.approx(
        2.0)


# ---------------------------------------------------------------------------
# Docs can't rot: DESIGN.md embeds the generated counter table
# ---------------------------------------------------------------------------


def test_design_md_counter_table_matches_registry():
    import pathlib

    design = pathlib.Path(__file__).resolve().parent.parent / "DESIGN.md"
    text = design.read_text()
    for line in registry.markdown_table().splitlines():
        assert line in text, f"DESIGN.md counter table is stale: {line!r}"
