"""A whole run of a cell on the CPU at a small size, without the harness's
look for a chip: a sound run is correct, and a run with the timed path
broken underneath is not."""

import time

import dataclasses

import jax
import jax.numpy as jnp
import pytest

import harness
import roofline
import trace_reduce
from test_trace_reduce import HLO, _trace
from traffic import KEY_MAX

SMALL = {"record_count": 30_000}
SEED = 2**31 + 99


def _run(name, hook=None, seconds=1.0, trace=False):
    cell = harness.load_cell(name)
    cell.config.update(SMALL)
    return harness.run(cell, jax.devices()[:1], SEED, seconds, trace,
                       t_start=time.perf_counter(), engine_hook=hook,
                       log=lambda m: None).result


def state_unchanged(step):
    def f(state, opc, keys, vals):
        return state, step(state, opc, keys, vals)[1]
    return f


def half_batch(step):
    def f(state, opc, keys, vals):
        half = keys.shape[0] // 2
        return step(state, opc, keys.at[half:].set(KEY_MAX), vals)
    return f


def answer_altered(step):
    def f(state, opc, keys, vals):
        state, r = step(state, opc, keys, vals)
        first = jnp.argmax(r.found)
        return state, r._replace(values=r.values.at[first].add(1))
    return f


def test_no_chip_is_refused():
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a chip is present")
    with pytest.raises(harness.NoChip):
        harness.run_cell("ycsb-a.zipf.1chip", SEED, 1.0, False,
                         t_start=time.perf_counter())


@pytest.mark.parametrize("cell", ["ycsb-a.zipf.1chip", "ycsb-e.zipf.1chip"])
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] > 0 and res["window"]["compiles"] == 0
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"ops_per_s", "setup_s"}


@pytest.mark.parametrize("cell,fault", [
    ("ycsb-a.zipf.1chip", state_unchanged),
    ("ycsb-a.zipf.1chip", half_batch),
    ("ycsb-c.uniform.1chip", half_batch),
    ("ycsb-c.uniform.1chip", answer_altered),
    ("ycsb-e.zipf.1chip", half_batch),
])
def test_broken_step_is_not_correct(cell, fault):
    res = _run(cell, fault)
    assert not res["correct"]
    assert res["checks"]["mismatched_lanes"]["value"] > 0


@pytest.mark.parametrize("coverages", [[1.0], [0.5, 0.99], [0.5, 0.5, 0.5]])
def test_traced_run_reads_every_metric(monkeypatch, coverages):
    # the CPU has no device plane to reduce: each trace of the batches
    # after the window reads as the hand-built one, with the coverage given
    red = trace_reduce.reduce(_trace(), {"engine": HLO})
    readings = iter(coverages)

    def reduce_dir(path, hlo):
        assert set(hlo) == {"engine", "smo"}
        return dataclasses.replace(red, coverage=next(readings))

    monkeypatch.setattr(trace_reduce, "reduce_dir", reduce_dir)
    v5e = roofline.peaks("TPU v5 lite")
    monkeypatch.setattr(roofline, "peaks", lambda kind: v5e)
    res = _run("ycsb-e.zipf.1chip", trace=True)
    assert res["correct"] and res["window"]["compiles"] == 0
    cell = harness.load_cell("ycsb-e.zipf.1chip")
    assert set(res["metrics"]) == {m["name"] for m in cell.metrics}
    assert res["window"]["trace_tries"] == len(coverages)
    assert res["window"]["trace_coverage"] == coverages[-1]
    assert res["device"]["busy_s"] == red.busy_s and res["breakdown"]
