"""``BENCHMARK.json`` names every configuration, mix and metric reader by a
file of its own, within the limits the benchmark's format sets."""

import json
import os
import re

import pytest

import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                    r"projection|head|expansion|experts_per")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"][:2] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    # a full check of 24 cells fits its time: runs of run_seconds + 60 s,
    # 2 x 90 s of compiling a cell, 1200 s spare
    rs = spec["run_seconds"]
    assert 1 <= rs and (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    cells = len(spec["workloads"])
    assert cells <= 24
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(1, cells // 2)


def test_every_config_is_used_and_loads(spec):
    used = {w["config"] for w in spec["workloads"]}
    files = set()
    for c in spec["configs"]:
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("bench/configs/")
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert set(c["reduced"]) == set(body["reduced"])
        assert not any(WIDTHS.search(k) for k in c["reduced"])
        for key in ("record_count", "request_distribution", "batch_lanes",
                    "max_count", "max_dispatches", "level_m", "fill",
                    "headroom", "guarantees"):
            assert key in body, (c["name"], key)
    sources = [c["source"] for c in spec["configs"]]
    assert len(set(sources)) == len(sources)


def test_every_cell_loads_its_files(spec):
    seen = set()
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert abs(sum(cell.mix["ops"].values()) - 1.0) < 1e-9
        names = {m["name"] for m in cell.end_to_end}
        assert {"setup_s", "ops_per_s"} <= names
        assert cell.metrics, w["name"]
        for m in cell.metrics:
            assert callable(harness.load_metric(m["name"]).read)


def test_metrics(spec):
    e2e = {m["name"] for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(set(names)) == len(names)
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert e2e == {"ops_per_s", "setup_s"}
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and NAME.match(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m["workloads"]) <= cells and m["workloads"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))


def test_size_limit():
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
