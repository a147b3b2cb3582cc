"""One run of one benchmark cell: set-up, the measured closed loop, the
read-back, the comparison with the plain reference and, with tracing, the
per-layer metrics.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in the file that entry names, its
traffic in ``bench/mixes/<traffic>.json`` and each per-layer metric in
``bench/metrics/<metric>.py``, whose ``read`` gets a :class:`Context`.
The program is used only for what is measured (the pool build, the engine
step and the SMO rounds) and for the names of its device counters.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from types import ModuleType

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import dex, engine, pool as pool_mod, smo  # noqa: E402
from repro.core.partition import LogicalPartitions  # noqa: E402
from repro.obs import registry  # noqa: E402

import reference  # noqa: E402
import roofline  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
from traffic import KEY_MAX, OP_INSERT, OP_LOOKUP, OP_UPDATE  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
STATUS_OK, STATUS_SPLIT = reference.STATUS_OK, reference.STATUS_SPLIT
#: at most this many lanes of the keys written in the window are read back
READBACK_LANES = 1 << 16
#: batches a ``--trace 1`` run records, after its window: one engine step
#: is about a million device op events (every loop iteration is one), so a
#: trace of the whole window would hold a hundred million
TRACE_BATCHES = 2
#: a trace whose op events cover less of the engine programs' spans than
#: this has lost events (the profiler drops some inside loops of tiny ops),
#: and the next TRACE_BATCHES batches are traced again, at most TRACE_TRIES
#: times in all
MIN_COVERAGE = 0.97
TRACE_TRIES = 3


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---- the specification ----------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    metrics: list          # the per-layer metric entries this cell reports
    end_to_end: list


def load_cell(name: str) -> Cell:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"options: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "mixes", w["traffic"] + ".json")) as f:
        mix = json.load(f)
    metrics = [m for m in spec["per_layer"]
               if name in m.get("workloads", [name])]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                metrics=metrics, end_to_end=e2e)


def load_metric(metric: str) -> ModuleType:
    """``bench/metrics/<metric>.py``: its ``read(ctx)`` gives the metric's
    value from a :class:`Context`, or ``None`` where there is nothing to
    read."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def set_compile_cache() -> None:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, or at the
    fixed ``<checkout>/.jax_cache``; every program is kept."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def cell_devices(chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"{chips} chips asked for, {len(devices)} present")
    return devices[:chips]


# ---- the served path -------------------------------------------------------

class Server:
    """The index on the cell's chips and its compiled programs."""

    def __init__(self, cell: Cell, seed: int, devices, engine_hook=None,
                 log=print):
        c = cell.config
        self.lanes = int(c["batch_lanes"])
        self.max_count = int(c["max_count"])
        self.max_dispatches = int(c["max_dispatches"])
        spans = {}

        t = time.perf_counter()
        self.keys = traffic.make_keys(int(c["record_count"]), seed)
        spans["data_s"] = time.perf_counter() - t

        t = time.perf_counter()
        mesh = dex.make_dex_mesh(devices)
        cfg = dex.DexMeshConfig(n_route=mesh.shape["data"],
                                n_memory=mesh.shape["model"])
        pool, self.meta = pool_mod.build_pool_host(
            self.keys, traffic.loaded_values(self.keys),
            level_m=int(c["level_m"]), fill=float(c["fill"]),
            n_shards=cfg.n_memory, headroom=float(c["headroom"]))
        stride = max(1, self.keys.size // 100_000)
        bounds = LogicalPartitions.from_samples(
            self.keys[::stride], cfg.n_route).boundaries
        spans["pool_build_s"] = time.perf_counter() - t

        t = time.perf_counter()
        shardings = dex.state_shardings(mesh, cfg)
        pool = jax.device_put(pool, shardings.pool)
        state = jax.device_put(
            dex.init_state(pool, self.meta, cfg, bounds), shardings)
        del pool
        self.state = jax.block_until_ready(state)
        self.pool_bytes = sum(s.data.nbytes for a in jax.tree.leaves(
            self.state.pool) for s in a.addressable_shards)
        spans["transfer_s"] = time.perf_counter() - t

        t = time.perf_counter()
        self.lane_sharding = NamedSharding(mesh, P(cfg.all_axes))
        fn = engine.make_dex_engine(self.meta, cfg, mesh,
                                    max_count=self.max_count, use_kernel=True)
        if engine_hook is not None:
            fn = engine_hook(fn)
        kmax = self.put(np.full(self.lanes, KEY_MAX, np.int64))
        zo = self.put(np.zeros(self.lanes, np.int32))
        self.step = jax.jit(fn, donate_argnums=0).lower(
            self.state, zo, kmax, kmax).compile()
        self.hlo = {"engine": self.step.as_text()}
        self.inserts = float(cell.mix["ops"].get("insert", 0)) > 0
        if self.inserts:
            self.smo_step = jax.jit(
                smo.make_dex_smo(self.meta, cfg, mesh, use_kernel=True),
                donate_argnums=0).lower(self.state, kmax, kmax).compile()
            self.hlo["smo"] = self.smo_step.as_text()
        spans["compile_s"] = time.perf_counter() - t
        self.spans = spans
        log(f"loaded {self.keys.size} keys: {self.meta.n_subtrees} subtrees"
            f" x {self.meta.subtree_cap} nodes, mesh {dict(mesh.shape)}, "
            f"pool {self.pool_bytes} device bytes; tpu_custom_call per "
            f"program { {k: v.count('tpu_custom_call') for k, v in self.hlo.items()} }")

    def put(self, x):
        return jax.device_put(x, self.lane_sharding)

    def smo_round(self, st, k, v):
        return self.smo_step(st, self.put(np.asarray(k)),
                             self.put(np.asarray(v)))

    def stats(self) -> np.ndarray:
        return np.asarray(jax.device_get(self.state.stats)).sum(axis=0)

    def free(self):
        self.state = None
        self.step = None
        self.smo_step = None


@dataclasses.dataclass
class Tally:
    """What the closed loop did, on the host clock."""

    batches: int = 0
    dispatches: int = 0
    attempted: int = 0
    completed: int = 0
    failed: int = 0
    wait_s: float = 0.0
    smo_s: float = 0.0
    smo_rounds: int = 0
    latencies: list = dataclasses.field(default_factory=list)
    #: per dispatch: seconds to issue it, to wait for its answers, and
    #: after them (recording, settling)
    phases: list = dataclasses.field(default_factory=list)

    def slowest(self, k=5):
        """The ``k`` slowest dispatches: index and their three phases."""
        order = sorted(range(len(self.phases)),
                       key=lambda i: -sum(self.phases[i]))[:k]
        return [[i] + [round(x, 6) for x in self.phases[i]] for i in order]


def trace_batches(server: Server, book: reference.LogBuilder,
                  client: traffic.Client):
    """Serve TRACE_BATCHES batches under the profiler (host annotations, no
    Python function tracer), inside a ``bench/window`` annotation.  Returns
    the trace's reduction, the batches' host spans and the range of their
    dispatch numbers."""
    path = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    tally = Tally()
    first = book.n_dispatches
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            for _ in range(TRACE_BATCHES):
                with jax.profiler.TraceAnnotation("bench/generate"):
                    batch = client.next_batch()
                run_batch(server, book, tally, *batch)
        loop_s = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    try:
        red = trace_reduce.reduce_dir(path, server.hlo)
    finally:
        shutil.rmtree(path, ignore_errors=True)
    host = {"loop_s": loop_s, "batches": tally.batches,
            "wait_s": tally.wait_s, "smo_s": tally.smo_s,
            "dispatches": tally.dispatches}
    return red, host, (first, book.n_dispatches)


def run_batch(server: Server, book: reference.LogBuilder, tally: Tally,
              opc, kk, vv):
    """Serve one client batch: dispatch, retry shed lanes, settle splits."""
    pending = kk != KEY_MAX
    tally.attempted += int(pending.sum())
    t_first = time.perf_counter()
    for _ in range(server.max_dispatches):
        t_issue = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/dispatch"):
            server.state, r = server.step(
                server.state, server.put(np.where(pending, opc, 0)),
                server.put(np.where(pending, kk, KEY_MAX)),
                server.put(np.where(pending, vv, 0)))
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/wait"):
            r = jax.device_get(r)
        t_ready = time.perf_counter()
        tally.wait_s += t_ready - t
        tally.dispatches += 1
        answered = pending & ~r.shed
        d = book.add(opc, kk, vv, r.found, r.values, r.status, answered,
                     scan_keys=r.scan_keys, scan_values=r.scan_values,
                     taken=r.taken)
        split = answered & (opc == OP_INSERT) & (r.status == STATUS_SPLIT)
        n_failed = 0
        if split.any():
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/smo"):
                server.state, status, rounds = smo.run_smo(
                    server.smo_round, server.state,
                    np.where(split, kk, KEY_MAX), np.where(split, vv, 0),
                    levels=server.meta.levels_in_subtree)
            tally.smo_s += time.perf_counter() - t
            tally.smo_rounds += rounds
            book.set_settle(d, np.where(split, status, reference.NO_SETTLE))
            n_failed = int((split & (status == STATUS_SPLIT)).sum())
        n_done = int(answered.sum()) - n_failed
        t_done = time.perf_counter()
        tally.latencies.append((t_done - t_first, n_done))
        tally.phases.append((t - t_issue, t_ready - t, t_done - t_ready))
        tally.completed += n_done
        tally.failed += n_failed
        pending = pending & r.shed
        if not pending.any():
            break
    tally.failed += int(pending.sum())
    tally.batches += 1


def written_keys(log: reference.Log) -> np.ndarray:
    """Keys with an acknowledged write in the log."""
    ok = (((log.opc == OP_UPDATE) & (log.status == STATUS_OK))
          | ((log.opc == OP_INSERT) & ((log.status == STATUS_OK)
                                       | (log.settle == STATUS_OK))))
    return np.unique(log.key[ok])


# ---- one run ----------------------------------------------------------------

def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, log=print) -> dict:
    """One run of the cell ``name`` on its chips; returns the result line
    as a dict (``checks`` last).  Raises :class:`NoChip` without a TPU."""
    cell = load_cell(name)
    return run(cell, cell_devices(cell.chips), seed, seconds, trace,
               t_start=t_start, log=log).result


@dataclasses.dataclass
class Run:
    result: dict
    log: reference.Log       # every answered lane, the read-back included
    keys: np.ndarray         # the loaded keys


def run(cell: Cell, devices, seed: int, seconds: float, trace: bool, *,
        t_start: float, engine_hook=None, log=print) -> Run:
    """The body of a run on the given devices.  ``engine_hook`` wraps the
    engine step before it is compiled (the tests break the step with it)."""
    set_compile_cache()
    compiles = []

    def on_event(event, duration, **kw):
        if event == COMPILE_EVENT:
            compiles.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        return _run(cell, seed, seconds, trace, t_start, devices, compiles,
                    engine_hook, log)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _run(cell, seed, seconds, trace, t_start, devices, compiles, engine_hook,
         log):
    server = Server(cell, seed, devices, engine_hook, log)
    book = reference.LogBuilder(server.lanes, server.max_count)
    client = traffic.Client(server.keys, cell.config, cell.mix, seed,
                            server.lanes)

    # warm-up: one batch of the cell's own mix (checked like any other) and,
    # where the mix inserts, one SMO round with no lanes
    t = time.perf_counter()
    run_batch(server, book, Tally(), *client.next_batch())
    if server.inserts:
        kmax = np.full(server.lanes, KEY_MAX, np.int64)
        server.state, _ = server.smo_round(server.state, kmax, kmax)
        np.asarray(server.state.stats)
    jax.block_until_ready(server.state)
    server.spans["warm_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    compiles_setup = len(compiles)
    log(f"set-up {setup_s:.3f}s {json.dumps(server.spans)}; "
        f"{compiles_setup} compiles")

    # ---- the measured window ------------------------------------------------
    # The client's own garbage is not collected inside the window.
    stats0 = server.stats()
    gc.collect()
    gc.freeze()
    gc.disable()
    tally = Tally()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        with jax.profiler.TraceAnnotation("bench/generate"):
            batch = client.next_batch()
        run_batch(server, book, tally, *batch)
    window_s = time.perf_counter() - t0
    gc.enable()
    gc.unfreeze()
    compiles_window = compiles[compiles_setup:]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    stats1 = server.stats()
    log(f"window {window_s:.3f}s: {tally.batches} batches, "
        f"{tally.dispatches} dispatches, {tally.completed} ops, "
        f"{tally.failed} failed, {len(compiles_window)} compiles")

    # ---- with tracing, the profiler over the batches after the window -------
    if trace:
        for tries in range(1, TRACE_TRIES + 1):
            red, traced_host, traced_span = trace_batches(server, book,
                                                          client)
            log(f"trace {tries}: op events cover {red.coverage:.4f} of the "
                f"programs' spans")
            if red.coverage >= MIN_COVERAGE:
                break

    # ---- read-back of the keys written, through the same step ---------------
    wk = written_keys(book.build())
    if wk.size > READBACK_LANES:
        wk = np.sort(traffic.stream(seed, 3).choice(wk, READBACK_LANES,
                                                    replace=False))
    for a in range(0, wk.size, server.lanes):
        kk = np.full(server.lanes, KEY_MAX, np.int64)
        part = wk[a:a + server.lanes]
        kk[:part.size] = part
        run_batch(server, book, Tally(), np.full(server.lanes, OP_LOOKUP,
                                                 np.int32),
                  kk, np.zeros(server.lanes, np.int64))
    pool_bytes = server.pool_bytes
    keys, setup_spans = server.keys, server.spans
    server.free()
    del server

    # ---- the comparison with the plain reference ----------------------------
    t = time.perf_counter()
    lg = book.build()
    verdict = reference.compare(keys, lg)
    check_s = time.perf_counter() - t
    log(f"reference check {check_s:.3f}s over {lg.opc.size} lanes "
        f"({wk.size} read back): {json.dumps(verdict)}")

    checks = {
        "mismatched_lanes": {"value": verdict["mismatched"], "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": tally.attempted,
              "failed": tally.failed}
    if trace:
        lo, hi = traced_span
        ctx = Context(
            cell=cell, keys=keys, log=lg,
            traced=(lg.dispatch >= lo) & (lg.dispatch < hi),
            host=traced_host, window=tally,
            stats=counters(stats1 - stats0), trace=red,
            peaks=roofline.peaks(dev0.device_kind))
        metrics = {}
        for m in cell.metrics:
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.top_gaps(10)}
    else:
        e2e = {"ops_per_s": tally.completed / window_s, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["window"] = {"seconds": window_s, "batches": tally.batches,
                        "dispatches": tally.dispatches,
                        "compiles": len(compiles_window),
                        "readback_lanes": int(wk.size),
                        "check_s": check_s, "pool_bytes": pool_bytes,
                        "smo_rounds": tally.smo_rounds,
                        "slowest_dispatches": tally.slowest(),
                        "setup_spans": setup_spans}
    if trace:
        result["window"].update(trace_tries=tries,
                                trace_coverage=red.coverage)
    result["checks"] = checks
    return Run(result=result, log=lg, keys=keys)


def counters(delta: np.ndarray) -> dict:
    """The engine's device counters by name (``STAT_OPS`` -> ``ops``)."""
    return {name.removeprefix("STAT_").lower(): int(delta[i])
            for name, i in registry.stat_constants().items()}


@dataclasses.dataclass
class Context:
    """What a per-layer reader (``bench/metrics/<metric>.py``) reads."""

    cell: Cell
    keys: np.ndarray             # the loaded keys
    log: reference.Log           # every answered lane of the run
    traced: np.ndarray           # lanes of ``log`` in the traced batches
    host: dict                   # the traced batches (after the window) on
                                 # the host clock: loop_s, wait_s, smo_s,
                                 # batches, dispatches
    window: Tally                # the whole window on the host clock
    stats: dict                  # device counters' deltas over the window
    trace: trace_reduce.Reduction  # device time of the traced batches
    peaks: dict                  # the chip's entry in bench/peaks.json
