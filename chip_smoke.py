#!/usr/bin/env python3
"""Chip smoke test: the DEX engine's served path, compiled, on a TPU.

Builds a YCSB dataset of 10^8 keys from ``--seed``, bulk-loads the
subtree-blocked pool onto the chip(s), each shard copied from the host
straight to its device, and drives the jitted
mixed-op engine (``core/engine.make_dex_engine`` with its Pallas leaf
kernels) through a warm batch, YCSB-A, YCSB-E and an insert batch dense
enough to overflow leaves, whose splits the on-mesh SMO engine settles
(``core/smo.settle_splits``, the ``leaf_split`` kernel).  Every answered
lane is checked against a plain reference that shares no code with the
engine: the sorted numpy keys (value ``key * 7``) plus the writes applied
so far, replayed in the engine's phase order (reads, then updates, then
inserts).  Any mismatch fails the run.

    python chip_smoke.py              # one chip, 1x1 mesh
    python chip_smoke.py --chips 4    # route x memory 2x2 mesh only

Exits non-zero and prints no result unless JAX's first device is a TPU.
The last line of stdout is one JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The compile cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is set
and in ``<repo>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import dex, engine, pool as pool_mod, smo  # noqa: E402
from repro.core.nodes import KEY_MAX  # noqa: E402
from repro.core.partition import LogicalPartitions  # noqa: E402
from repro.core.write import STATUS_MISS, STATUS_OK, STATUS_SPLIT  # noqa: E402
from repro.data import ycsb  # noqa: E402

N_KEYS = 10**8
BATCH = 1024
MAX_COUNT = 128
THETA = 0.99
SCAN_LEN = 100
VALUE_MUL = 7          # the bulk-loaded value of key k is k * VALUE_MUL
WRITE_BASE = 1 << 40   # written values: WRITE_BASE + a per-run sequence
MAX_RETRIES = 4
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
OP_NAMES = {engine.OP_LOOKUP: "lookup", engine.OP_UPDATE: "update",
            engine.OP_INSERT: "insert", engine.OP_SCAN: "scan"}


class SmokeError(RuntimeError):
    """The engine disagreed with the reference or broke a smoke invariant."""


class Reference:
    """The index as a plain model: the sorted bulk-loaded keys plus every
    write applied so far (``over``), and the inserted keys the load did not
    hold (``fresh``, sorted) for scans."""

    def __init__(self, keys: np.ndarray):
        self.keys = keys
        self.over: dict = {}
        self.fresh = np.empty((0,), np.int64)

    def _loaded(self, k: int) -> bool:
        i = int(np.searchsorted(self.keys, k))
        return i < self.keys.size and int(self.keys[i]) == k

    def exists(self, k: int) -> bool:
        return k in self.over or self._loaded(k)

    def value(self, k: int) -> int:
        return self.over[k] if k in self.over else k * VALUE_MUL

    def lookup(self, k: int):
        return (True, self.value(k)) if self.exists(k) else (False, 0)

    def scan(self, q: int, count: int):
        i = int(np.searchsorted(self.keys, q))
        j = int(np.searchsorted(self.fresh, q))
        ks = np.sort(np.concatenate(
            [self.keys[i:i + count], self.fresh[j:j + count]]
        ))[:count]
        return ks, np.array([self.value(int(k)) for k in ks], np.int64)

    def write(self, k: int, v: int) -> None:
        if not self.exists(k):
            self.fresh = np.insert(
                self.fresh, np.searchsorted(self.fresh, k), k
            )
        self.over[k] = v


class _SplitReplay:
    """The host mirror ``smo.settle_splits`` replays applied lanes into; the
    reference takes the same lanes itself, so this only counts them."""

    def __init__(self):
        self.lanes = []

    def insert(self, k: int, v: int) -> None:
        self.lanes.append((k, v))


def check_dispatch(ref, opc, kk, vv, r, answered, tally):
    """Check the answered lanes of one engine dispatch in the engine's phase
    order and apply their writes to ``ref``.  Returns ``(mismatches,
    split)``: ``split`` marks insert lanes left for the SMO engine."""
    bad = []
    lanes = np.nonzero(answered)[0]
    for i in lanes:
        tally[OP_NAMES[int(opc[i])]] += 1
    for i in lanes[opc[lanes] == engine.OP_LOOKUP]:
        k = int(kk[i])
        f, v = ref.lookup(k)
        if bool(r.found[i]) != f or (f and int(r.values[i]) != v):
            bad.append(("lookup", k))
    for i in lanes[opc[lanes] == engine.OP_SCAN]:
        k = int(kk[i])
        ek, ev = ref.scan(k, min(int(vv[i]), MAX_COUNT))
        t = int(r.taken[i])
        if (t != ek.size or not np.array_equal(r.scan_keys[i, :t], ek)
                or not np.array_equal(r.scan_values[i, :t], ev)
                or (r.scan_keys[i, t:] != KEY_MAX).any()):
            bad.append(("scan", k))
    for i in lanes[opc[lanes] == engine.OP_UPDATE]:
        k = int(kk[i])
        hit = ref.exists(k)
        if int(r.status[i]) != (STATUS_OK if hit else STATUS_MISS):
            bad.append(("update", k))
        if hit:
            ref.write(k, int(vv[i]))
    split = np.zeros(kk.shape, bool)
    for i in lanes[opc[lanes] == engine.OP_INSERT]:
        k = int(kk[i])
        status = int(r.status[i])
        if status == STATUS_OK:
            ref.write(k, int(vv[i]))
        elif status == STATUS_SPLIT and not ref.exists(k):
            split[i] = True
        else:
            bad.append(("insert", k))
    return bad, split


def split_batch(keys, per_node, rng, seq):
    """Inserts that overflow leaves: 32 fresh keys into each of 32 leaves
    of the bulk layout (44 of 64 slots filled), lanes shuffled so every
    leaf's inserts arrive from many lanes."""
    n_leaves = keys.size // per_node
    fresh = []
    for leaf in rng.permutation(n_leaves):
        row = keys[leaf * per_node:(leaf + 1) * per_node]
        gaps = np.setdiff1d(np.arange(row[0] + 1, row[-1], dtype=np.int64),
                            row)
        if gaps.size >= 32:
            fresh.append(rng.choice(gaps, 32, replace=False))
        if len(fresh) * 32 == BATCH:
            break
    kk = rng.permutation(np.concatenate(fresh))
    opc = np.full(BATCH, engine.OP_INSERT, np.int32)
    return opc, kk, seq + np.arange(BATCH, dtype=np.int64)


def run(n_chips: int, n_keys: int, seed: int, n_batches: int = 3,
        log=print) -> dict:
    """Load ``n_keys`` keys over the first ``n_chips`` devices and drive the
    engine; returns the run's summary (raises :class:`SmokeError` on any
    disagreement with the reference)."""
    compiles = []

    def on_event(event, duration, **kwargs):
        if event == COMPILE_EVENT:
            compiles.append(kwargs.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        return _drive(n_chips, n_keys, seed, n_batches, log, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _drive(n_chips, n_keys, seed, n_batches, log, compiles) -> dict:
    t_start = time.perf_counter()
    devices = jax.devices()[:n_chips]
    if len(devices) < n_chips:
        raise SmokeError(f"{n_chips} chips asked for, {len(devices)} present")
    mesh = dex.make_dex_mesh(devices)
    cfg = dex.DexMeshConfig(n_route=mesh.shape["data"],
                            n_memory=mesh.shape["model"])

    # ---- data and state -------------------------------------------------
    keys = ycsb.make_dataset(n_keys, seed=seed)
    pool, meta = pool_mod.build_pool_host(keys, keys * VALUE_MUL, level_m=1,
                                          fill=0.7, n_shards=cfg.n_memory)
    bounds = LogicalPartitions.from_samples(
        keys[:: max(1, keys.size // 100_000)], cfg.n_route
    ).boundaries
    shardings = dex.state_shardings(mesh, cfg)
    # the host planes go shard by shard to their devices: no device ever
    # stages the whole pool
    pool = jax.device_put(pool, shardings.pool)
    state = jax.device_put(dex.init_state(pool, meta, cfg, bounds),
                           shardings)
    del pool
    state = jax.block_until_ready(state)
    pool_leaves = jax.tree.leaves(state.pool)
    pool_bytes = sum(s.data.nbytes for a in pool_leaves
                     for s in a.addressable_shards)
    pool_devices = {s.device for s in state.pool.pool_keys.hi.addressable_shards}
    if len(pool_devices) != n_chips:
        raise SmokeError(f"pool shards on {len(pool_devices)} devices, "
                         f"expected {n_chips}")
    log(f"loaded {n_keys} keys: {meta.n_subtrees} subtrees x "
        f"{meta.subtree_cap} nodes, mesh {dict(mesh.shape)}, pool "
        f"{pool_bytes} device bytes on {len(pool_devices)} devices, "
        f"boundaries {bounds.tolist()}")

    # ---- compiled programs: the served kernels must be in them ----------
    lanes = NamedSharding(mesh, P(cfg.all_axes))

    def put(x):
        return jax.device_put(x, lanes)

    step = jax.jit(engine.make_dex_engine(meta, cfg, mesh,
                                          max_count=MAX_COUNT,
                                          use_kernel=True), donate_argnums=0)
    smo_step = jax.jit(smo.make_dex_smo(meta, cfg, mesh, use_kernel=True),
                       donate_argnums=0)

    def smo_round(st, k, v):
        # settle_splits hands over uncommitted device arrays; place them
        # from the host like the lowering below, so every round reuses one
        # executable and no resharding program
        return smo_step(st, put(np.asarray(k)), put(np.asarray(v)))

    kmax = np.full(BATCH, KEY_MAX, np.int64)
    zk = put(kmax)
    zo = put(np.zeros(BATCH, np.int32))
    hlo = {
        "engine": step.lower(state, zo, zk, zk).compile().as_text(),
        "smo": smo_step.lower(state, zk, zk).compile().as_text(),
    }
    custom_calls = {k: v.count("tpu_custom_call") for k, v in hlo.items()}
    log(f"compiled: tpu_custom_call per program {custom_calls}")

    ref = Reference(keys)
    tally = {name: 0 for name in OP_NAMES.values()}
    counts = {"mismatches": 0, "split_lanes": 0, "smo_rounds": 0}
    rng = np.random.default_rng(seed + 1)
    seq = [WRITE_BASE]

    def unique_writes(opc, kk, vv):
        w = (opc == engine.OP_UPDATE) | (opc == engine.OP_INSERT)
        vv = vv.copy()
        vv[w] = seq[0] + np.arange(int(w.sum()), dtype=np.int64)
        seq[0] += int(w.sum())
        return opc, kk, vv

    def run_batch(name, opc, kk, vv):
        nonlocal state
        pending = kk != KEY_MAX
        for _ in range(MAX_RETRIES):
            state, r = step(state, put(np.where(pending, opc, 0)),
                            put(np.where(pending, kk, KEY_MAX)),
                            put(np.where(pending, vv, 0)))
            r = jax.device_get(r)
            answered = pending & ~r.shed
            bad, split = check_dispatch(ref, opc, kk, vv, r, answered, tally)
            counts["mismatches"] += len(bad)
            if bad:
                raise SmokeError(f"{name}: {len(bad)} lanes disagree with "
                                 f"the reference, first {bad[:5]}")
            if split.any():
                settle(name, kk, vv, split)
            pending &= r.shed
            if not pending.any():
                return
        raise SmokeError(f"{name}: {int(pending.sum())} lanes still shed "
                         f"after {MAX_RETRIES} dispatches")

    def settle(name, kk, vv, split):
        nonlocal state
        replay = _SplitReplay()
        state, meta2, info = smo.settle_splits(
            state, meta, cfg, smo_round, replay,
            np.where(split, kk, KEY_MAX), np.where(split, vv, 0), bounds,
        )
        if meta2 is not meta or info["drained"] or info["residual"]:
            raise SmokeError(f"{name}: splits left the device: {info}")
        if not info["onmesh"] == int(split.sum()) == len(replay.lanes):
            raise SmokeError(f"{name}: settled {info} of {int(split.sum())}")
        for i in np.nonzero(split)[0]:
            ref.write(int(kk[i]), int(vv[i]))
        counts["split_lanes"] += int(split.sum())
        counts["smo_rounds"] += info["rounds"]

    def ycsb_batches(mix, n, **kw):
        wl = ycsb.generate(mix, keys, n * BATCH, theta=THETA,
                           seed=int(rng.integers(1 << 30)), **kw)
        for b in range(n):
            yield unique_writes(*ycsb.engine_lanes(wl, b * BATCH,
                                                   (b + 1) * BATCH))

    # ---- warm-up: two engine batches and two no-op SMO rounds compile
    #      every signature the window dispatches (the lowering above filled
    #      the compile cache)
    t_warm = time.perf_counter()
    for opc, kk, vv in ycsb_batches("ycsb-a", 2):
        run_batch("warm", opc, kk, vv)
        state, _ = smo_round(state, kmax, kmax)
        np.asarray(state.stats)  # the settle loop's host reads
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t_start
    compiles_setup = len(compiles)
    log(f"warm-up {time.perf_counter() - t_warm:.3f}s, "
        f"{compiles_setup} compiles in set-up")

    # ---- checked window ---------------------------------------------------
    t_run = time.perf_counter()
    phases = {}
    for name, batches in (
        ("ycsb-a", ycsb_batches("ycsb-a", n_batches)),
        ("ycsb-e", ycsb_batches("ycsb-e", n_batches, scan_len=SCAN_LEN,
                                scan_len_dist="uniform")),
    ):
        t0 = time.perf_counter()
        for opc, kk, vv in batches:
            run_batch(name, opc, kk, vv)
        phases[name] = time.perf_counter() - t0

    t0 = time.perf_counter()
    opc, kk, vv = split_batch(keys, meta.per_node, rng, seq[0])
    seq[0] += BATCH
    splits_before = int(np.asarray(state.stats)[:, dex.STAT_SMO_SPLITS].sum())
    run_batch("split-insert", opc, kk, vv)
    leaf_splits = int(
        np.asarray(state.stats)[:, dex.STAT_SMO_SPLITS].sum()
    ) - splits_before
    if leaf_splits < 1 or counts["split_lanes"] == 0:
        raise SmokeError(f"split-insert settled {leaf_splits} splits over "
                         f"{counts['split_lanes']} lanes")
    # read the split leaves back: lookups of the new keys, scans across
    # each split leaf and its new sibling
    half = BATCH // 2
    opc = np.full(BATCH, engine.OP_LOOKUP, np.int32)
    opc[half:] = engine.OP_SCAN
    probe = kk.copy()
    probe[half:] = np.sort(kk)[:: (BATCH // half)][:half] - 1
    vv = np.zeros(BATCH, np.int64)
    vv[half:] = SCAN_LEN
    run_batch("split-readback", opc, probe, vv)
    phases["split"] = time.perf_counter() - t0
    jax.block_until_ready(state)
    run_s = time.perf_counter() - t_run
    compiles_run = compiles[compiles_setup:]

    peak = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
    ]
    summary = {
        "keys": n_keys,
        "chips": n_chips,
        "mesh": dict(mesh.shape),
        "pool_device_bytes": pool_bytes,
        "peak_bytes_in_use": peak,
        "ops": tally,
        "mismatches": counts["mismatches"],
        "split_lanes": counts["split_lanes"],
        "leaf_splits_on_device": leaf_splits,
        "smo_rounds": counts["smo_rounds"],
        "tpu_custom_calls": custom_calls,
        "compiles_setup": compiles_setup,
        "compiles_run": len(compiles_run),
        "setup_s": setup_s,
        "run_s": run_s,
        "phase_s": phases,
    }
    log(json.dumps(summary))
    if compiles_run:
        raise SmokeError(f"compiles inside the checked window: {compiles_run}")
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev0.platform}",
              file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))

    summary = run(args.chips, N_KEYS, args.seed)
    missing = [k for k, n in summary["tpu_custom_calls"].items() if n == 0]
    if missing:
        raise SmokeError(f"no Pallas kernel compiled into {missing}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": summary["chips"],
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
