"""Multi-device exercise of the Plane-B mesh DEX.  Run as a subprocess by
tests/test_dex_mesh.py so the main pytest session keeps a single device."""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import dex as dex_mod  # noqa: E402
from repro.core import pool as pool_mod  # noqa: E402
from repro.core import scan as scan_mod  # noqa: E402
from repro.core import write as write_mod  # noqa: E402
from repro.compat import make_mesh_compat  # noqa: E402
from repro.core.nodes import FANOUT, KEY_MAX, KEY_MIN  # noqa: E402
from repro.core.sim import HostBTree  # noqa: E402


def main() -> None:
    mesh = make_mesh_compat((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    keys = np.sort(
        rng.choice(1_000_000, size=20_000, replace=False).astype(np.int64) + 1
    )
    vals = keys * 7
    pool, meta = pool_mod.build_pool(keys, vals, level_m=1, fill=0.7, n_shards=4)

    bounds = np.array([KEY_MIN, 500_000, KEY_MAX], dtype=np.int64)
    B = 512
    qk = rng.choice(keys, size=B).astype(np.int64)
    qk[::13] = qk[::13] + 1  # inject misses
    expect = np.isin(qk, keys)

    for policy in ("fetch", "offload", "auto"):
        cfg = dex_mod.DexMeshConfig(
            route_axes=("data",),
            memory_axis="model",
            n_route=2,
            n_memory=4,
            cache_sets=64,
            cache_ways=4,
            policy=policy,
            route_capacity_factor=4.0,
        )
        state = dex_mod.init_state(pool, meta, cfg, bounds)
        state = jax.tree.map(
            lambda x, s: jax.device_put(x, s), state, dex_mod.state_shardings(mesh, cfg)
        )
        qk_dev = jax.device_put(
            jnp.asarray(qk), NamedSharding(mesh, P(("data", "model")))
        )
        lk = jax.jit(dex_mod.make_dex_lookup(meta, cfg, mesh))
        s2, found, values, _ = lk(state, qk_dev)
        found, values = np.asarray(found), np.asarray(values)
        assert (found == expect).all(), f"{policy}: found mismatch"
        assert (values[expect] == qk[expect] * 7).all(), f"{policy}: value mismatch"
        assert int(np.asarray(s2.stats)[:, dex_mod.STAT_DROPS].sum()) == 0
        if policy == "fetch":
            # second batch must produce cache hits
            s3, f3, _, _ = lk(s2, qk_dev)
            hits = int(np.asarray(s3.stats)[:, dex_mod.STAT_HITS].sum())
            assert hits > 0, "no cache hits on repeat batch"
            assert (np.asarray(f3) == expect).all()
        if policy == "offload":
            offs = int(np.asarray(s2.stats)[:, dex_mod.STAT_OFFLOADS].sum())
            assert offs == B, f"expected {B} offloads, got {offs}"

    # ---- batched range scans (core/scan.py) vs HostBTree.scan --------------
    host = HostBTree(keys, vals, fill=0.7)
    cfg = dex_mod.DexMeshConfig(
        route_axes=("data",),
        memory_axis="model",
        n_route=2,
        n_memory=4,
        cache_sets=64,
        cache_ways=4,
        route_capacity_factor=4.0,
    )
    state = dex_mod.init_state(pool, meta, cfg, bounds)
    state = jax.tree.map(
        lambda x, s: jax.device_put(x, s), state, dex_mod.state_shardings(mesh, cfg)
    )
    MC = 64
    scan = jax.jit(scan_mod.make_dex_scan(meta, cfg, mesh, max_count=MC))
    BS = 512
    starts = rng.choice(keys, size=BS).astype(np.int64)
    starts[::7] = starts[::7] + 1               # start keys not in the index
    starts[0] = keys[-1] + 100                  # empty-result scan
    # scans straddling the partition boundary at 500_000
    below = keys[(keys > 480_000) & (keys < 500_000)]
    starts[1 : 1 + min(8, below.size)] = below[-8:]
    counts = rng.integers(1, MC + 1, size=BS).astype(np.int64)
    counts[2] = 0
    sharding = NamedSharding(mesh, P(("data", "model")))
    s_scan, out_k, out_v, taken = scan(
        state,
        jax.device_put(jnp.asarray(starts), sharding),
        jax.device_put(jnp.asarray(counts), sharding),
    )
    out_k, out_v, taken = np.asarray(out_k), np.asarray(out_v), np.asarray(taken)
    for i in range(BS):
        expect_keys = [
            k for _, ks in host.scan(int(starts[i]), int(counts[i])) for k in ks
        ][: int(counts[i])] if counts[i] > 0 else []
        got = out_k[i][out_k[i] != KEY_MAX].tolist()
        assert got == expect_keys, f"scan {i}: {got[:4]} != {expect_keys[:4]}"
        assert int(taken[i]) == len(expect_keys), f"scan {i}: taken mismatch"
        assert (out_v[i][: len(expect_keys)]
                == np.asarray(expect_keys, np.int64) * 7).all(), f"scan {i}: values"
    assert int(np.asarray(s_scan.stats)[:, dex_mod.STAT_DROPS].sum()) == 0
    # repeat batch must hit the warmed cache
    s_scan2, k2, _, t2 = scan(
        s_scan,
        jax.device_put(jnp.asarray(starts), sharding),
        jax.device_put(jnp.asarray(counts), sharding),
    )
    np.testing.assert_array_equal(np.asarray(k2), out_k)
    np.testing.assert_array_equal(np.asarray(t2), taken)
    d_hits = (np.asarray(s_scan2.stats)[:, dex_mod.STAT_HITS].sum()
              - np.asarray(s_scan.stats)[:, dex_mod.STAT_HITS].sum())
    assert d_hits > 0, "no cache hits on repeat scan batch"

    # ---- batched writes (core/write.py): update/insert across 2 route ----
    # partitions x 4 memory columns, with cross-partition stale-cache
    # rejection via the per-leaf version table
    cfg_w = dex_mod.DexMeshConfig(
        route_axes=("data",),
        memory_axis="model",
        n_route=2,
        n_memory=4,
        cache_sets=256,
        cache_ways=4,
        policy="fetch",
        p_admit_leaf_pct=100,   # make every leaf cacheable: the staleness
                                # check below needs rows cached on all chips
        route_capacity_factor=4.0,
    )
    host_w = HostBTree(keys, vals, fill=0.7)
    state = dex_mod.init_state(pool, meta, cfg_w, bounds)
    state = jax.tree.map(
        lambda x, s: jax.device_put(x, s), state,
        dex_mod.state_shardings(mesh, cfg_w)
    )
    lk = jax.jit(dex_mod.make_dex_lookup(meta, cfg_w, mesh))
    up = jax.jit(write_mod.make_dex_update(meta, cfg_w, mesh))
    ins = jax.jit(write_mod.make_dex_insert(meta, cfg_w, mesh))
    scan_w = jax.jit(scan_mod.make_dex_scan(meta, cfg_w, mesh, max_count=MC))

    BW = 512
    # scans crossing the partition boundary cache partition-1 leaves on
    # chips of BOTH route rows (start below 500_000, scan across)
    below = keys[(keys > 480_000) & (keys < 500_000)]
    sk = np.concatenate([below[-BW // 2:],
                         rng.choice(keys, size=BW - min(BW // 2, below.size))])
    sk = sk[:BW].astype(np.int64)
    counts = np.full(BW, MC, np.int64)
    state, pre_k, pre_v, pre_t = scan_w(
        state,
        jax.device_put(jnp.asarray(sk), sharding),
        jax.device_put(jnp.asarray(counts), sharding),
    )
    jax.block_until_ready(pre_t)

    # duplicate writers of the same keys land on different source chips;
    # batch-priority conflict resolution must make the last lane win
    wk = rng.choice(keys, size=BW).astype(np.int64)
    wk[: BW // 4] = wk[BW // 4 : BW // 2]   # cross-chip duplicate writers
    wv = rng.integers(0, 1 << 40, size=BW).astype(np.int64)
    state, res = up(
        state,
        jax.device_put(jnp.asarray(wk), sharding),
        jax.device_put(jnp.asarray(wv), sharding),
    )
    res = np.asarray(res)
    assert (res == write_mod.STATUS_OK).all(), "update lanes failed"
    for k, v in zip(wk, wv):
        host_w.update(int(k), int(v))

    # lookups (all chips) must see the new values — any chip still holding
    # the pre-update row must reject it via the version check
    s2, f2, v2, _ = lk(
        state, jax.device_put(jnp.asarray(wk), sharding)
    )
    f2, v2 = np.asarray(f2), np.asarray(v2)
    assert f2.all(), "updated keys must be found"
    for i in range(BW):
        assert int(v2[i]) == host_w.get(int(wk[i])), f"stale value at {i}"
    state = s2

    # scans from the *other* partition over the written leaves must also
    # see fresh values (their cached copies are version-stale)
    state, k3, v3, t3 = scan_w(
        state,
        jax.device_put(jnp.asarray(sk), sharding),
        jax.device_put(jnp.asarray(counts), sharding),
    )
    k3, v3, t3 = np.asarray(k3), np.asarray(v3), np.asarray(t3)
    for i in range(BW):
        if t3[i] < 0:
            continue
        expect = [kk for _, ks in host_w.scan(int(sk[i]), int(counts[i]))
                  for kk in ks][: int(counts[i])]
        got = k3[i][k3[i] != KEY_MAX].tolist()
        assert got == expect, f"post-write scan keys diverge at {i}"
        for j, kk in enumerate(expect):
            assert int(v3[i, j]) == host_w.get(int(kk)), (
                f"post-write scan value stale at {i},{j}"
            )

    # inserts: fresh keys spread over both partitions; applied on the mesh,
    # shed leaves replayed via the host SMO path
    ik = (rng.choice(keys[:-1], size=BW) + 1).astype(np.int64)
    ik = np.unique(ik[~np.isin(ik, keys)])
    ik = ik[: (ik.size // 8) * 8]
    iv = ik * 3
    meta_w = meta
    state, ri = ins(
        state,
        jax.device_put(jnp.asarray(ik), sharding),
        jax.device_put(jnp.asarray(iv), sharding),
    )
    ri = np.asarray(ri)
    assert (ri != write_mod.STATUS_SHED).all()
    for k, v, r in zip(ik, iv, ri):
        if r == write_mod.STATUS_OK:
            host_w.insert(int(k), int(v))
    shed = ri == write_mod.STATUS_SPLIT
    if shed.any():
        state, meta_w = write_mod.drain_splits(
            state, meta, cfg_w, host_w, ik[shed], iv[shed], bounds
        )
        state = jax.tree.map(
            lambda x, s: jax.device_put(x, s), state,
            dex_mod.state_shardings(mesh, cfg_w)
        )
        lk = jax.jit(dex_mod.make_dex_lookup(meta_w, cfg_w, mesh))
    s4, f4, v4, _ = lk(
        state, jax.device_put(jnp.asarray(ik[: (ik.size // 8) * 8]), sharding)
    )
    f4, v4 = np.asarray(f4), np.asarray(v4)
    probe = ik[: (ik.size // 8) * 8]
    for i in range(probe.size):
        hv = host_w.get(int(probe[i]))
        assert bool(f4[i]) == (hv is not None), f"insert missing at {i}"
        if hv is not None:
            assert int(v4[i]) == hv, f"insert value wrong at {i}"

    # ---- on-mesh SMO engine (core/smo.py): 8-device split round trip -----
    # leaf overflows on two different memory columns split device-side; the
    # split leaf/sibling/parent versions bump (poisoned stale cached rows
    # must be rejected) while every other warm row survives untouched — no
    # global version reset, no pool rebuild
    from repro.core import smo as smo_mod  # noqa: E402

    cfg_m = dex_mod.DexMeshConfig(
        route_axes=("data",),
        memory_axis="model",
        n_route=2,
        n_memory=4,
        cache_sets=256,
        cache_ways=4,
        policy="fetch",
        p_admit_leaf_pct=100,   # deterministic warm rows for the poison check
        route_capacity_factor=4.0,
    )
    host_m = HostBTree(keys, vals, fill=0.7)
    state = dex_mod.init_state(pool, meta, cfg_m, bounds)
    shardings_m = dex_mod.state_shardings(mesh, cfg_m)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, shardings_m)
    lk_m = jax.jit(dex_mod.make_dex_lookup(meta, cfg_m, mesh))
    ins_m = jax.jit(write_mod.make_dex_insert(meta, cfg_m, mesh))
    scan_m = jax.jit(scan_mod.make_dex_scan(meta, cfg_m, mesh, max_count=MC))
    smo_m = jax.jit(smo_mod.make_dex_smo(meta, cfg_m, mesh))

    def put_m(x):
        return jax.device_put(jnp.asarray(x), sharding)

    def pad512(x):
        return np.concatenate(
            [x, np.full(512 - x.size, KEY_MAX, np.int64)]
        )

    # warm rows far from the burst regions on every chip (both partitions)
    far = np.concatenate([keys[2000:2256], keys[-256:]]).astype(np.int64)
    state, f_far, v_far, _ = lk_m(state, put_m(far))
    assert bool(np.asarray(f_far).all())
    # warm the to-be-split leaves too, so stale copies exist to poison
    near = pad512(np.concatenate([keys[:32], keys[-40:-8]]).astype(np.int64))
    state, _, _, _ = lk_m(state, put_m(near))

    # overflow bursts on two memory columns: around the smallest keys
    # (partition 0 / column 0) and the largest (partition 1 / last column)
    b_lo = np.arange(int(keys[0]) + 1, int(keys[0]) + 1 + FANOUT, dtype=np.int64)
    b_lo = b_lo[~np.isin(b_lo, keys)][: FANOUT - 8]
    b_hi = np.arange(int(keys[-2]) + 1, int(keys[-2]) + 1 + FANOUT,
                     dtype=np.int64)
    b_hi = b_hi[~np.isin(b_hi, keys)][: FANOUT - 8]
    burst = pad512(np.concatenate([b_lo, b_hi]))
    bvals = np.where(burst != KEY_MAX, burst * 3, 0)
    state, ri_m = ins_m(state, put_m(burst), put_m(bvals))
    ri_m = np.asarray(ri_m)
    live_b = burst != KEY_MAX
    for kk, rr in zip(burst[live_b], ri_m[live_b]):
        if rr == write_mod.STATUS_OK:
            host_m.insert(int(kk), int(kk) * 3)
    shed_m = live_b & (ri_m == write_mod.STATUS_SPLIT)
    assert shed_m.sum() > 0, "bursts must overflow their leaves"
    state, meta_m, info = smo_mod.settle_splits(
        state, meta, cfg_m, smo_m, host_m,
        np.where(shed_m, burst, KEY_MAX), np.where(shed_m, bvals, 0), bounds,
    )
    assert meta_m is meta, "on-mesh SMO must not rebuild the pool"
    assert not info["drained"] and info["residual"] == 0
    assert info["onmesh"] == int(shed_m.sum())
    stats_m = np.asarray(state.stats).sum(axis=0)
    assert int(stats_m[dex_mod.STAT_SMO_SPLITS]) >= 2  # one per column
    assert int(stats_m[dex_mod.STAT_DRAINS]) == 0

    # surgical invalidation: only the split leaves + siblings + ancestors
    # bumped; every cached copy of a bumped node is poisoned on every chip
    # and must be re-fetched, never served
    vers_m = np.asarray(state.versions)
    assert (vers_m == vers_m[:1]).all(), "version table must be pmax-synced"
    bumped = np.where(vers_m[0] > 0)[0]
    assert 0 < bumped.size <= 8 * meta.levels_in_subtree, bumped.size
    tags_m = np.asarray(state.cache.tags)
    hitm = np.isin(tags_m, bumped)
    assert hitm.any(), "warm caches must hold a stale copy of a split node"
    pois = np.asarray(state.cache.values).copy()
    pois[hitm] = -424242
    state = state._replace(cache=state.cache._replace(
        values=jax.device_put(jnp.asarray(pois), shardings_m.cache.values)
    ))
    probe = pad512(np.concatenate([b_lo, b_hi, keys[:16], keys[-16:]]))
    state, f_p, v_p, _ = lk_m(state, put_m(probe))
    f_p, v_p = np.asarray(f_p), np.asarray(v_p)
    for i in np.where(probe != KEY_MAX)[0]:
        hv = host_m.get(int(probe[i]))
        assert bool(f_p[i]) == (hv is not None), f"smo lookup {i}"
        if hv is not None:
            assert int(v_p[i]) == hv, f"poisoned stale row served at {i}"

    # unmoved warm rows survive the splits: the far probe repeats entirely
    # from cache (hits grow by at least the batch) with identical results
    before_m = np.asarray(state.stats).sum(axis=0)
    state, f_far2, v_far2, _ = lk_m(state, put_m(far))
    after_m = np.asarray(state.stats).sum(axis=0)
    np.testing.assert_array_equal(np.asarray(f_far2), np.asarray(f_far))
    np.testing.assert_array_equal(np.asarray(v_far2), np.asarray(v_far))
    assert (
        after_m[dex_mod.STAT_HITS] - before_m[dex_mod.STAT_HITS]
        >= far.size
    ), "far-region cached rows must survive an on-mesh split"

    # scans across both split leaves follow the successor chain (multi-hop
    # across the relocated sibling) and stay bit-identical to the host
    starts_m = pad512(np.array(
        [int(keys[0]), int(b_lo[0]), int(keys[-2]), int(b_hi[0])], np.int64
    ))
    cnts_m = np.where(starts_m != KEY_MAX, 48, 0).astype(np.int64)
    state, sk_m, sv_m, tk_m = scan_m(state, put_m(starts_m), put_m(cnts_m))
    sk_m, sv_m, tk_m = np.asarray(sk_m), np.asarray(sv_m), np.asarray(tk_m)
    for i in np.where(starts_m != KEY_MAX)[0]:
        expect = [
            kk for _, ks in host_m.scan(int(starts_m[i]), int(cnts_m[i]))
            for kk in ks
        ][: int(cnts_m[i])]
        got = sk_m[i][sk_m[i] != KEY_MAX].tolist()
        assert got == expect, f"post-split scan diverges at {i}"
        for j, kk in enumerate(expect):
            assert int(sv_m[i, j]) == host_m.get(int(kk)), (i, j)

    # ---- mixed-batch coherence (core/engine.py): an update and an insert
    # of the SAME leaf land in ONE engine batch from different source
    # chips.  The updater's chip must not keep a version-fresh cached row
    # whose keys plane misses the insert — the engine skips the
    # write-through refresh for leaves that took same-batch inserts, so
    # the stale row fails the version check and refetches.
    from repro.core import engine as engine_mod  # noqa: E402

    cfg_e = dex_mod.DexMeshConfig(
        route_axes=("data",),
        memory_axis="model",
        n_route=2,
        n_memory=4,
        cache_sets=256,
        cache_ways=4,
        policy="fetch",
        p_admit_leaf_pct=100,   # the warm lookup must cache the leaf
        route_capacity_factor=4.0,
    )
    host_e = HostBTree(keys, vals, fill=0.7)
    state = dex_mod.init_state(pool, meta, cfg_e, bounds)
    state = jax.tree.map(
        lambda x, s: jax.device_put(x, s), state,
        dex_mod.state_shardings(mesh, cfg_e)
    )
    eng_e = jax.jit(engine_mod.make_dex_engine(
        meta, cfg_e, mesh, ops=("lookup", "update", "insert"), max_count=1
    ))
    lk_e = jax.jit(dex_mod.make_dex_lookup(meta, cfg_e, mesh))

    def put_e(x):
        return jax.device_put(jnp.asarray(x), sharding)

    # an existing key and a fresh key guaranteed to share its leaf
    j = 5000
    while keys[j] + 1 >= keys[j + 1]:  # need a gap right above keys[j]
        j += 1
    k_upd = int(keys[j])
    k_ins = k_upd + 1
    # warm: every chip serves (and caches, P_A=100%) the target leaf
    warm_e = np.full(512, k_upd, np.int64)
    state, f_w, _, _ = lk_e(state, put_e(warm_e))
    assert bool(np.asarray(f_w).all())
    # one mixed batch: the update sources on chip 0, the insert on the
    # last chip (lane // 64 is the source device on the 8-device mesh)
    opc_e = np.zeros(512, np.int32)
    kk_e = np.full(512, KEY_MAX, np.int64)
    vv_e = np.zeros(512, np.int64)
    opc_e[3], kk_e[3], vv_e[3] = engine_mod.OP_UPDATE, k_upd, 777
    opc_e[460], kk_e[460], vv_e[460] = engine_mod.OP_INSERT, k_ins, 999
    state, r_e = eng_e(state, put_e(opc_e), put_e(kk_e), put_e(vv_e))
    st_e = np.asarray(r_e.status)
    assert st_e[3] == write_mod.STATUS_OK, st_e[3]
    assert st_e[460] == write_mod.STATUS_OK, st_e[460]
    host_e.update(k_upd, 777)
    host_e.insert(k_ins, 999)
    # lookups of both keys from EVERY chip must match the host: a chip
    # still serving a version-fresh pre-insert keys plane would miss k_ins
    probe_e = np.tile(np.array([k_upd, k_ins], np.int64), 256)
    state, f_e, v_e, _ = lk_e(state, put_e(probe_e))
    f_e, v_e = np.asarray(f_e), np.asarray(v_e)
    assert f_e.all(), "mixed-batch insert invisible on some chip"
    for i in range(512):
        assert int(v_e[i]) == host_e.get(int(probe_e[i])), (
            f"mixed-batch stale cached row served at lane {i}"
        )

    # ---- pipelined round trip (core/engine.py, pipeline=True) -----------
    # the same mixed traffic streamed through the continuous double-
    # buffered service on the 8-device mesh must be lane-for-lane
    # identical to the batch-synchronous engine AND to a phased HostBTree
    # replay — including deliberate cross-batch same-leaf conflicts
    # (even batches update the hot keys that odd batches read), which the
    # version check turns into counted two-sided stalls, never stale
    # answers.
    def fresh_state_e():
        st = dex_mod.init_state(pool, meta, cfg_e, bounds)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, s), st,
            dex_mod.state_shardings(mesh, cfg_e)
        )

    NB, BP = 4, 512
    hot_p = keys[1000:1008].astype(np.int64)
    hot_lanes = np.arange(8) * (BP // 8) + 7    # one hot lane per chip
    fresh_p = np.unique(
        (rng.choice(keys[:-1], size=8 * NB * BP) + 1).astype(np.int64)
    )
    fresh_p = fresh_p[~np.isin(fresh_p, keys)]
    batches_p, fi = [], 0
    for bi in range(NB):
        pick = rng.integers(0, 3, size=BP)
        opc = np.where(
            pick == 0, engine_mod.OP_LOOKUP,
            np.where(pick == 1, engine_mod.OP_UPDATE, engine_mod.OP_INSERT),
        ).astype(np.int32)
        kk = np.empty(BP, np.int64)
        # disjoint key regions keep the host replay order-free: a lookup
        # never races a same-batch write and write keys are batch-unique
        kk[pick == 0] = rng.choice(
            keys[12_000:16_000], size=int((pick == 0).sum())
        )
        kk[pick == 1] = rng.choice(
            keys[8_000:12_000], size=int((pick == 1).sum()), replace=False
        )
        n_ins = int((pick == 2).sum())
        kk[pick == 2] = fresh_p[fi : fi + n_ins]
        fi += n_ins
        vv = rng.integers(1, 1 << 40, size=BP).astype(np.int64)
        if bi % 2 == 0:
            opc[hot_lanes] = engine_mod.OP_UPDATE
            kk[hot_lanes] = hot_p
            vv[hot_lanes] = hot_p ^ (1000 + bi)
        else:
            opc[hot_lanes] = engine_mod.OP_LOOKUP
            kk[hot_lanes] = hot_p
        batches_p.append((opc, kk, vv))

    st_s = fresh_state_e()
    res_s = []
    for opc, kk, vv in batches_p:
        st_s, r = eng_e(st_s, put_e(opc), put_e(kk), put_e(vv))
        res_s.append(jax.tree.map(np.asarray, r))

    pipe_m = engine_mod.make_dex_engine(
        meta, cfg_e, mesh, ops=("lookup", "update", "insert"), max_count=1,
        pipeline=True,
    )
    assert pipe_m.plan["pipeline"] is True
    assert pipe_m.plan["overlap_phases"] == ("pipe/front", "pipe/back")
    st_p, res_p = pipe_m.run(
        fresh_state_e(),
        [(put_e(o), put_e(k), put_e(v)) for o, k, v in batches_p],
    )
    assert len(res_p) == NB
    res_p = [jax.tree.map(np.asarray, r) for r in res_p]

    host_p = HostBTree(keys, vals, fill=0.7)
    for bi, ((opc, kk, vv), rs, rp) in enumerate(
        zip(batches_p, res_s, res_p)
    ):
        for name in ("found", "values", "status", "shed"):
            np.testing.assert_array_equal(
                getattr(rs, name), getattr(rp, name),
                err_msg=f"pipelined batch {bi} diverges on {name}",
            )
        assert not rs.shed.any(), f"batch {bi} shed under factor-4 capacity"
        for i in np.where(opc == engine_mod.OP_LOOKUP)[0]:
            hv = host_p.get(int(kk[i]))
            assert bool(rs.found[i]) == (hv is not None), (bi, i)
            if hv is not None:
                assert int(rs.values[i]) == hv, (
                    f"stale value served at batch {bi} lane {i}"
                )
        for i in np.where(opc == engine_mod.OP_UPDATE)[0]:
            assert rs.status[i] == write_mod.STATUS_OK, (bi, i)
            host_p.update(int(kk[i]), int(vv[i]))
        for i in np.where(opc == engine_mod.OP_INSERT)[0]:
            assert rs.status[i] != write_mod.STATUS_SHED, (bi, i)
            if rs.status[i] == write_mod.STATUS_OK:
                host_p.insert(int(kk[i]), int(vv[i]))
    # the drained pipeline index IS the synchronous one, bit for bit
    np.testing.assert_array_equal(
        np.asarray(st_s.pool.pool_keys), np.asarray(st_p.pool.pool_keys)
    )
    np.testing.assert_array_equal(
        np.asarray(st_s.pool.pool_values), np.asarray(st_p.pool.pool_values)
    )
    np.testing.assert_array_equal(
        np.asarray(st_s.versions), np.asarray(st_p.versions)
    )
    np.testing.assert_array_equal(
        np.asarray(st_s.occupancy), np.asarray(st_p.occupancy)
    )
    stalls_p = int(np.asarray(st_p.stats)[:, dex_mod.STAT_PIPE_STALLS].sum())
    stalls_s = int(np.asarray(st_s.stats)[:, dex_mod.STAT_PIPE_STALLS].sum())
    assert stalls_s == 0, "synchronous engine must never count pipe stalls"
    assert stalls_p > 0, "hot cross-batch writers must stall in the window"

    # ---- forced-offload round trip (policy="offload"): ALL op types ------
    # through the two-sided path on 8 devices — every lookup/update/insert
    # lane ships a tagged message in the engine's fused round and the
    # owning memory column walks its own block; scans stay one-sided (§7:
    # scans never offload).  Results must match a HostBTree replay and the
    # offloaded writes must be visible to offloaded lookups (version bumps
    # travel back through the fused responses).
    cfg_o = dex_mod.DexMeshConfig(
        route_axes=("data",),
        memory_axis="model",
        n_route=2,
        n_memory=4,
        cache_sets=256,
        cache_ways=4,
        policy="offload",
        route_capacity_factor=4.0,
    )
    host_o = HostBTree(keys, vals, fill=0.7)
    state = dex_mod.init_state(pool, meta, cfg_o, bounds)
    state = jax.tree.map(
        lambda x, s: jax.device_put(x, s), state,
        dex_mod.state_shardings(mesh, cfg_o)
    )
    lk_o = jax.jit(dex_mod.make_dex_lookup(meta, cfg_o, mesh))
    up_o = jax.jit(write_mod.make_dex_update(meta, cfg_o, mesh))
    ins_o = jax.jit(write_mod.make_dex_insert(meta, cfg_o, mesh))
    scan_o = jax.jit(scan_mod.make_dex_scan(meta, cfg_o, mesh, max_count=MC))

    def put_o(x):
        return jax.device_put(jnp.asarray(x), sharding)

    BO = 512
    qo = rng.choice(keys, size=BO).astype(np.int64)
    qo[::11] = qo[::11] + 1                     # misses through the RPC too
    state, f_o, v_o, sh_o = lk_o(state, put_o(qo))
    f_o, v_o, sh_o = np.asarray(f_o), np.asarray(v_o), np.asarray(sh_o)
    assert not sh_o.any()
    exp_o = np.isin(qo, keys)
    assert (f_o == exp_o).all(), "offloaded lookup found mismatch"
    assert (v_o[exp_o] == qo[exp_o] * 7).all(), "offloaded lookup values"

    uk_o = rng.choice(keys, size=BO).astype(np.int64)
    uk_o[: BO // 4] = uk_o[BO // 4 : BO // 2]   # cross-chip duplicate writers
    uv_o = rng.integers(0, 1 << 40, size=BO).astype(np.int64)
    state, ru_o = up_o(state, put_o(uk_o), put_o(uv_o))
    ru_o = np.asarray(ru_o)
    assert (ru_o == write_mod.STATUS_OK).all(), "offloaded updates failed"
    for k, v in zip(uk_o, uv_o):
        host_o.update(int(k), int(v))
    state, f_u, v_u, _ = lk_o(state, put_o(uk_o))
    f_u, v_u = np.asarray(f_u), np.asarray(v_u)
    assert f_u.all()
    for i in range(BO):
        assert int(v_u[i]) == host_o.get(int(uk_o[i])), (
            f"offloaded update not visible at {i}"
        )

    io = (rng.choice(keys[:-1], size=BO) + 1).astype(np.int64)
    io = np.unique(io[~np.isin(io, keys)])
    io = io[: (io.size // 8) * 8]
    state, ri_o = ins_o(state, put_o(io), put_o(io * 13))
    ri_o = np.asarray(ri_o)
    assert (ri_o != write_mod.STATUS_SHED).all()
    for k, r in zip(io, ri_o):
        if r == write_mod.STATUS_OK:
            host_o.insert(int(k), int(k) * 13)
    # the SMO fallback rule: an offloaded insert that would split sheds
    # STATUS_SPLIT exactly like a fetched-path one (settled between batches)
    meta_o = meta
    shed_o = ri_o == write_mod.STATUS_SPLIT
    if shed_o.any():
        state, meta_o = write_mod.drain_splits(
            state, meta, cfg_o, host_o, io[shed_o], io[shed_o] * 13, bounds
        )
        state = jax.tree.map(
            lambda x, s: jax.device_put(x, s), state,
            dex_mod.state_shardings(mesh, cfg_o)
        )
        lk_o = jax.jit(dex_mod.make_dex_lookup(meta_o, cfg_o, mesh))
        scan_o = jax.jit(
            scan_mod.make_dex_scan(meta_o, cfg_o, mesh, max_count=MC)
        )
    state, f_i, v_i, _ = lk_o(state, put_o(io))
    f_i, v_i = np.asarray(f_i), np.asarray(v_i)
    for i in range(io.size):
        hv = host_o.get(int(io[i]))
        assert bool(f_i[i]) == (hv is not None), f"offloaded insert at {i}"
        if hv is not None:
            assert int(v_i[i]) == hv, f"offloaded insert value at {i}"

    # scans under the offload policy still run the one-sided path
    so = rng.choice(keys, size=BO).astype(np.int64)
    sc = np.full(BO, 24, np.int64)
    state, sk_o, sv_o, tk_o = scan_o(state, put_o(so), put_o(sc))
    sk_o, sv_o, tk_o = np.asarray(sk_o), np.asarray(sv_o), np.asarray(tk_o)
    for i in range(BO):
        if tk_o[i] < 0:
            continue
        exp = [kk for _, ks in host_o.scan(int(so[i]), 24) for kk in ks][:24]
        got = sk_o[i][sk_o[i] != KEY_MAX].tolist()
        assert got == exp, f"offload-policy scan diverges at {i}"
    stats_o = np.asarray(state.stats).sum(axis=0)
    n_off = int(stats_o[dex_mod.STAT_OFFLOADS])
    assert n_off > 0, "forced-offload must count offloaded messages"
    # every live lookup/update/insert lane went two-sided
    assert n_off >= BO + BO + io.size, (n_off, BO, io.size)
    assert int(stats_o[dex_mod.STAT_OFFLOAD_GROUPS]) > 0
    assert int(stats_o[dex_mod.STAT_FETCH_GROUPS]) == 0

    # ---- live logical repartitioning round trip (core/repartition.py) ----
    # a skewed batch sheds load under tight buckets; the controller moves
    # the boundary, results stay identical, drops strictly fall, and
    # version-stale cached rows of moved nodes are rejected, never served
    from repro.core.partition import LogicalPartitions  # noqa: E402
    from repro.core.repartition import (  # noqa: E402
        RepartitionConfig,
        RepartitionController,
        moved_intervals,
        node_key_ranges,
    )

    cfg_r = dex_mod.DexMeshConfig(
        route_axes=("data",),
        memory_axis="model",
        n_route=2,
        n_memory=4,
        cache_sets=256,
        cache_ways=4,
        policy="fetch",
        p_admit_leaf_pct=100,       # deterministic cache warm for the
                                    # stale-row poisoning check below
        route_capacity_factor=1.25,  # tight: skew must shed
    )
    state = dex_mod.init_state(pool, meta, cfg_r, bounds)
    state = jax.tree.map(
        lambda x, s: jax.device_put(x, s), state,
        dex_mod.state_shardings(mesh, cfg_r)
    )
    lkr = jax.jit(dex_mod.make_dex_lookup(meta, cfg_r, mesh))
    scan_r = jax.jit(scan_mod.make_dex_scan(meta, cfg_r, mesh, max_count=MC))

    BR = 512
    low = keys[keys < 500_000]
    qs = rng.choice(low, size=BR).astype(np.int64)   # all -> partition 0
    qs_dev = jax.device_put(jnp.asarray(qs), sharding)
    cnts = np.full(BR, 16, np.int64)
    cnts_dev = jax.device_put(jnp.asarray(cnts), sharding)

    def drops_of(st):
        return int(np.asarray(st.stats)[:, dex_mod.STAT_DROPS].sum())

    def fetches_of(st):
        return int(np.asarray(st.stats)[:, dex_mod.STAT_FETCHES].sum())

    s1, f1, v1, sh1 = lkr(state, qs_dev)
    f1, v1, sh1 = np.asarray(f1), np.asarray(v1), np.asarray(sh1)
    drops_skew = drops_of(s1)
    assert drops_skew > 0, "tight buckets under full skew must shed"
    assert f1[~sh1].all() and (v1[~sh1] == qs[~sh1] * 7).all()
    # warm repeat (also routes to partition 0; caches now hold the rows)
    s2, _, _, _ = lkr(s1, qs_dev)
    s2s, pre_k, pre_v, pre_t = scan_r(s2, qs_dev, cnts_dev)
    pre_k, pre_v, pre_t = np.asarray(pre_k), np.asarray(pre_v), np.asarray(pre_t)
    s2 = s2s

    ctl = RepartitionController(
        LogicalPartitions(bounds), n_memory=cfg_r.n_memory,
        cfg=RepartitionConfig(imbalance_threshold=1.2, min_ops=BR,
                              cooldown_batches=0),
    )
    ctl.observe(np.asarray(s2.stats), qs,
                demand=np.asarray(s2.route_demand))
    s3, report = ctl.maybe_repartition(s2, meta)
    assert report is not None, "skewed load must trigger a repartition"
    newp = LogicalPartitions(report.new_boundaries)
    assert newp.num_partitions == 2, "server count is fixed"
    assert report.nodes_invalidated > 0
    assert int(report.new_boundaries[1]) < 500_000  # boundary chased skew

    # poison every cached copy of a moved node on every chip: if the
    # version bump failed to invalidate them, lookups would serve garbage
    gids_all, lo_all, hi_all = node_key_ranges(
        pool_mod.keys_i64(state.pool), meta,
        np.asarray(state.pool.pool_children),
    )
    affected = np.zeros(gids_all.shape, bool)
    for a, b2 in moved_intervals(LogicalPartitions(bounds), newp):
        affected |= (lo_all.astype(object) < b2) & (hi_all.astype(object) > a)
    moved_gids = gids_all[affected]
    tags = np.asarray(s3.cache.tags)
    poisoned_vals = np.asarray(s3.cache.values).copy()
    hitmask = np.isin(tags, moved_gids)
    assert hitmask.any(), "warm caches must hold some moved rows"
    poisoned_vals[hitmask] = -12345
    s3 = s3._replace(cache=s3.cache._replace(
        values=jax.device_put(
            jnp.asarray(poisoned_vals),
            dex_mod.state_shardings(mesh, cfg_r).cache.values,
        )
    ))

    fetches_before = fetches_of(s3)
    s4r, f4r, v4r, sh4 = lkr(s3, qs_dev)
    f4r, v4r, sh4 = np.asarray(f4r), np.asarray(v4r), np.asarray(sh4)
    drops_after = drops_of(s4r) - drops_of(s3)
    assert drops_after < drops_skew, (
        f"repartitioning must strictly reduce drops: {drops_after} vs "
        f"{drops_skew}"
    )
    # identical results before/after the mid-stream boundary change
    both = ~sh1 & ~sh4
    assert (f4r[both] == f1[both]).all(), "found flipped across repartition"
    assert (v4r[both] == v1[both]).all(), "values drifted across repartition"
    assert f4r[~sh4].all() and (v4r[~sh4] == qs[~sh4] * 7).all(), (
        "stale cached rows of moved nodes were served"
    )
    assert fetches_of(s4r) > fetches_before, (
        "moved rows must re-fetch (version-stale), not serve from cache"
    )
    # scans across the moved boundary replay identically too
    s5, post_k, post_v, post_t = scan_r(s4r, qs_dev, cnts_dev)
    post_k, post_v, post_t = (
        np.asarray(post_k), np.asarray(post_v), np.asarray(post_t)
    )
    ok_scan = (pre_t >= 0) & (post_t >= 0)
    assert ok_scan.any()
    np.testing.assert_array_equal(post_k[ok_scan], pre_k[ok_scan])
    np.testing.assert_array_equal(post_v[ok_scan], pre_v[ok_scan])
    np.testing.assert_array_equal(post_t[ok_scan], pre_t[ok_scan])

    # ---- cooperative fleet caching (core/fleet_cache.py): 8-device -------
    # peer-peek round trip.  Under the divergent policy each chip's leaf
    # admission skews toward its own memory column's subtrees, so the four
    # siblings of a route row specialise on disjoint quarters of the hot
    # set; a local leaf miss for a foreign column is answered from the
    # sibling specialist's cache via a MSG_PEEK lane riding the engine's
    # existing fused all_to_all.  Then every cached row fleet-wide is
    # poisoned and version-bumped: a stale peer row must FAIL the peek's
    # version check (counted as a peer miss, answered by the owner's block
    # walk) — never served.
    from repro.core import fleet_cache  # noqa: E402

    cfg_f = dex_mod.DexMeshConfig(
        route_axes=("data",),
        memory_axis="model",
        n_route=2,
        n_memory=4,
        cache_sets=128,
        cache_ways=4,
        policy="fetch",
        p_admit_leaf_pct=50,
        route_capacity_factor=4.0,
    )
    pol_f = fleet_cache.divergent_policy(cfg_f, peek_budget=512)
    shardings_f = dex_mod.state_shardings(mesh, cfg_f)
    state = dex_mod.init_state(pool, meta, cfg_f, bounds)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, shardings_f)
    eng_f = jax.jit(engine_mod.make_dex_engine(
        meta, cfg_f, mesh, ops=("lookup", "update"), max_count=1,
        cache_policy=pol_f,
    ))

    def put_f(x):
        return jax.device_put(jnp.asarray(x), sharding)

    def stat_sum(st):
        return np.asarray(st.stats).sum(axis=0)

    hot_f = keys[::40].astype(np.int64)          # spread over all 4 columns
    rng_f = np.random.default_rng(77)

    def lookup_batch(st):
        qf = rng_f.choice(hot_f, size=512).astype(np.int64)
        st, r = eng_f(st, put_f(np.zeros(512, np.int32)), put_f(qf),
                      put_f(np.zeros(512, np.int64)))
        assert not np.asarray(r.shed).any()
        assert np.asarray(r.found).all(), "hot fleet-cache lookup missed"
        assert (np.asarray(r.values) == qf * 7).all(), (
            "wrong/stale value served through the fleet cache"
        )
        return st

    for _ in range(5):                            # warm the specialists
        state = lookup_batch(state)
    before_f = stat_sum(state)
    state = lookup_batch(state)
    delta_f = stat_sum(state) - before_f
    assert int(delta_f[dex_mod.STAT_PEER_HITS]) > 0, (
        "warm divergent fleet must answer foreign-column misses via peeks"
    )

    # poison EVERY cached row on EVERY chip and bump EVERY node version:
    # all cached copies (local and peer alike) are now stale garbage; the
    # version check must reject each one.  Proven directly on the arrays
    # with the same `peer_answer` the fused round runs: every tagged row of
    # every chip answers freely before the poison and not at all after.
    def fleet_probe(st):
        cache_np = jax.tree.map(np.asarray, st.cache)
        vers_np = jnp.asarray(np.asarray(st.versions)[0])
        n_hits = n_rows = 0
        for d in range(cache_np.tags.shape[0]):
            cache_d = jax.tree.map(lambda a: jnp.asarray(a[d:d + 1]), cache_np)
            gids = np.unique(cache_np.tags[d][cache_np.tags[d] >= 0])
            if gids.size == 0:
                continue
            ph, _fnd, _val = fleet_cache.peer_answer(
                cache_d, cfg_f, vers_np, jnp.asarray(gids.astype(np.int64)),
                jnp.zeros(gids.size, jnp.int64), jnp.ones(gids.size, bool),
            )
            n_hits += int(np.asarray(ph).sum())
            n_rows += int(gids.size)
        return n_hits, n_rows

    fresh_hits, fresh_rows = fleet_probe(state)
    assert fresh_rows > 0 and fresh_hits > 0, (
        "warm fleet caches must answer peer probes before the poison"
    )
    pois_f = np.asarray(state.cache.values).copy()
    pois_f[:] = -777_777
    state = state._replace(
        cache=state.cache._replace(
            values=jax.device_put(jnp.asarray(pois_f),
                                  shardings_f.cache.values)
        ),
        versions=jax.device_put(jnp.asarray(state.versions) + 1,
                                shardings_f.versions),
    )
    stale_hits, stale_rows = fleet_probe(state)
    assert stale_rows >= fresh_rows and stale_hits == 0, (
        "a version-stale poisoned peer row survived the peek version check"
    )
    # engine-level: the batch right after the poison still returns correct
    # values everywhere (lookup_batch asserts them) and peeks the sibling
    # could not serve from a fresh row land as peer misses.  Peer hits may
    # legitimately reappear in the same batch: the fused round answers from
    # the post-descent cache, so a specialist that re-fetched (and
    # re-admitted) a hot leaf during this batch's own descent serves it
    # fresh — never the poisoned copy, which the probe above rejects.
    before_f = stat_sum(state)
    state = lookup_batch(state)
    delta_f = stat_sum(state) - before_f
    assert int(delta_f[dex_mod.STAT_PEER_MISSES]) > 0, (
        "stale-fleet peeks must be counted as peer misses"
    )
    # recovery: re-warmed specialists serve peeks again from fresh rows
    for _ in range(4):
        state = lookup_batch(state)
    before_f = stat_sum(state)
    state = lookup_batch(state)
    delta_f = stat_sum(state) - before_f
    assert int(delta_f[dex_mod.STAT_PEER_HITS]) > 0, (
        "fleet must recover peer hits after re-warming fresh rows"
    )
    print("MESH_CHECK_OK")


if __name__ == "__main__":
    main()
