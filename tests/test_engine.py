"""Tests for the unified mixed-op execution engine (core/engine.py).

The per-op builders (``make_dex_lookup`` / ``make_dex_update`` /
``make_dex_insert`` / ``make_dex_scan``) are thin wrappers over the engine,
so the load-bearing checks here are (a) an all-one-opcode batch through the
*full* four-opcode engine is bit-identical to the specialized wrappers,
(b) opcode edge cases (empty batch, all-inactive batch, unknown opset),
and (c) interleaved mixed batches match a phased sequential HostBTree
replay — reads see the pre-batch index, then updates apply, then inserts
(the engine's phase-offset batch priority).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import dex as dex_mod
from repro.core import engine as engine_mod
from repro.core import pool as pool_mod
from repro.core import scan as scan_mod
from repro.core import write as write_mod
from repro.compat import make_mesh_compat
from repro.core.nodes import KEY_MAX, KEY_MIN
from repro.core.sim import HostBTree

MC = 32


def _dataset(n, seed=0, space=None):
    rng = np.random.default_rng(seed)
    space = space or 16 * n
    return np.sort(rng.choice(space, size=n, replace=False).astype(np.int64) + 1)


def _setup(keys, *, policy="fetch", p_admit_leaf_pct=10, cache_sets=128):
    vals = keys * 5
    pool, meta = pool_mod.build_pool(keys, vals, level_m=1, fill=0.7,
                                     n_shards=1)
    mesh = make_mesh_compat((1, 1), ("data", "model"))
    cfg = dex_mod.DexMeshConfig(
        n_route=1, n_memory=1, cache_sets=cache_sets, cache_ways=4,
        p_admit_leaf_pct=p_admit_leaf_pct, route_capacity_factor=2.0,
        policy=policy,
    )
    bounds = np.array([KEY_MIN, KEY_MAX], np.int64)
    state = dex_mod.init_state(pool, meta, cfg, bounds)
    host = HostBTree(keys, vals, fill=0.7)
    return state, meta, cfg, mesh, host, bounds


def _full_engine(meta, cfg, mesh):
    return jax.jit(engine_mod.make_dex_engine(
        meta, cfg, mesh, ops=engine_mod.ALL_OPS, max_count=MC
    ))


def _plane(op, keys):
    return jnp.full(keys.shape, op, jnp.int32), jnp.asarray(keys)


class TestSingleOpcodeParity:
    """All-one-opcode batches through the full mixed engine must be
    bit-identical to the specialized single-opcode wrappers."""

    def test_lookup_batch_matches_wrapper(self):
        keys = _dataset(4000, seed=1)
        state, meta, cfg, mesh, _, _ = _setup(keys)
        eng = _full_engine(meta, cfg, mesh)
        lookup = jax.jit(dex_mod.make_dex_lookup(meta, cfg, mesh))
        q = np.concatenate([keys[:300], keys[:100] + 1]).astype(np.int64)
        opc, kk = _plane(engine_mod.OP_LOOKUP, q)
        s_e, r = eng(state, opc, kk, jnp.zeros_like(kk))
        s_w, f, v, sh = lookup(state, kk)
        np.testing.assert_array_equal(np.asarray(r.found), np.asarray(f))
        np.testing.assert_array_equal(np.asarray(r.values), np.asarray(v))
        np.testing.assert_array_equal(np.asarray(r.shed), np.asarray(sh))
        # one more batch from each evolved state must also agree (the
        # engine's cache/EMA updates match the wrapper's)
        s_e2, r2 = eng(s_e, opc, kk, jnp.zeros_like(kk))
        s_w2, f2, v2, _ = lookup(s_w, kk)
        np.testing.assert_array_equal(np.asarray(r2.found), np.asarray(f2))
        np.testing.assert_array_equal(np.asarray(r2.values), np.asarray(v2))

    def test_update_batch_matches_wrapper(self):
        keys = _dataset(4000, seed=2)
        state, meta, cfg, mesh, _, _ = _setup(keys)
        eng = _full_engine(meta, cfg, mesh)
        update = jax.jit(write_mod.make_dex_update(meta, cfg, mesh))
        uk = np.concatenate([keys[:200], keys[:40] + 1]).astype(np.int64)
        uv = (uk * 13 + 1).astype(np.int64)
        opc, kk = _plane(engine_mod.OP_UPDATE, uk)
        s_e, r = eng(state, opc, kk, jnp.asarray(uv))
        s_w, res = update(state, kk, jnp.asarray(uv))
        np.testing.assert_array_equal(np.asarray(r.status), np.asarray(res))
        np.testing.assert_array_equal(
            np.asarray(s_e.pool.pool_values), np.asarray(s_w.pool.pool_values)
        )
        np.testing.assert_array_equal(
            np.asarray(s_e.versions), np.asarray(s_w.versions)
        )

    def test_insert_batch_matches_wrapper(self):
        keys = _dataset(4000, seed=3)
        state, meta, cfg, mesh, _, _ = _setup(keys)
        eng = _full_engine(meta, cfg, mesh)
        insert = jax.jit(write_mod.make_dex_insert(meta, cfg, mesh))
        rng = np.random.default_rng(4)
        ik = (rng.choice(keys[:-1], size=256)
              + rng.integers(1, 3, size=256)).astype(np.int64)
        iv = ik * 3
        opc, kk = _plane(engine_mod.OP_INSERT, ik)
        s_e, r = eng(state, opc, kk, jnp.asarray(iv))
        s_w, res = insert(state, kk, jnp.asarray(iv))
        np.testing.assert_array_equal(np.asarray(r.status), np.asarray(res))
        np.testing.assert_array_equal(
            np.asarray(s_e.pool.pool_keys), np.asarray(s_w.pool.pool_keys)
        )
        np.testing.assert_array_equal(
            np.asarray(s_e.occupancy), np.asarray(s_w.occupancy)
        )

    def test_scan_batch_matches_wrapper(self):
        keys = _dataset(4000, seed=5)
        state, meta, cfg, mesh, _, _ = _setup(keys)
        eng = _full_engine(meta, cfg, mesh)
        scan = jax.jit(scan_mod.make_dex_scan(meta, cfg, mesh, max_count=MC))
        rng = np.random.default_rng(6)
        starts = rng.choice(keys, size=128).astype(np.int64)
        starts[::7] = starts[::7] + 1
        cnts = rng.integers(0, MC + 1, size=128).astype(np.int64)
        opc, kk = _plane(engine_mod.OP_SCAN, starts)
        s_e, r = eng(state, opc, kk, jnp.asarray(cnts))
        s_w, sk, sv, tk = scan(state, kk, jnp.asarray(cnts))
        np.testing.assert_array_equal(np.asarray(r.scan_keys), np.asarray(sk))
        np.testing.assert_array_equal(np.asarray(r.scan_values), np.asarray(sv))
        np.testing.assert_array_equal(np.asarray(r.taken), np.asarray(tk))


class TestOpcodeEdgeCases:
    def test_all_inactive_batch_is_a_noop(self):
        keys = _dataset(2000, seed=7)
        state, meta, cfg, mesh, _, _ = _setup(keys)
        eng = _full_engine(meta, cfg, mesh)
        kk = jnp.full((64,), KEY_MAX, jnp.int64)
        opc = jnp.zeros((64,), jnp.int32)
        s2, r = eng(state, opc, kk, jnp.zeros((64,), jnp.int64))
        assert not np.asarray(r.found).any()
        assert (np.asarray(r.status) == write_mod.STATUS_MISS).all()
        assert not np.asarray(r.shed).any()
        assert (np.asarray(r.taken) == 0).all()
        stats = np.asarray(s2.stats).sum(axis=0)
        assert stats[dex_mod.STAT_OPS] == 0
        assert stats[dex_mod.STAT_DROPS] == 0
        np.testing.assert_array_equal(
            np.asarray(s2.pool.pool_keys), np.asarray(state.pool.pool_keys)
        )

    def test_empty_batch(self):
        keys = _dataset(2000, seed=8)
        state, meta, cfg, mesh, _, _ = _setup(keys)
        eng = engine_mod.make_dex_engine(meta, cfg, mesh, max_count=MC)
        s2, r = eng(state, jnp.zeros((0,), jnp.int32),
                    jnp.zeros((0,), jnp.int64), jnp.zeros((0,), jnp.int64))
        assert r.found.shape == (0,)
        assert r.scan_keys.shape == (0, MC)
        assert s2 is state

    def test_unknown_op_rejected(self):
        keys = _dataset(1000, seed=9)
        _, meta, cfg, mesh, _, _ = _setup(keys)
        with pytest.raises(ValueError):
            engine_mod.make_dex_engine(meta, cfg, mesh, ops=("delete",))

    def test_inactive_lanes_interleave_with_live_ones(self):
        keys = _dataset(3000, seed=10)
        state, meta, cfg, mesh, _, _ = _setup(keys)
        eng = _full_engine(meta, cfg, mesh)
        q = keys[:128].astype(np.int64).copy()
        q[::3] = KEY_MAX
        opc = np.full(q.shape, engine_mod.OP_LOOKUP, np.int32)
        s2, r = eng(state, jnp.asarray(opc), jnp.asarray(q),
                    jnp.zeros_like(jnp.asarray(q)))
        f = np.asarray(r.found)
        live = q != KEY_MAX
        assert f[live].all() and not f[~live].any()
        assert int(np.asarray(s2.stats).sum(axis=0)[dex_mod.STAT_OPS]) == int(
            live.sum()
        )


class TestMixedBatchPhasedReplay:
    """A mixed batch equals the phased sequential replay: lookups/scans see
    the pre-batch index, then updates, then inserts."""

    def test_mixed_batch_matches_host(self):
        keys = _dataset(6000, seed=11)
        state, meta, cfg, mesh, host, bounds = _setup(keys)
        eng = _full_engine(meta, cfg, mesh)
        rng = np.random.default_rng(12)
        b = 512
        opc = rng.integers(0, 4, size=b).astype(np.int32)
        kk = rng.choice(keys, size=b).astype(np.int64)
        ins = opc == engine_mod.OP_INSERT
        fresh = kk + rng.integers(1, 3, size=b)
        kk[ins] = np.where(np.isin(fresh[ins], keys), kk[ins], fresh[ins])
        vals = np.zeros(b, np.int64)
        vals[opc == engine_mod.OP_UPDATE] = kk[opc == engine_mod.OP_UPDATE] ^ 0x77
        vals[ins] = kk[ins] * 3
        cnt_mask = opc == engine_mod.OP_SCAN
        vals[cnt_mask] = rng.integers(1, MC + 1, size=int(cnt_mask.sum()))
        # one update and one insert of the SAME existing key in one batch:
        # phased replay applies the update first, so the insert's value
        # (a duplicate-key value update) must win
        opc[0], kk[0], vals[0] = engine_mod.OP_UPDATE, keys[100], 111
        opc[1], kk[1], vals[1] = engine_mod.OP_INSERT, keys[100], 222
        ins = opc == engine_mod.OP_INSERT
        cnt_mask = opc == engine_mod.OP_SCAN

        s2, r = eng(state, jnp.asarray(opc), jnp.asarray(kk), jnp.asarray(vals))
        found = np.asarray(r.found)
        got_v = np.asarray(r.values)
        status = np.asarray(r.status)
        sk = np.asarray(r.scan_keys)
        sv = np.asarray(r.scan_values)
        tk = np.asarray(r.taken)
        shed = np.asarray(r.shed)
        assert not shed.any()

        # phase 1: reads against the pre-batch host
        for i in np.where(opc == engine_mod.OP_LOOKUP)[0]:
            hv = host.get(int(kk[i]))
            assert bool(found[i]) == (hv is not None), i
            if hv is not None:
                assert int(got_v[i]) == hv, i
        for i in np.where(cnt_mask)[0]:
            exp = [k for _, ks in host.scan(int(kk[i]), int(vals[i]))
                   for k in ks][: int(vals[i])]
            got = sk[i][sk[i] != KEY_MAX].tolist()
            assert got == exp, i
            assert tk[i] == len(exp)
            for j, key in enumerate(exp):
                assert int(sv[i, j]) == host.get(int(key)), (i, j)
        # phase 2: updates, then phase 3: inserts
        for i in np.where(opc == engine_mod.OP_UPDATE)[0]:
            applied = host.update(int(kk[i]), int(vals[i]))
            assert (status[i] == write_mod.STATUS_OK) == applied, i
        for i in np.where(ins)[0]:
            if status[i] == write_mod.STATUS_OK:
                host.insert(int(kk[i]), int(vals[i]))
            else:
                assert status[i] == write_mod.STATUS_SPLIT, (i, status[i])
        # post-batch: every key now matches the replayed host
        lookup = jax.jit(dex_mod.make_dex_lookup(meta, cfg, mesh))
        probe = kk[: (kk.size // 8) * 8]
        s3, f3, v3, _ = lookup(s2, jnp.asarray(probe))
        f3, v3 = np.asarray(f3), np.asarray(v3)
        for i in range(probe.size):
            hv = host.get(int(probe[i]))
            assert bool(f3[i]) == (hv is not None), i
            if hv is not None:
                assert int(v3[i]) == hv, i
        # the same-key update+insert pair resolved in phase order
        assert host.get(int(keys[100])) == 222


def _mixed_batches(keys, rng, n, b, *, with_scan=False, hot=None):
    """Interleaved mixed-op batches; ``hot`` keys are woven into every
    batch (writes on even batches, reads on odd) so adjacent batches
    conflict on the same leaves — the overlap window's hard case."""
    out = []
    hi = 4 if with_scan else 3
    for bi in range(n):
        opc = rng.integers(0, hi, size=b).astype(np.int32)
        kk = rng.choice(keys, size=b).astype(np.int64)
        ins = opc == engine_mod.OP_INSERT
        fresh = kk + rng.integers(1, 4, size=b)
        ok_f = ~np.isin(fresh, keys)
        kk[ins & ok_f] = fresh[ins & ok_f]
        vals = np.zeros(b, np.int64)
        upd = opc == engine_mod.OP_UPDATE
        vals[upd] = kk[upd] ^ 0x5A5A
        vals[ins] = kk[ins] * 7
        if with_scan:
            scn = opc == engine_mod.OP_SCAN
            vals[scn] = rng.integers(1, MC + 1, size=int(scn.sum()))
        if hot is not None:
            h = len(hot)
            if bi % 2 == 0:
                opc[:h] = engine_mod.OP_UPDATE
                kk[:h] = hot
                vals[:h] = (hot ^ (100 + bi)).astype(np.int64)
            else:
                opc[:h] = (engine_mod.OP_SCAN if with_scan
                           else engine_mod.OP_LOOKUP)
                kk[:h] = hot
                vals[:h] = 8 if with_scan else 0
        out.append((opc, kk, vals))
    return out


class TestPipelinedEngine:
    """``pipeline=True``: the two-stage software pipeline must be
    bit-identical to the synchronous engine on interleaved mixed-op
    batches — including same-key cross-batch update/lookup conflicts
    (resolved by the version-check + forced two-sided fallback) and the
    drain tail — while scans stall-shed conservatively."""

    OPS = ("lookup", "update", "insert")

    def test_pipelined_matches_synchronous_mixed(self):
        keys = _dataset(4000, seed=21)
        state, meta, cfg, mesh, _, _ = _setup(keys)
        sync = jax.jit(engine_mod.make_dex_engine(
            meta, cfg, mesh, ops=self.OPS, max_count=1))
        pipe = engine_mod.make_dex_engine(
            meta, cfg, mesh, ops=self.OPS, max_count=1, pipeline=True)
        rng = np.random.default_rng(22)
        batches = _mixed_batches(keys, rng, 5, 128, hot=keys[40:48])

        s_sync = state
        sync_res = []
        for opc, kk, vals in batches:
            s_sync, r = sync(s_sync, jnp.asarray(opc), jnp.asarray(kk),
                             jnp.asarray(vals))
            sync_res.append(r)
        s_pipe, pipe_res = pipe.run(
            state,
            [(jnp.asarray(o), jnp.asarray(k), jnp.asarray(v))
             for o, k, v in batches],
        )
        assert len(pipe_res) == len(batches)
        for b, (rs, rp) in enumerate(zip(sync_res, pipe_res)):
            for field in ("found", "values", "status", "shed"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(rs, field)),
                    np.asarray(getattr(rp, field)),
                    err_msg=f"batch {b} {field}",
                )
        # the drained index is the synchronous one, bit for bit
        np.testing.assert_array_equal(
            np.asarray(s_sync.pool.pool_keys),
            np.asarray(s_pipe.pool.pool_keys))
        np.testing.assert_array_equal(
            np.asarray(s_sync.pool.pool_values),
            np.asarray(s_pipe.pool.pool_values))
        np.testing.assert_array_equal(
            np.asarray(s_sync.versions), np.asarray(s_pipe.versions))
        np.testing.assert_array_equal(
            np.asarray(s_sync.occupancy), np.asarray(s_pipe.occupancy))
        # the hot-key conflicts stalled lanes in the overlap window; the
        # synchronous engine never stalls
        st_p = np.asarray(s_pipe.stats).sum(axis=0)
        st_s = np.asarray(s_sync.stats).sum(axis=0)
        assert int(st_p[dex_mod.STAT_PIPE_STALLS]) > 0
        assert int(st_s[dex_mod.STAT_PIPE_STALLS]) == 0

    def test_pipelined_scans_stall_shed_conservatively(self):
        keys = _dataset(4000, seed=23)
        state, meta, cfg, mesh, _, _ = _setup(keys)
        sync = _full_engine(meta, cfg, mesh)
        pipe = engine_mod.make_dex_engine(
            meta, cfg, mesh, ops=engine_mod.ALL_OPS, max_count=MC,
            pipeline=True)
        rng = np.random.default_rng(24)
        batches = _mixed_batches(keys, rng, 4, 128, with_scan=True,
                                 hot=keys[40:48])
        s_sync = state
        sync_res = []
        for opc, kk, vals in batches:
            s_sync, r = sync(s_sync, jnp.asarray(opc), jnp.asarray(kk),
                             jnp.asarray(vals))
            sync_res.append(r)
        s_pipe, pipe_res = pipe.run(
            state,
            [(jnp.asarray(o), jnp.asarray(k), jnp.asarray(v))
             for o, k, v in batches],
        )
        any_scan_shed = False
        for b, (rs, rp) in enumerate(zip(sync_res, pipe_res)):
            shed_s = np.asarray(rs.shed)
            shed_p = np.asarray(rp.shed)
            # pipelining only ADDS sheds (stall-shed scans), never loses one
            assert not (shed_s & ~shed_p).any(), b
            stalled = shed_p & ~shed_s
            any_scan_shed = any_scan_shed or stalled.any()
            assert (np.asarray(rp.taken)[stalled] == -1).all(), b
            ok = ~shed_p
            for field in ("found", "values", "status",
                          "scan_keys", "scan_values", "taken"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(rs, field))[ok],
                    np.asarray(getattr(rp, field))[ok],
                    err_msg=f"batch {b} {field}",
                )
        # writes still applied identically despite the stall-shed scans
        np.testing.assert_array_equal(
            np.asarray(s_sync.pool.pool_values),
            np.asarray(s_pipe.pool.pool_values))
        np.testing.assert_array_equal(
            np.asarray(s_sync.versions), np.asarray(s_pipe.versions))
        assert any_scan_shed  # the hot write->scan weave must conflict

    def test_pipeline_protocol(self):
        keys = _dataset(2000, seed=25)
        state, meta, cfg, mesh, _, _ = _setup(keys)
        pipe = engine_mod.make_dex_engine(
            meta, cfg, mesh, ops=self.OPS, max_count=1, pipeline=True)
        b = 64
        opc = jnp.full((b,), engine_mod.OP_LOOKUP, jnp.int32)
        kk = jnp.asarray(keys[:b])
        vv = jnp.zeros((b,), jnp.int64)
        with pytest.raises(RuntimeError):
            pipe.push(opc, kk, vv)
        pipe.start(state)
        assert pipe.drain() is None          # nothing in flight
        assert pipe.push(opc, kk, vv) is None  # prologue primes
        with pytest.raises(ValueError):
            pipe.push(opc[: b // 2], kk[: b // 2], vv[: b // 2])
        r1 = pipe.push(opc, kk, vv)          # steady state: lag-one result
        assert r1 is not None and np.asarray(r1.found).all()
        rd = pipe.drain()                    # drain flushes the tail
        assert rd is not None and np.asarray(rd.found).all()
        assert pipe.drain() is None
        assert pipe.push(opc, kk, vv) is None  # re-primes after drain
        assert pipe.plan["pipeline"] is True
        assert pipe.plan["overlap_phases"] == ("pipe/front", "pipe/back")


class TestInterleavedPropertyHypothesis:
    def test_interleaved_mixed_batches_match_host_replay(self):
        pytest.importorskip(
            "hypothesis",
            reason="property tests need hypothesis "
                   "(optional [test] dep; CI's hyp-installed legs run them)",
        )
        from hypothesis import given, settings, strategies as st

        keys = _dataset(3000, seed=13)
        state0, meta0, cfg, mesh, _, bounds = _setup(keys)

        @settings(max_examples=8, deadline=None)
        @given(st.data())
        def scenario(data):
            host = HostBTree(keys, keys * 5, fill=0.7)
            state, meta = state0, meta0
            eng = _full_engine(meta, cfg, mesh)
            lookup = jax.jit(dex_mod.make_dex_lookup(meta, cfg, mesh))
            rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
            for _ in range(data.draw(st.integers(1, 3))):
                b = 256
                opc = rng.integers(0, 4, size=b).astype(np.int32)
                kk = rng.choice(keys, size=b).astype(np.int64)
                ins = opc == engine_mod.OP_INSERT
                fresh = kk + rng.integers(1, 4, size=b)
                ok_f = ~np.isin(fresh, keys)
                kk[ins & ok_f] = fresh[ins & ok_f]
                vals = np.zeros(b, np.int64)
                upd = opc == engine_mod.OP_UPDATE
                vals[upd] = kk[upd] ^ 0x5A5A
                vals[ins] = kk[ins] * 7
                scn = opc == engine_mod.OP_SCAN
                vals[scn] = rng.integers(1, MC + 1, size=int(scn.sum()))
                s2, r = eng(state, jnp.asarray(opc), jnp.asarray(kk),
                            jnp.asarray(vals))
                found = np.asarray(r.found)
                got_v = np.asarray(r.values)
                status = np.asarray(r.status)
                sk = np.asarray(r.scan_keys)
                tk = np.asarray(r.taken)
                for i in np.where(opc == engine_mod.OP_LOOKUP)[0]:
                    hv = host.get(int(kk[i]))
                    assert bool(found[i]) == (hv is not None)
                    if hv is not None:
                        assert int(got_v[i]) == hv
                for i in np.where(scn)[0]:
                    if tk[i] < 0:
                        continue
                    exp = [k for _, ks in host.scan(int(kk[i]), int(vals[i]))
                           for k in ks][: int(vals[i])]
                    assert sk[i][sk[i] != KEY_MAX].tolist() == exp
                for i in np.where(upd)[0]:
                    applied = host.update(int(kk[i]), int(vals[i]))
                    assert (status[i] == write_mod.STATUS_OK) == applied
                shed_i = np.zeros(b, bool)
                for i in np.where(ins)[0]:
                    if status[i] == write_mod.STATUS_OK:
                        host.insert(int(kk[i]), int(vals[i]))
                    elif status[i] == write_mod.STATUS_SPLIT:
                        shed_i[i] = True
                state = s2
                if shed_i.any():
                    state, meta = write_mod.drain_splits(
                        state, meta, cfg, host, kk[shed_i], vals[shed_i],
                        bounds,
                    )
                    eng = _full_engine(meta, cfg, mesh)
                    lookup = jax.jit(dex_mod.make_dex_lookup(meta, cfg, mesh))
                probe = rng.choice(kk, size=64).astype(np.int64)
                s3, f3, v3, _ = lookup(state, jnp.asarray(probe))
                state = s3
                f3, v3 = np.asarray(f3), np.asarray(v3)
                for i in range(64):
                    hv = host.get(int(probe[i]))
                    assert bool(f3[i]) == (hv is not None)
                    if hv is not None:
                        assert int(v3[i]) == hv

        scenario()


# ---------------------------------------------------------------------------
# Fleet-cache policy layer (core/fleet_cache.py): the refactor must be
# invisible in uniform mode — golden digests captured from the pre-refactor
# engine pin the results plane, the pool/version/occupancy planes and the
# pre-existing stat slots bit-for-bit
# ---------------------------------------------------------------------------


def _digest(*arrays):
    import hashlib

    m = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        m.update(str(a.dtype).encode())
        m.update(str(a.shape).encode())
        m.update(np.ascontiguousarray(a).tobytes())
    return m.hexdigest()[:16]


#: digests captured from the pre-refactor engine (commit f10d0ee) on the
#: exact traces below; ``stats`` covers the first 12 slots — the append-only
#: registry grew STAT_PEER_HITS/STAT_PEER_MISSES behind them
GOLDEN_SYNC = {
    "results": "13a52c855d8bb34c",
    "state": "c15d2578f7089877",
    "stats12": "8360e212492d6683",
}
GOLDEN_PIPE = {
    "results": "9a0530fdcd963a29",
    "state": "94e5a79e074503c5",
    "stats12": "368a753978770c50",
}


class TestFleetCachePolicyGoldens:
    def _sync_digests(self, cache_policy):
        keys = _dataset(4000, seed=31)
        state, meta, cfg, mesh, _, _ = _setup(keys)
        eng = jax.jit(engine_mod.make_dex_engine(
            meta, cfg, mesh, ops=engine_mod.ALL_OPS, max_count=MC,
            cache_policy=cache_policy,
        ))
        rng = np.random.default_rng(32)
        batches = _mixed_batches(keys, rng, 4, 256, with_scan=True,
                                 hot=keys[40:48])
        import hashlib

        res_h = hashlib.sha256()
        for opc, kk, vals in batches:
            state, r = eng(state, jnp.asarray(opc), jnp.asarray(kk),
                           jnp.asarray(vals))
            res_h.update(_digest(r.found, r.values, r.status, r.shed,
                                 r.scan_keys, r.scan_values, r.taken)
                         .encode())
        stats = np.asarray(state.stats)
        return {
            "results": res_h.hexdigest()[:16],
            "state": _digest(pool_mod.blocks(state.pool.pool_keys),
                             pool_mod.blocks(state.pool.pool_values),
                             state.versions, state.occupancy),
            "stats12": _digest(stats[:, :12]),
        }, stats

    def test_uniform_mode_bit_identical_to_pre_refactor(self):
        """``cache_policy=None`` reproduces the pre-refactor goldens:
        results lane-for-lane, pool/version/occupancy planes, and every
        pre-existing stat slot; the two new peer slots stay zero.  Run
        twice with the same trace+seed: bit-identical across runs."""
        d1, stats = self._sync_digests(None)
        assert d1 == GOLDEN_SYNC, d1
        assert (stats[:, dex_mod.STAT_PEER_HITS] == 0).all()
        assert (stats[:, dex_mod.STAT_PEER_MISSES] == 0).all()
        d2, _ = self._sync_digests(None)
        assert d2 == d1, "same trace+seed must be bit-identical across runs"

    def test_explicit_uniform_policy_matches_none(self):
        """An all-ones/zero-salt ``uniform_policy`` pytree is the SAME
        program as ``cache_policy=None`` — the policy layer's uniform
        branch defers to ``routing.leaf_admit_dice`` verbatim."""
        from repro.core import fleet_cache

        keys = _dataset(4000, seed=31)
        _, _, cfg, _, _, _ = _setup(keys)
        pol = fleet_cache.uniform_policy(cfg)
        assert fleet_cache.is_uniform(pol)
        assert not fleet_cache.peeks_enabled(pol)
        d, _ = self._sync_digests(pol)
        assert d == GOLDEN_SYNC, d

    def test_pipelined_uniform_mode_matches_goldens(self):
        keys = _dataset(4000, seed=33)
        state, meta, cfg, mesh, _, _ = _setup(keys)
        pipe = engine_mod.make_dex_engine(
            meta, cfg, mesh, ops=("lookup", "update", "insert"),
            max_count=1, pipeline=True,
        )
        rng = np.random.default_rng(34)
        batches = _mixed_batches(keys, rng, 5, 128, hot=keys[40:48])
        s_pipe, pipe_res = pipe.run(
            state,
            [(jnp.asarray(o), jnp.asarray(k), jnp.asarray(v))
             for o, k, v in batches],
        )
        import hashlib

        res_h = hashlib.sha256()
        for r in pipe_res:
            res_h.update(_digest(r.found, r.values, r.status, r.shed)
                         .encode())
        got = {
            "results": res_h.hexdigest()[:16],
            "state": _digest(pool_mod.blocks(s_pipe.pool.pool_keys),
                             pool_mod.blocks(s_pipe.pool.pool_values),
                             s_pipe.versions, s_pipe.occupancy),
            "stats12": _digest(np.asarray(s_pipe.stats)[:, :12]),
        }
        assert got == GOLDEN_PIPE, got

    def test_golden_trace_matches_host_replay(self):
        """The golden trace itself replays against HostBTree — the pinned
        digests encode *correct* behaviour, not just frozen behaviour."""
        keys = _dataset(4000, seed=31)
        state, meta, cfg, mesh, host, bounds = _setup(keys)
        eng = _full_engine(meta, cfg, mesh)
        rng = np.random.default_rng(32)
        batches = _mixed_batches(keys, rng, 4, 256, with_scan=True,
                                 hot=keys[40:48])
        for opc, kk, vals in batches:
            state, r = eng(state, jnp.asarray(opc), jnp.asarray(kk),
                           jnp.asarray(vals))
            found = np.asarray(r.found)
            got_v = np.asarray(r.values)
            status = np.asarray(r.status)
            done = ~np.asarray(r.shed)
            for i in np.where(done & (opc == engine_mod.OP_LOOKUP))[0]:
                hv = host.get(int(kk[i]))
                assert bool(found[i]) == (hv is not None), int(kk[i])
                if hv is not None:
                    assert int(got_v[i]) == hv, int(kk[i])
            for i in np.where(done & (opc == engine_mod.OP_UPDATE))[0]:
                applied = host.update(int(kk[i]), int(vals[i]))
                assert (status[i] == write_mod.STATUS_OK) == applied
            for i in np.where(done & (opc == engine_mod.OP_INSERT))[0]:
                if status[i] == write_mod.STATUS_OK:
                    host.insert(int(kk[i]), int(vals[i]))


class TestSharedAdmissionConstant:
    def test_one_definition_of_the_leaf_admission_dice(self):
        """Both planes derive the leaf-admission probability from ONE
        definition: cache.DEFAULT_P_ADMIT_LEAF is the source of truth,
        fleet_cache.P_ADMIT_LEAF_PCT is its percent form, and the mesh
        config default plus the dex re-export point at it."""
        from repro.core import fleet_cache
        from repro.core.cache import DEFAULT_P_ADMIT_LEAF

        pct = int(round(DEFAULT_P_ADMIT_LEAF * 100))
        assert fleet_cache.P_ADMIT_LEAF_PCT == pct
        assert dex_mod.P_ADMIT_LEAF_PCT == pct
        assert dex_mod.DexMeshConfig().p_admit_leaf_pct == pct
