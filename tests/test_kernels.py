"""Per-kernel validation: sweep shapes/dtypes, assert_allclose vs ref.py
oracles (kernels run in interpret mode on CPU)."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import pool as pool_mod
from repro.core.nodes import FANOUT, KEY_MAX
from repro.kernels import ops, ref


def _keys(n, seed=0, hi=None):
    rng = np.random.default_rng(seed)
    hi = hi or 8 * n
    return np.sort(rng.choice(hi, size=n, replace=False).astype(np.int64) + 1)


# ---------------------------------------------------------------------------
# node_search
# ---------------------------------------------------------------------------


class TestNodeSearch:
    @pytest.mark.parametrize("b", [1, 17, 256, 300])
    def test_matches_ref(self, b):
        rng = np.random.default_rng(b)
        rows = np.sort(
            rng.integers(1, 2**62, size=(b, FANOUT), dtype=np.int64), axis=1
        )
        vals = rng.integers(0, 2**62, size=(b, FANOUT), dtype=np.int64)
        # half the queries hit exactly, half fall between keys
        q = rows[np.arange(b), rng.integers(0, FANOUT, size=b)].copy()
        q[::2] = q[::2] + 1
        slot, found, value = ops.node_search(rows, q, vals)
        rslot, rfound, rvalue = ref.node_search_ref(rows, q, vals)
        np.testing.assert_array_equal(np.asarray(slot), np.asarray(rslot))
        np.testing.assert_array_equal(np.asarray(found), np.asarray(rfound))
        np.testing.assert_array_equal(np.asarray(value), np.asarray(rvalue))

    def test_extreme_keys(self):
        # keys spanning the full signed 64-bit range, incl. negatives
        rows = np.sort(
            np.array([[-(2**62), -5, 0, 3, 2**62] + [2**63 - 2] * (FANOUT - 5)]),
            axis=1,
        ).astype(np.int64)
        vals = np.arange(FANOUT, dtype=np.int64)[None] * 7
        for q in [-(2**62), -5, -4, 0, 3, 2**62, 2**62 + 9]:
            qa = np.array([q], dtype=np.int64)
            s, f, v = ops.node_search(rows, qa, vals)
            rs, rf, rv = ref.node_search_ref(rows, qa, vals)
            assert int(s[0]) == int(rs[0]), q
            assert bool(f[0]) == bool(rf[0]), q
            assert int(v[0]) == int(rv[0]), q


# ---------------------------------------------------------------------------
# subtree_walk
# ---------------------------------------------------------------------------


class TestSubtreeWalk:
    @pytest.mark.parametrize("level_m,n", [(1, 2000), (2, 20_000)])
    def test_matches_ref_per_subtree(self, level_m, n):
        keys = _keys(n, seed=level_m)
        pool, meta = pool_mod.build_pool(keys, keys * 5, level_m=level_m)
        rng = np.random.default_rng(3)
        q = rng.choice(keys, size=256).astype(np.int64)
        q[::3] += 1  # misses
        st = np.asarray(pool_mod.top_walk(pool, meta, jnp.asarray(q)))
        s0 = int(st[0])
        qs = q[st == s0]
        pk, pv = pool_mod.keys_i64(pool), pool_mod.values_i64(pool)
        f_k, v_k = ops.subtree_walk(
            pk[s0],
            pool.pool_children[s0],
            pv[s0],
            qs,
            levels=meta.levels_in_subtree,
        )
        f_r, v_r = ref.subtree_walk_ref(
            pk[s0],
            pool.pool_children[s0],
            pv[s0],
            qs,
            levels=meta.levels_in_subtree,
        )
        np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_r))
        np.testing.assert_array_equal(
            np.asarray(v_k)[np.asarray(f_r)], np.asarray(v_r)[np.asarray(f_r)]
        )

    def test_small_batch_padding(self):
        keys = _keys(500, seed=9)
        pool, meta = pool_mod.build_pool(keys, keys, level_m=1)
        q = keys[:5]
        f, v = ops.subtree_walk(
            pool_mod.keys_i64(pool)[0], pool.pool_children[0],
            pool_mod.values_i64(pool)[0],
            q, levels=meta.levels_in_subtree,
        )
        st = np.asarray(pool_mod.top_walk(pool, meta, jnp.asarray(q)))
        mask = st == 0
        assert bool(np.all(np.asarray(f)[mask]))


# ---------------------------------------------------------------------------
# leaf_write
# ---------------------------------------------------------------------------


class TestLeafWrite:
    def _case(self, q, s, seed):
        """Random leaf rows plus staged updates (distinct slots) and staged
        inserts (sorted, distinct from the row, within slack) — the caller
        contract that core/write.py enforces."""
        rng = np.random.default_rng(seed)
        k = np.full((q, FANOUT), KEY_MAX, np.int64)
        v = np.zeros((q, FANOUT), np.int64)
        us = np.full((q, s), -1, np.int32)
        uv = np.zeros((q, s), np.int64)
        ik = np.full((q, s), KEY_MAX, np.int64)
        iv = np.zeros((q, s), np.int64)
        for i in range(q):
            occ = int(rng.integers(0, FANOUT - s + 1))
            keys = np.sort(
                rng.choice(1 << 30, size=occ, replace=False).astype(np.int64)
            ) * 2 + 2                          # even keys
            k[i, :occ] = keys
            v[i, :occ] = keys * 3
            nu = int(rng.integers(0, min(occ, s) + 1))
            if nu:
                us[i, :nu] = rng.choice(occ, size=nu, replace=False)
                uv[i, :nu] = rng.integers(0, 1 << 40, size=nu)
            ni = int(rng.integers(0, min(s, FANOUT - occ) + 1))
            if ni:
                newk = np.sort(
                    rng.choice(1 << 30, size=ni, replace=False).astype(np.int64)
                ) * 2 + 1                      # odd: distinct from the row
                ik[i, :ni] = newk
                iv[i, :ni] = newk * 5
        return map(jnp.asarray, (k, v, us, uv, ik, iv))

    @pytest.mark.parametrize("q", [1, 8, 37, 130])
    def test_matches_ref(self, q):
        args = list(self._case(q, s=16, seed=q))
        got = ops.leaf_write(*args)
        want = ref.leaf_write_ref(*args)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_full_width_staging(self):
        # staged width == FANOUT: a completely empty row filled in one batch
        k = np.full((2, FANOUT), KEY_MAX, np.int64)
        v = np.zeros((2, FANOUT), np.int64)
        us = np.full((2, FANOUT), -1, np.int32)
        uv = np.zeros((2, FANOUT), np.int64)
        ik = np.full((2, FANOUT), KEY_MAX, np.int64)
        iv = np.zeros((2, FANOUT), np.int64)
        ik[0] = np.arange(1, FANOUT + 1, dtype=np.int64) * 7
        iv[0] = ik[0] * 11
        args = list(map(jnp.asarray, (k, v, us, uv, ik, iv)))
        gk, gv, gocc = ops.leaf_write(*args)
        rk, rv, rocc = ref.leaf_write_ref(*args)
        np.testing.assert_array_equal(np.asarray(gk), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(rv))
        np.testing.assert_array_equal(np.asarray(gocc), np.asarray(rocc))
        assert np.asarray(gocc).tolist() == [FANOUT, 0]
        np.testing.assert_array_equal(np.asarray(gk)[0], ik[0])

    def test_negative_and_extreme_keys(self):
        k = np.full((1, FANOUT), KEY_MAX, np.int64)
        v = np.zeros((1, FANOUT), np.int64)
        k[0, :4] = [-(2**62), -7, 0, 2**62]
        v[0, :4] = [1, 2, 3, 4]
        us = np.array([[1, -1]], np.int32)
        uv = np.array([[99, 0]], np.int64)
        ik = np.array([[-(2**61), 2**61]], np.int64)
        iv = np.array([[5, 6]], np.int64)
        args = list(map(jnp.asarray, (k, v, us, uv, ik, iv)))
        gk, gv, gocc = ops.leaf_write(*args)
        rk, rv, rocc = ref.leaf_write_ref(*args)
        np.testing.assert_array_equal(np.asarray(gk), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(rv))
        assert int(gocc[0]) == 6
        assert np.asarray(gk)[0, :6].tolist() == [
            -(2**62), -(2**61), -7, 0, 2**61, 2**62
        ]
        assert np.asarray(gv)[0, :6].tolist() == [1, 5, 99, 3, 6, 4]


# ---------------------------------------------------------------------------
# leaf_split
# ---------------------------------------------------------------------------


class TestLeafSplit:
    def _case(self, q, s, seed, *, force_overflow=False):
        """Random leaf rows plus staged inserts (sorted, distinct from the
        row) — the core/smo.py caller contract.  ``force_overflow`` draws
        occupancy + staging so every lane must split."""
        rng = np.random.default_rng(seed)
        k = np.full((q, FANOUT), KEY_MAX, np.int64)
        v = np.zeros((q, FANOUT), np.int64)
        ik = np.full((q, s), KEY_MAX, np.int64)
        iv = np.zeros((q, s), np.int64)
        for i in range(q):
            if force_overflow:
                occ = FANOUT
                ni = int(rng.integers(1, s + 1))
            else:
                occ = int(rng.integers(0, FANOUT + 1))
                ni = int(rng.integers(0, s + 1))
            keys = np.sort(
                rng.choice(1 << 30, size=occ, replace=False).astype(np.int64)
            ) * 2 + 2                          # even keys
            k[i, :occ] = keys
            v[i, :occ] = keys * 3
            if ni:
                newk = np.sort(
                    rng.choice(1 << 30, size=ni, replace=False).astype(np.int64)
                ) * 2 + 1                      # odd: distinct from the row
                ik[i, :ni] = newk
                iv[i, :ni] = newk * 5
        return list(map(jnp.asarray, (k, v, ik, iv)))

    @pytest.mark.parametrize("q", [1, 8, 37, 130])
    def test_matches_ref(self, q):
        args = self._case(q, s=FANOUT, seed=q)
        got = ops.leaf_split(*args)
        want = ref.leaf_split_ref(*args)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_overflow_always_splits_in_halves(self):
        q = 16
        args = self._case(q, s=FANOUT, seed=3, force_overflow=True)
        lk, lv, rk, rv, occl, occr, sep, did = map(
            np.asarray, ops.leaf_split(*args)
        )
        wk = np.asarray(args[0])
        wik = np.asarray(args[2])
        assert (did == 1).all()
        for i in range(q):
            merged = np.sort(np.concatenate(
                [wk[i][wk[i] != KEY_MAX], wik[i][wik[i] != KEY_MAX]]
            ))
            m = merged.size
            assert int(occl[i]) == m // 2
            assert int(occr[i]) == m - m // 2
            np.testing.assert_array_equal(lk[i][: m // 2], merged[: m // 2])
            np.testing.assert_array_equal(rk[i][: m - m // 2], merged[m // 2:])
            assert int(sep[i]) == int(merged[m // 2])
            # left/right key sets partition around the separator
            assert (lk[i][lk[i] != KEY_MAX] < sep[i]).all()
            assert (rk[i][rk[i] != KEY_MAX] >= sep[i]).all()

    def test_no_overflow_is_plain_merge(self):
        # m <= FANOUT must reproduce leaf_write's merge in the left row
        q = 9
        rng = np.random.default_rng(11)
        k = np.full((q, FANOUT), KEY_MAX, np.int64)
        v = np.zeros((q, FANOUT), np.int64)
        ik = np.full((q, FANOUT), KEY_MAX, np.int64)
        iv = np.zeros((q, FANOUT), np.int64)
        for i in range(q):
            occ = int(rng.integers(0, FANOUT - 4))
            ni = int(rng.integers(0, FANOUT - occ + 1))
            keys = np.sort(
                rng.choice(1 << 20, size=occ, replace=False).astype(np.int64)
            ) * 2 + 2
            k[i, :occ] = keys
            v[i, :occ] = keys * 3
            if ni:
                newk = np.sort(
                    rng.choice(1 << 20, size=ni, replace=False).astype(np.int64)
                ) * 2 + 1
                ik[i, :ni] = newk
                iv[i, :ni] = newk * 5
        args = list(map(jnp.asarray, (k, v, ik, iv)))
        lk, lv, rk, rv, occl, occr, sep, did = ops.leaf_split(*args)
        us = np.full((q, FANOUT), -1, np.int32)
        uv = np.zeros((q, FANOUT), np.int64)
        mk, mv, mocc = ref.leaf_write_ref(
            args[0], args[1], jnp.asarray(us), jnp.asarray(uv), args[2], args[3]
        )
        assert (np.asarray(did) == 0).all()
        np.testing.assert_array_equal(np.asarray(lk), np.asarray(mk))
        np.testing.assert_array_equal(np.asarray(lv), np.asarray(mv))
        np.testing.assert_array_equal(np.asarray(occl), np.asarray(mocc))
        assert (np.asarray(occr) == 0).all()
        assert (np.asarray(rk) == KEY_MAX).all()
        assert (np.asarray(sep) == KEY_MAX).all()

    def test_negative_and_extreme_keys(self):
        k = np.full((1, FANOUT), KEY_MAX, np.int64)
        v = np.zeros((1, FANOUT), np.int64)
        keys = np.sort(np.concatenate([
            np.array([-(2**62), -7, 0, 2**61], np.int64),
            np.arange(2, 2 * (FANOUT - 4) + 1, 2, dtype=np.int64),
        ]))
        k[0] = keys
        v[0] = np.arange(FANOUT, dtype=np.int64) + 1
        ik = np.full((1, 8), KEY_MAX, np.int64)
        iv = np.zeros((1, 8), np.int64)
        ik[0, :3] = [-(2**61), 3, 2**62]
        iv[0, :3] = [7, 8, 9]
        args = list(map(jnp.asarray, (k, v, ik, iv)))
        got = ops.leaf_split(*args)
        want = ref.leaf_split_ref(*args)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert int(got[7][0]) == 1  # FANOUT + 3 merged records must split


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


class TestFlashAttention:
    @pytest.mark.parametrize(
        "b,h,hkv,s,d",
        [(1, 4, 4, 128, 64), (2, 8, 2, 256, 64), (1, 4, 1, 128, 128)],
    )
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_matches_ref(self, b, h, hkv, s, d, dtype):
        rng = np.random.default_rng(42)
        q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
        k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), dtype)
        v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), dtype)
        out = ops.flash_attention(q, k, v, causal=True)
        expect = ref.flash_attention_ref(q, k, v, causal=True)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(expect, np.float32),
            atol=tol, rtol=tol,
        )

    def test_non_causal(self):
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=False)
        expect = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expect), atol=2e-5, rtol=2e-5
        )

    def test_cross_lengths_causal_offset(self):
        """Decode-style: Sq < Sk with causal alignment at the end."""
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, 384, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 384, 64)), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True)
        expect = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expect), atol=2e-5, rtol=2e-5
        )


# ---------------------------------------------------------------------------
# paged_attention
# ---------------------------------------------------------------------------


class TestPagedAttention:
    @pytest.mark.parametrize("b,h,hkv,d,page,ppr", [(2, 8, 2, 64, 16, 4),
                                                    (1, 4, 4, 128, 32, 2)])
    def test_matches_ref(self, b, h, hkv, d, page, ppr):
        rng = np.random.default_rng(5)
        n_pages = b * ppr + 3
        q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((n_pages, page, hkv, d)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((n_pages, page, hkv, d)), jnp.float32)
        table = rng.permutation(n_pages)[: b * ppr].reshape(b, ppr).astype(np.int32)
        seq_lens = rng.integers(1, ppr * page + 1, size=b).astype(np.int32)
        out = ops.paged_attention(q, kp, vp, jnp.asarray(table), jnp.asarray(seq_lens))
        expect = ref.paged_attention_ref(q, kp, vp, jnp.asarray(table),
                                         jnp.asarray(seq_lens))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expect), atol=3e-5, rtol=3e-5
        )


# ---------------------------------------------------------------------------
# mamba_scan
# ---------------------------------------------------------------------------


class TestMambaScan:
    @pytest.mark.parametrize("b,l,d,n", [(1, 32, 128, 16), (2, 64, 256, 16)])
    def test_matches_ref(self, b, l, d, n):
        rng = np.random.default_rng(11)
        delta = jnp.asarray(np.abs(rng.standard_normal((b, l, d))) * 0.1 + 0.01,
                            jnp.float32)
        A = jnp.asarray(-np.abs(rng.standard_normal((d, n))) - 0.1, jnp.float32)
        Bm = jnp.asarray(rng.standard_normal((b, l, n)), jnp.float32)
        C = jnp.asarray(rng.standard_normal((b, l, n)), jnp.float32)
        x = jnp.asarray(rng.standard_normal((b, l, d)), jnp.float32)
        out = ops.mamba_scan(delta, A, Bm, C, x)
        expect = ref.mamba_scan_ref(delta, A, Bm, C, x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expect), atol=1e-4, rtol=1e-4
        )
