"""The pool's int64 key and value planes are stored as pairs of int32 word
planes ``[S, C * FANOUT]`` (high word, low word).  Splitting, joining and the
row gather and scatter on word planes must give exactly what the int64
forms give, for every bit pattern: bit 31 of the low word set, negative
values, 0, -1, ``KEY_MAX`` and ``KEY_MIN``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import dex as dex_mod
from repro.core import pool as pool_mod
from repro.core.nodes import FANOUT, KEY_MAX, KEY_MIN

SPECIAL = np.array(
    [0, -1, 1, KEY_MAX, KEY_MIN, 2**31 - 1, 2**31, 2**32 - 1, 2**32,
     2**32 + 2**31, -(2**31), -(2**31) - 1, -(2**32), 0x7FFFFFFF80000000,
     -0x7FFFFFFF, 0x123456789ABCDEF0 - 2**63],
    dtype=np.int64,
)


def _values(n, seed):
    """``n`` int64 values: the special patterns, then uniform 64-bit ones
    (half of which have bit 31 of the low word set)."""
    rng = np.random.default_rng(seed)
    rand = rng.integers(KEY_MIN, KEY_MAX, size=n, dtype=np.int64,
                        endpoint=True)
    out = np.concatenate([SPECIAL, rand])[:n]
    return rng.permutation(out)


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_split_words_is_the_hi_lo_convention(xp):
    x = _values(4096, seed=0)
    words = pool_mod.split_words(xp.asarray(x), xp)
    assert isinstance(words, pool_mod.Words)
    words = np.asarray(words)
    assert words.dtype == np.int32 and words.shape == (2,) + x.shape
    np.testing.assert_array_equal(words[0], (x >> 32).astype(np.int32))
    np.testing.assert_array_equal(
        words[1].view(np.uint32), (x & 0xFFFFFFFF).astype(np.uint32))
    kmax = np.asarray(pool_mod.split_words(KEY_MAX, xp))
    np.testing.assert_array_equal(kmax, [0x7FFFFFFF, -1])
    back = np.asarray(pool_mod.join_words(xp.asarray(words), xp))
    assert back.dtype == np.int64
    np.testing.assert_array_equal(back, x)


def _planes(s, c, seed):
    """An int64 plane ``[S, C * FANOUT]`` of mixed bit patterns and its
    word plane."""
    p64 = _values(s * c * FANOUT, seed).reshape(s, c * FANOUT)
    return p64, pool_mod.split_words(jnp.asarray(p64))


def _node_ids(s, c, n, seed):
    """Node ids with repeats, the last node of each axis and negative ids
    (numpy wrap)."""
    rng = np.random.default_rng(seed)
    st = rng.integers(-s, s, size=n).astype(np.int32)
    lo = rng.integers(-c, c, size=n).astype(np.int32)
    st[:3], lo[:3] = [s - 1, 0, -1], [c - 1, 0, -c]
    return st, lo


@pytest.mark.parametrize("shape", [(7, 3), (2, 5)])
def test_node_rows_on_word_planes_equal_the_int64_rows(shape):
    s, c = shape
    p64, words = _planes(s, c, seed=s)
    st, lo = _node_ids(s, c, 257, seed=c)
    got = pool_mod.node_rows(words, st, lo)
    assert got.dtype == jnp.int64 and got.shape == (257, FANOUT)
    want = pool_mod.blocks(p64)[st, lo]
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(pool_mod.node_rows(jnp.asarray(p64), st, lo)), want)
    # the batch shape is kept: [n_mem, cap, F] as the fetch gathers it
    got2 = pool_mod.node_rows(words, st[:256].reshape(4, 64),
                              lo[:256].reshape(4, 64))
    np.testing.assert_array_equal(np.asarray(got2),
                                  want[:256].reshape(4, 64, FANOUT))


@pytest.mark.parametrize("shape", [(7, 3), (2, 5)])
def test_set_node_rows_on_word_planes_equals_the_int64_scatter(shape):
    s, c = shape
    p64, words = _planes(s, c, seed=10 + s)
    # distinct nodes (a scatter's order among duplicates is unspecified)
    flat = np.random.default_rng(c).permutation(s * c)[: min(s * c, 9)]
    st, lo = (flat // c).astype(np.int32), (flat % c).astype(np.int32)
    rows = _values(st.size * FANOUT, seed=20 + s).reshape(-1, FANOUT)
    # out-of-range subtrees are dropped, as a masked writer sends them
    st_w = st.copy()
    st_w[::3] = s
    got = pool_mod.set_node_rows(words, st_w, lo, jnp.asarray(rows))
    assert isinstance(got, pool_mod.Words)
    assert all(w.dtype == jnp.int32 and w.shape == (s, c * FANOUT)
               for w in got)
    want = pool_mod.blocks(p64.copy())
    keep = st_w < s
    want[st_w[keep], lo[keep]] = rows[keep]
    np.testing.assert_array_equal(
        np.asarray(pool_mod.join_words(got)), want.reshape(s, -1))
    ref64 = pool_mod.set_node_rows(jnp.asarray(p64), st_w, lo,
                                   jnp.asarray(rows))
    np.testing.assert_array_equal(np.asarray(pool_mod.join_words(got)),
                                  np.asarray(ref64))


def test_word_row_access_traces_to_no_whole_plane_int64_array():
    """A jitted gather and scatter on a word plane joins and splits only
    the ``[n, FANOUT]`` rows: no int64 array of the plane's shape."""
    s, c = 5, 3
    _, words = _planes(s, c, seed=3)
    st, lo = _node_ids(s, c, 16, seed=4)

    def step(w, st, lo):
        rows = pool_mod.node_rows(w, st, lo)
        return pool_mod.set_node_rows(w, st, lo, rows + 1)

    hlo = jax.jit(step).lower(words, st, lo).as_text()
    assert f"{s}x{c * FANOUT}xi64" not in hlo
    assert f"{s}x{c * FANOUT}xi32" in hlo


def _pool_keys(n, seed):
    """Sorted unique keys over the whole int64 range (negative keys, keys
    with bit 31 of the low word set), and payloads of every pattern."""
    rng = np.random.default_rng(seed)
    k = np.unique(np.concatenate([
        SPECIAL[(SPECIAL != KEY_MAX)],
        rng.integers(KEY_MIN + 1, KEY_MAX - 1, size=n, dtype=np.int64),
    ]))
    return k, _values(k.size, seed + 1)


@pytest.mark.parametrize("level_m,n_shards", [(1, 1), (2, 2)])
def test_int64_views_round_trip_a_host_built_pool(level_m, n_shards):
    keys, vals = _pool_keys(6000, seed=level_m)
    pool, meta = pool_mod.build_pool_host(keys, vals, level_m=level_m,
                                          n_shards=n_shards)
    s, cf = meta.n_subtrees_padded, meta.subtree_cap * FANOUT
    for w in (*pool.pool_keys, *pool.pool_values):
        assert w.shape == (s, cf) and w.dtype == np.int32
    pk, pv = pool_mod.keys_i64(pool), pool_mod.values_i64(pool)
    assert pk.shape == pv.shape == (s, cf) and pk.dtype == np.int64
    np.testing.assert_array_equal(pool_mod.split_words(pk, np),
                                  pool.pool_keys)
    np.testing.assert_array_equal(pool_mod.split_words(pv, np),
                                  pool.pool_values)
    # the jnp view of the device planes is the same
    dev = jax.tree.map(jnp.asarray, pool)
    np.testing.assert_array_equal(
        np.asarray(pool_mod.keys_i64(dev, jnp)), pk)
    np.testing.assert_array_equal(
        np.asarray(pool_mod.values_i64(dev, jnp)), pv)
    # every key sits in a leaf with its payload, in order
    leaves = pool_mod.blocks(pk)[:, meta.leaf_start:meta.base_cap]
    leaf_v = pool_mod.blocks(pv)[:, meta.leaf_start:meta.base_cap]
    real = leaves != KEY_MAX
    np.testing.assert_array_equal(leaves[real], keys)
    np.testing.assert_array_equal(leaf_v[real], vals)
    # and the reference lookup over the word planes finds each of them
    found, got = pool_mod.pool_lookup_ref(dev, meta, jnp.asarray(keys))
    assert bool(np.all(np.asarray(found)))
    np.testing.assert_array_equal(np.asarray(got), vals)


def test_init_state_occupancy_counts_real_keys_of_the_word_plane():
    keys, vals = _pool_keys(3000, seed=7)
    pool, meta = pool_mod.build_pool(keys, vals, level_m=1)
    cfg = dex_mod.DexMeshConfig()
    state = dex_mod.init_state(pool, meta, cfg,
                               np.array([KEY_MIN, KEY_MAX], np.int64))
    want = (pool_mod.blocks(pool_mod.keys_i64(pool)) != KEY_MAX).sum(-1)
    np.testing.assert_array_equal(np.asarray(state.occupancy), want)
    assert state.occupancy.dtype == jnp.int32
