"""Mesh-plane DEX tests.

The multi-device exercise runs in a subprocess (tests/mesh_check.py) because
device count is locked at first JAX init and the main pytest session must
keep a single device.  Single-device pool/reference tests run inline.
"""

import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import pool as pool_mod


def _dataset(n, seed=0):
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(8 * n, size=n, replace=False).astype(np.int64) + 1)


class TestSubtreePool:
    @pytest.mark.parametrize("level_m", [0, 1, 2])
    def test_build_and_ref_lookup(self, level_m):
        keys = _dataset(5000, seed=level_m)
        pool, meta = pool_mod.build_pool(keys, keys * 3, level_m=level_m, n_shards=4)
        assert meta.n_subtrees_padded % 4 == 0
        q = np.concatenate([keys[::11], keys[::17] + 1])
        found, vals = pool_mod.pool_lookup_ref(pool, meta, q)
        found, vals = np.asarray(found), np.asarray(vals)
        expect = np.isin(q, keys)
        np.testing.assert_array_equal(found, expect)
        np.testing.assert_array_equal(vals[expect], q[expect] * 3)

    def test_host_build_matches_device_build(self):
        keys = _dataset(3000, seed=7)
        host, meta_h = pool_mod.build_pool_host(keys, keys * 3, level_m=1,
                                                n_shards=2)
        dev, meta_d = pool_mod.build_pool(keys, keys * 3, level_m=1,
                                          n_shards=2)
        assert meta_h == meta_d
        for h, d in zip(jax.tree.leaves(host), jax.tree.leaves(dev),
                        strict=True):
            assert isinstance(h, np.ndarray)
            np.testing.assert_array_equal(h, np.asarray(d))

    def test_single_subtree(self):
        keys = np.arange(1, 30, dtype=np.int64)
        pool, meta = pool_mod.build_pool(keys, level_m=1, n_shards=1)
        assert meta.n_subtrees == 1
        found, vals = pool_mod.pool_lookup_ref(pool, meta, keys)
        assert bool(np.all(np.asarray(found)))

    def test_subtree_walk_ref_matches(self):
        keys = _dataset(3000, seed=5)
        pool, meta = pool_mod.build_pool(keys, level_m=1, n_shards=1)
        st = pool_mod.top_walk(pool, meta, keys[:256])
        st = np.asarray(st)
        # all queries routed to subtree holding them; walk block 0 queries
        q0 = keys[:256][st == 0]
        if q0.size:
            f, v = pool_mod.subtree_walk_ref(
                pool_mod.keys_i64(pool)[0],
                pool.pool_children[0],
                pool_mod.values_i64(pool)[0],
                q0,
                levels=meta.levels_in_subtree,
            )
            assert bool(np.all(np.asarray(f)))
            np.testing.assert_array_equal(np.asarray(v), q0)


@pytest.mark.slow
def test_mesh_dex_subprocess():
    """Full multi-device routing/cache/offload check on 8 fake devices."""
    here = pathlib.Path(__file__).parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(here.parent / "src") + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, str(here / "mesh_check.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "MESH_CHECK_OK" in res.stdout
