"""Compile the served Pallas kernels and the jitted engine step for a
described TPU v5e (no chip attached): the TPU compiler refuses misaligned
blocks, 64-bit values inside a kernel and lowerings Mosaic lacks, none of
which interpret mode sees.  Shapes are the served ones: batch 1024 routed
to 2048 rows, ``FANOUT`` 64, ``max_count`` 128.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import dex as dex_mod
from repro.core import engine as engine_mod
from repro.core import pool as pool_mod
from repro.core import smo as smo_mod
from repro.core.nodes import FANOUT, KEY_MAX, KEY_MIN
from repro.kernels.leaf_scan import leaf_scan
from repro.kernels.leaf_split import leaf_split
from repro.kernels.leaf_write import leaf_write

ROWS = 2048          # a 1024-lane batch routed with capacity factor 2
MAX_COUNT = 128
WINDOW = 5 * FANOUT  # scan_hops(max_count=128) leaves at fill 0.7


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_leaf_scan_compiles(one_chip):
    i64, i32 = jnp.int64, jnp.int32
    fn = jax.jit(lambda k, v, s, c: leaf_scan(
        k, v, s, c, max_count=MAX_COUNT, interpret=False))
    _assert_kernel(fn.lower(
        _shape(one_chip, (ROWS, WINDOW), i64),
        _shape(one_chip, (ROWS, WINDOW), i64),
        _shape(one_chip, (ROWS,), i64),
        _shape(one_chip, (ROWS,), i32),
    ).compile())


def test_leaf_write_compiles(one_chip):
    i64, i32 = jnp.int64, jnp.int32
    fn = jax.jit(lambda *a: leaf_write(*a, interpret=False))
    _assert_kernel(fn.lower(
        _shape(one_chip, (ROWS, FANOUT), i64),
        _shape(one_chip, (ROWS, FANOUT), i64),
        _shape(one_chip, (ROWS, FANOUT), i32),
        _shape(one_chip, (ROWS, FANOUT), i64),
        _shape(one_chip, (ROWS, FANOUT), i64),
        _shape(one_chip, (ROWS, FANOUT), i64),
    ).compile())


def test_leaf_split_compiles(one_chip):
    i64 = jnp.int64
    fn = jax.jit(lambda *a: leaf_split(*a, interpret=False))
    _assert_kernel(fn.lower(
        *(_shape(one_chip, (ROWS, FANOUT), i64) for _ in range(4))
    ).compile())


def test_mesh_helper_on_described_2x2(topo):
    mesh = dex_mod.make_dex_mesh(topo.devices)
    assert dict(mesh.shape) == {"data": 2, "model": 2}
    assert len(set(mesh.devices.flat)) == 4
    one = dex_mod.make_dex_mesh(topo.devices[:1])
    assert dict(one.shape) == {"data": 1, "model": 1}


def _one_chip_state(topo):
    """A 1x1 mesh on the described chip and the shapes of a DexState over
    a 20,000-key pool on it."""
    keys = np.arange(1, 20_001, dtype=np.int64) * 4
    pool, meta = pool_mod.build_pool(keys, keys * 7, level_m=1, fill=0.7)
    mesh = dex_mod.make_dex_mesh(topo.devices[:1])
    cfg = dex_mod.DexMeshConfig(n_route=1, n_memory=1)
    bounds = np.array([KEY_MIN, KEY_MAX], np.int64)
    state = jax.eval_shape(
        lambda p: dex_mod.init_state(p, meta, cfg, bounds), pool
    )
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, dex_mod.state_shardings(mesh, cfg),
    )
    return state, meta, cfg, mesh, NamedSharding(mesh, P(cfg.all_axes))


def _kernels(hlo):
    """Names of the program's Pallas kernels: the instruction names of its
    TPU custom calls (the trace reduction finds the kernels by them)."""
    return set(re.findall(r"%(\w+?)(?:\.\d+)? = .*custom-call\(.*"
                          r"tpu_custom_call", hlo))


def _assert_no_int64_pool_plane(hlo, meta):
    """The pool's key and value planes are int32 words: the program holds
    no int64 array of a plane's shape (the compiler's X64 split and combine
    of a whole plane would make one)."""
    plane = f"s64[{meta.n_subtrees_padded},{meta.subtree_cap * FANOUT}]"
    assert plane not in hlo, f"{hlo.count(plane)} x {plane}"


def test_engine_step_compiles_with_kernels(topo):
    """The jitted 1x1 engine step, all four opcodes, kernels compiled in."""
    state, meta, cfg, mesh, lanes = _one_chip_state(topo)
    step = jax.jit(engine_mod.make_dex_engine(
        meta, cfg, mesh, max_count=MAX_COUNT, interpret=False
    ), donate_argnums=0)
    compiled = step.lower(
        state,
        _shape(lanes, (1024,), jnp.int32),
        _shape(lanes, (1024,), jnp.int64),
        _shape(lanes, (1024,), jnp.int64),
    ).compile()
    _assert_kernel(compiled)
    hlo = compiled.as_text()
    assert _kernels(hlo) == {"leaf_scan", "leaf_write"}
    _assert_no_int64_pool_plane(hlo, meta)


def test_smo_round_compiles_with_kernels(topo):
    """The jitted 1x1 SMO round: ``leaf_split`` compiled in, and no int64
    pool plane either."""
    state, meta, cfg, mesh, lanes = _one_chip_state(topo)
    rnd = jax.jit(smo_mod.make_dex_smo(meta, cfg, mesh, interpret=False),
                  donate_argnums=0)
    compiled = rnd.lower(
        state,
        _shape(lanes, (1024,), jnp.int64),
        _shape(lanes, (1024,), jnp.int64),
    ).compile()
    _assert_kernel(compiled)
    hlo = compiled.as_text()
    assert _kernels(hlo) == {"leaf_split", "leaf_write"}
    _assert_no_int64_pool_plane(hlo, meta)
