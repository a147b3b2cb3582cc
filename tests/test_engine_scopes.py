"""The engine step names every op it emits: each instruction of the compiled
program whose ``op_name`` is a path of the program sits under exactly one
``dex/<family>`` scope listed in ``plan["phases"]`` (a profiler trace
attributes an op to the first ``dex/`` component of its op_name, so a family
nested in another would hide the inner one).  Also: the offload audit's
O(lanes) count of distinct fetched nodes equals the node-bitmap form it
replaced.
"""

import json
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh_compat
from repro.core import dex as dex_mod
from repro.core import engine as engine_mod
from repro.core import fleet_cache
from repro.core import pool as pool_mod
from repro.core.nodes import KEY_MAX, KEY_MIN

LANES = 64
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_FAMILY = re.compile(r"(?:^|/)(dex/[A-Za-z0-9_]+)")

#: op_names that are not paths of the program, by what JAX names with them
ALLOWED = {
    # JAX names a program's arguments by their path in the call; the
    # compiler keeps that name on the parameter and on bitcasts of it
    "argument": re.compile(r"^(state(\.\w+)+|opcodes|keys|values|carry.*)$"),
    # JAX names the ops of the reducers and comparators it makes for reduce,
    # scatter, sort and reduce-window by the bare primitive; the compiler
    # keeps the name where it inlines such a body
    "reducer": re.compile(r"^[a-z_]+$"),
    # on a mesh of several devices, the partitioner makes the shard_map's
    # boundary (partition-id iotas, constants, broadcasts, copies) and names
    # it after the shard_map
    "shard_map": re.compile(r"^jit\(\w+\)/shard_map(/broadcast\.\d+)?$"),
}


def _engine(variant, n_route=1, n_memory=1):
    keys = np.arange(1, 8001, dtype=np.int64) * 4
    pool, meta = pool_mod.build_pool(keys, keys * 5, level_m=1, fill=0.7,
                                     n_shards=n_memory)
    mesh = make_mesh_compat((n_route, n_memory), ("data", "model"))
    kw = {"policy": variant} if variant in ("fetch", "offload") else {}
    if variant == "divergent":
        kw["route_table_slots"] = 64
    cfg = dex_mod.DexMeshConfig(n_route=n_route, n_memory=n_memory, **kw)
    bounds = np.array([KEY_MIN] + [16_000] * (n_route - 1) + [KEY_MAX],
                      np.int64)
    state = jax.device_put(dex_mod.init_state(pool, meta, cfg, bounds),
                           dex_mod.state_shardings(mesh, cfg))
    policy = (fleet_cache.divergent_policy(cfg) if variant == "divergent"
              else None)
    fn = engine_mod.make_dex_engine(meta, cfg, mesh, max_count=32,
                                    pipeline=variant == "pipeline",
                                    cache_policy=policy)
    lanes = NamedSharding(mesh, P(cfg.all_axes))
    opc = jax.device_put(jnp.zeros((LANES,), jnp.int32), lanes)
    kk = jax.device_put(jnp.full((LANES,), KEY_MAX, jnp.int64), lanes)
    if variant == "pipeline":
        lowered = jax.jit(fn.step_fn).lower(state, fn.init_carry(LANES),
                                            opc, kk, kk)
    else:
        lowered = jax.jit(fn, donate_argnums=0).lower(state, opc, kk, kk)
    return lowered.compile().as_text(), fn.plan


def _op_names(hlo):
    """``(computation, opcode, op_name)`` of every instruction that carries
    an op_name, leaving out the reducer and comparator computations
    (``to_apply``), which run inside the op that calls them."""
    reducers = set(re.findall(r"to_apply=%([\w.\-]+)", hlo))
    comp, out = None, []
    for line in hlo.splitlines():
        cm = _COMPUTATION.match(line)
        if cm:
            comp = cm.group(1)
            continue
        im = _INSTR.match(line)
        om = _OP_NAME.search(line)
        if not im or not om or comp in reducers:
            continue
        op = _OPCODE.search(im.group(2))
        out.append((comp, op.group(1) if op else "", om.group(1)))
    return out


def _families(variant, n_route=1, n_memory=1):
    """The scope families the compiled step's ops sit under, and the ops
    outside them: ``(seen, stray)``."""
    hlo, plan = _engine(variant, n_route, n_memory)
    phases = set(plan["phases"])
    seen, stray = set(), []
    for comp, opcode, name in _op_names(hlo):
        fams = _FAMILY.findall(name)
        if fams:
            if len(fams) == 1 and fams[0] in phases:
                seen.add(fams[0])
            else:
                stray.append((comp, opcode, name))
            continue
        why = [k for k, pat in ALLOWED.items() if pat.match(name)]
        if not why or (why == ["argument"]
                       and opcode not in ("parameter", "bitcast")):
            stray.append((comp, opcode, name))
    return seen, stray


@pytest.mark.parametrize(
    "variant", ["auto", "fetch", "offload", "divergent", "pipeline"]
)
def test_every_op_of_the_step_is_in_one_phase_family(variant):
    seen, stray = _families(variant)
    assert not stray, stray[:20]
    # on one chip the exchanges are no-ops and emit nothing; the descent,
    # the apply, the plumbing and the ledger run in every variant
    assert {"dex/descent", "dex/apply", "dex/lanes", "dex/locate",
            "dex/lat", "dex/coherence"} <= seen, seen
    if variant in ("auto", "offload", "divergent"):
        assert "dex/offload" in seen


@pytest.mark.parametrize("variant", ["auto", "pipeline"])
def test_every_op_on_a_2x2_mesh_is_in_one_phase_family(variant):
    # a mesh of four devices adds the exchanges, the psums and the
    # partitioner's shard_map boundary; it needs its own process
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    here = pathlib.Path(__file__).parent
    env["PYTHONPATH"] = os.pathsep.join(
        [str(here.parent / "src"), str(here), env.get("PYTHONPATH", "")])
    res = subprocess.run(
        [sys.executable, "-c",
         f"import test_engine_scopes as t; t._print_families({variant!r})"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    seen, stray = json.loads(res.stdout.strip().splitlines()[-1])
    assert not stray, stray[:20]
    assert {"dex/route", "dex/descent", "dex/scan", "dex/fused_a2a",
            "dex/apply", "dex/route_back", "dex/locate", "dex/offload",
            "dex/coherence", "dex/lanes", "dex/lat"} <= set(seen), seen


def _print_families(variant):
    assert len(jax.devices()) == 4, jax.devices()
    seen, stray = _families(variant, n_route=2, n_memory=2)
    print(json.dumps([sorted(seen), stray[:20]]))


def _bitmap_counts(gid, fetched, n_nodes, subtree_cap, s_per, n_memory):
    """The audit's count as it was: a bitmap over every node of the pool,
    reduced along the node -> column map."""
    nset = jnp.zeros((n_nodes,), jnp.float32).at[
        jnp.where(fetched, gid, n_nodes)
    ].set(1.0, mode="drop")
    node_col = ((jnp.arange(n_nodes) // subtree_cap) // s_per).astype(
        jnp.int32)
    return jnp.zeros((n_memory,), jnp.float32).at[node_col].add(nset)


@pytest.mark.parametrize("seed,n_memory", [(0, 2), (1, 3), (2, 4), (3, 2)])
def test_audit_count_over_lanes_equals_the_node_bitmap(seed, n_memory):
    rng = np.random.default_rng(seed)
    subtree_cap, s_per = 7, 5
    # the sentinel (n_nodes) falls inside the last column's node range
    n_nodes = subtree_cap * s_per * n_memory - 3
    lanes = 512
    # few distinct gids, so runs of duplicates; some at the sentinel
    gid = rng.integers(0, n_nodes // 3, lanes) * 3
    gid[rng.random(lanes) < 0.1] = n_nodes
    fetched = rng.random(lanes) < 0.6
    gid, fetched = jnp.asarray(gid, jnp.int64), jnp.asarray(fetched)
    got = engine_mod.distinct_fetched_per_column(
        gid, fetched, n_nodes, subtree_cap, s_per, n_memory)
    want = _bitmap_counts(gid, fetched, n_nodes, subtree_cap, s_per,
                          n_memory)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(got).sum() > 0 and (np.asarray(got) > 0).sum() >= 2
    # nothing fetched counts nothing
    none = engine_mod.distinct_fetched_per_column(
        gid, jnp.zeros_like(fetched), n_nodes, subtree_cap, s_per, n_memory)
    assert not np.asarray(none).any()
