"""Leaf-direct route-table trainer for the mesh plane (DESIGN.md §13).

DEX's central claim is that fewer remote accesses win on disaggregated
memory (paper §1), yet every mesh op pays a full cached inner descent
before touching a leaf.  Outback (PAPERS.md) resolves location
compute-side in ~one round with a learned mapping; this module is that
analogue for the subtree-blocked pool: a **piecewise-linear index over the
observed key hull** whose segments are the leaves' fence ranges.  The
trained table is four replicated arrays on :class:`~repro.core.dex.DexState`
(``rt_keys``/``rt_hi``/``rt_sub``/``rt_local``/``rt_ver``); predicting a
leaf is one ``searchsorted`` against ``rt_keys``
(:func:`repro.core.routing.rt_predict`) — no collective, no remote read.

Correctness never depends on the table: the engine accepts a guess only
under :func:`repro.core.fleet_cache.rt_accept`'s fence-key bounds + leaf
version fence, so the trainer is free to be approximate.  When the pool
holds more leaves than ``cfg.route_table_slots``, the trainer keeps the
leaves of the **demand-hottest partitions first** (``DexState.route_demand``
is the same source-side load signal the repartition controller uses), so
the table's capacity chases the workload like the paper's cooling map
chases cache capacity.

Training runs host-side between batches (exactly like the repartition
controller's decisions): bulk load, the controller's boundary installs
(``RepartitionController.maybe_repartition`` retrains automatically after
an install when the table is active) and explicit benchmark calls after a
hotspot shift.  A *stale* table needs no retraining for correctness —
every insert/update/split/repartition move bumps the leaf's version, so
the fence rejects moved entries and those lanes simply pay full descent
until the next train.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.dex import DexState
from repro.core.nodes import KEY_MAX
from repro.core.pool import PoolMeta, keys_i64
from repro.core.repartition import node_key_ranges


def route_table_active(state: DexState) -> bool:
    """Host-side check: does the state carry any trained (live) entry?"""
    return bool(np.any(np.asarray(state.rt_ver) >= 0))


def leaf_ranges(state: DexState, meta: PoolMeta):
    """Fence ranges of every real leaf: ``(gids, lo, hi)`` sorted by ``lo``
    (the children-graph walk of :func:`node_key_ranges` keeps working after
    on-mesh splits relocate leaves into free-list headroom)."""
    gids, lo, hi, lvl = node_key_ranges(
        keys_i64(state.pool), meta,
        np.asarray(state.pool.pool_children), with_levels=True,
    )
    keep = lvl == 0
    gids, lo, hi = gids[keep], lo[keep], hi[keep]
    order = np.argsort(lo, kind="stable")
    return gids[order], lo[order], hi[order]


def train_route_table(
    state: DexState,
    meta: PoolMeta,
    *,
    slots: Optional[int] = None,
    mesh=None,
) -> DexState:
    """(Re)train the leaf-direct route table from the current pool.

    Builds the piecewise-linear segment table over the leaves' fence
    ranges, stamps each entry with the leaf's *current* version (the
    fence the engine later verifies), and — when leaves outnumber
    ``slots`` — keeps the leaves of the demand-hottest route partitions
    (ties broken toward lower keys, so the kept set stays contiguous-ish
    and the searchsorted gaps reject cleanly).  Returns the new state;
    pass ``mesh`` to re-commit the replicated arrays with the same
    ``P()`` sharding ``state_shardings`` uses.
    """
    r = int(state.rt_keys.shape[0]) if slots is None else int(slots)
    gids, lo, hi = leaf_ranges(state, meta)
    if gids.size > r:
        boundaries = np.asarray(state.boundaries, np.int64)
        n_route = boundaries.shape[0] - 1
        demand = np.asarray(state.route_demand, np.int64).sum(axis=0)
        owner = np.clip(
            np.searchsorted(boundaries, lo, side="right") - 1, 0, n_route - 1
        )
        # hot partitions first; stable sort keeps key order within a
        # partition so the kept prefix is a union of hot key ranges
        hot = np.argsort(-demand[owner], kind="stable")[:r]
        keep = np.sort(hot)
        gids, lo, hi = gids[keep], lo[keep], hi[keep]
    vers = np.asarray(state.versions)[0]
    n = gids.size
    rt_keys = np.full((r,), KEY_MAX, np.int64)
    rt_hi = np.full((r,), KEY_MAX, np.int64)
    rt_sub = np.zeros((r,), np.int32)
    rt_local = np.zeros((r,), np.int32)
    rt_ver = np.full((r,), -1, np.int32)
    rt_keys[:n] = lo
    rt_hi[:n] = hi
    rt_sub[:n] = (gids // meta.subtree_cap).astype(np.int32)
    rt_local[:n] = (gids % meta.subtree_cap).astype(np.int32)
    rt_ver[:n] = vers[gids]
    arrs = dict(
        rt_keys=jnp.asarray(rt_keys),
        rt_hi=jnp.asarray(rt_hi),
        rt_sub=jnp.asarray(rt_sub),
        rt_local=jnp.asarray(rt_local),
        rt_ver=jnp.asarray(rt_ver),
    )
    if mesh is not None:
        rep = jax.sharding.NamedSharding(mesh, P())
        arrs = {k: jax.device_put(v, rep) for k, v in arrs.items()}
    return state._replace(**arrs)


def poison_route_table(state: DexState) -> DexState:
    """Adversarial-table helper for tests and the fig20 fallback arm: bump
    every live entry's train-time version stamp so the engine's version
    fence rejects **every** guess.  The contract under test: a fully
    poisoned table yields bit-identical results to descent-only mode (all
    guesses become ``rt_mispredicts``; no probe is ever mis-accepted).

    The bump is large so later writes cannot re-arm an entry mid-trace: a
    +1 bump aliases with the version bump of a single write to that leaf
    (a benign accept — the fence compares the CURRENT version — but it
    would break the all-mispredict contract tests pin)."""
    ver = np.asarray(state.rt_ver).copy()
    ver[ver >= 0] += 1 << 20
    return state._replace(rt_ver=jnp.asarray(ver))
