"""Live skew-adaptive logical repartitioning for the mesh plane (paper §4,
Fig. 10).

The mesh ops (core/dex.py, core/scan.py, core/write.py) route every request
to the compute partition owning its key and *load-shed* whatever overflows a
routing bucket — honest back-pressure, but a dead end under sustained skew:
the shed lanes retry into the same overloaded partition forever.  The
paper's systemic fix is logical repartitioning: the boundary table is
metadata, so moving boundaries toward the load costs one table update plus a
dirty-cache flush (< 2 s, Fig. 10), never a data move.

:class:`RepartitionController` closes that loop between batches:

1. **Accumulate** per-partition load from the ops' counters.  The primary
   signal is ``DexState.route_demand`` — routed requests per partition
   counted at the *source* chip before bucketing, so shed lanes count too
   and the signal never saturates at bucket capacity the way the served
   ``STAT_OPS`` does; ``STAT_DROPS`` (summed over the route-major device
   grid) feeds the trigger.  The controller also tracks the observed key
   hull (min/max routed key) so the rebalance walk stays inside real key
   space.
2. **Decide**: when the max/mean served-load imbalance crosses
   ``imbalance_threshold`` (or drops exceed ``drop_frac`` of ops) after at
   least ``min_ops`` accumulated, call the fixed
   :meth:`LogicalPartitions.rebalance` — count-preserving, hull-confined —
   for a new boundary table.
3. **Install** (:func:`install_boundaries`): swap the replicated boundary
   table inside :class:`DexState` (all ops read it per batch, so the next
   batch routes under the new table with no recompilation), bump the
   per-node version table for every pool node whose key range changed
   owner — the existing ``DexState.versions`` coherence machinery then
   rejects now-foreign cached rows on their next probe, exactly like a
   write-invalidate — and re-derive which nodes are *shared* (fence range
   crossing a boundary: cached everywhere, never owner-private) under the
   new table.

Because repartitioning is logical, the memory-side pool, occupancy and the
host mirror are untouched; results before and after a boundary change are
bit-identical (tests/mesh_check.py exercises the round trip).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import fleet_cache
from repro.core.dex import N_STATS, STAT_DROPS, STAT_OPS, DexState
from repro.core.nodes import KEY_MAX, KEY_MIN
from repro.core.partition import LogicalPartitions
from repro.core.pool import PoolMeta, blocks, keys_i64


@dataclasses.dataclass(frozen=True)
class RepartitionConfig:
    """Trigger policy for the controller."""

    imbalance_threshold: float = 1.25  # max/mean demand ratio
    drop_frac: float = 0.01            # drops / ops that force a trigger
    min_ops: int = 1024                # accumulate at least this many ops
    cooldown_batches: int = 1          # maybe_repartition() decisions to
    #                                    skip after an install


@dataclasses.dataclass
class RepartitionReport:
    """What one boundary install did (returned by ``maybe_repartition``)."""

    old_boundaries: np.ndarray
    new_boundaries: np.ndarray
    loads: np.ndarray                  # per-partition served ops this window
    drops: int                         # routing-bucket drops this window
    imbalance: float                   # max/mean of ``loads``
    fraction_keyspace_moved: float     # LogicalPartitions.assignment_diff
    nodes_invalidated: int             # pool nodes whose version was bumped
    shared_nodes_before: int           # boundary-crossing nodes, old table
    shared_nodes_after: int            # boundary-crossing nodes, new table


def node_key_ranges(
    pool_keys: np.ndarray, meta: PoolMeta,
    pool_children: "np.ndarray | None" = None,
    *,
    with_levels: bool = False,
):
    """Per-node fence ranges ``(gids, lo, hi)`` for every real pool node,
    from the int64 key plane ``pool_keys`` (:func:`repro.core.pool.keys_i64`).

    Each node's range runs from its first key to the next node's first key
    at the same level (the leftmost node of a level covers from ``KEY_MIN``
    — the in-node search clamps slot 0 — and the rightmost to ``KEY_MAX``).
    Node levels are derived by walking the children graph from each block's
    root rather than from local-id offsets: on-mesh splits (core/smo.py)
    allocate siblings from the free-list headroom, so after the first split
    a node's level is no longer a function of its slot.  Pass
    ``pool_children`` whenever the pool may have seen on-mesh splits; when
    omitted, the dense bulk layout is assumed (bulk-built pools only).
    With ``with_levels=True`` a fourth array of per-node tree levels (0 =
    leaf) is returned — the leaf-direct route-table trainer
    (core/route_table.py) uses it to keep only leaf fence ranges.
    """
    pk0 = blocks(np.asarray(pool_keys))[:, :, 0]      # [S, C] first keys
    n_sub, cap = pk0.shape
    lvl_of = np.full((n_sub, cap), -1, np.int32)
    lvl_of[:, 0] = meta.level_m                       # block roots
    if pool_children is not None:
        pc = blocks(np.asarray(pool_children))
        for lvl in range(meta.level_m, 0, -1):
            s_idx, c_idx = np.where(lvl_of == lvl)
            if s_idx.size == 0:
                break
            ch = pc[s_idx, c_idx]                     # [K, FANOUT]
            valid = (ch >= 0) & (ch < cap)
            s_rep = np.broadcast_to(s_idx[:, None], ch.shape)[valid]
            lvl_of[s_rep, ch[valid]] = lvl - 1
    else:
        from repro.core.pool import _level_offsets

        offs = _level_offsets(
            meta.per_node, meta.level_m, meta.leaves_per_subtree
        )
        for lvl in range(meta.level_m + 1):
            lvl_of[:, int(offs[lvl]) : int(offs[lvl + 1])] = (
                meta.level_m - lvl
            )
    base = np.arange(n_sub, dtype=np.int64) * meta.subtree_cap
    gid_grid = base[:, None] + np.arange(cap, dtype=np.int64)[None, :]
    all_gids: List[np.ndarray] = []
    all_lo: List[np.ndarray] = []
    all_hi: List[np.ndarray] = []
    all_lvl: List[np.ndarray] = []
    for lvl in range(meta.level_m, -1, -1):
        real = (lvl_of == lvl) & (pk0 != KEY_MAX)
        lo_r = pk0[real]
        gid_r = gid_grid[real]
        # global key order within the level: subtrees are key-ordered and
        # ranges within a level are disjoint, so first-key order is it
        order = np.argsort(lo_r, kind="stable")
        lo_r = lo_r[order]
        gid_r = gid_r[order]
        if lo_r.size:
            hi_r = np.concatenate([lo_r[1:], [KEY_MAX]])
            lo_r = lo_r.copy()
            lo_r[0] = KEY_MIN
        else:
            hi_r = np.zeros((0,), np.int64)
        all_gids.append(gid_r)
        all_lo.append(lo_r)
        all_hi.append(hi_r)
        all_lvl.append(np.full(gid_r.shape, lvl, np.int32))
    out = (
        np.concatenate(all_gids),
        np.concatenate(all_lo),
        np.concatenate(all_hi),
    )
    if with_levels:
        return out + (np.concatenate(all_lvl),)
    return out


def moved_intervals(
    old: LogicalPartitions, new: LogicalPartitions
) -> List[Tuple[int, int]]:
    """Key intervals ``[a, b)`` whose owning partition changes between the
    two tables (ownership is piecewise constant on the merged boundaries)."""
    pts = np.unique(
        np.concatenate([old.boundaries, new.boundaries]).astype(np.int64)
    )
    starts = pts[:-1]
    changed = old.owner_of(starts) != new.owner_of(starts)
    out: List[Tuple[int, int]] = []
    for i in np.where(changed)[0]:
        a, b = int(pts[i]), int(pts[i + 1])
        if out and out[-1][1] == a:
            out[-1] = (out[-1][0], b)      # coalesce adjacent intervals
        else:
            out.append((a, b))
    return out


def install_boundaries(
    state: DexState,
    meta: PoolMeta,
    old: LogicalPartitions,
    new: LogicalPartitions,
) -> Tuple[DexState, int, int, int]:
    """Install ``new`` boundaries into ``state`` (logical repartitioning).

    Swaps the replicated boundary table and bumps ``DexState.versions`` for
    every pool node whose fence range intersects a moved key interval, so
    each chip's cached copy of a now-foreign (or newly-owned) row fails the
    version check on its next probe and is re-fetched — the mesh analogue of
    the paper's dirty-flush + cache re-warm.  The pool itself never moves.
    Returns ``(new_state, nodes_invalidated, shared_before, shared_after)``.
    """
    gids, lo, hi = node_key_ranges(
        keys_i64(state.pool), meta, state.pool.pool_children
    )
    moved = moved_intervals(old, new)
    affected = np.zeros(gids.shape, dtype=bool)
    for a, b in moved:
        affected |= (lo < b) & (hi > a)
    shared_before = int(np.sum(np.asarray(old.is_shared_range(lo, hi))))
    shared_after = int(np.sum(np.asarray(new.is_shared_range(lo, hi))))
    new_state = state._replace(
        boundaries=jnp.asarray(new.boundaries, jnp.int64),
        versions=fleet_cache.invalidate_nodes(
            state.versions, gids[affected]
        ),
    )
    return new_state, int(affected.sum()), shared_before, shared_after


class RepartitionController:
    """Between-batch control loop turning load shedding into repartitioning.

    Usage (see ``benchmarks/fig10_mesh_repartition.py``)::

        ctl = RepartitionController(parts, n_memory=cfg.n_memory)
        for batch in trace:
            state, ... = op(state, batch_keys, ...)
            ctl.observe(np.asarray(state.stats), batch_keys)
            state, report = ctl.maybe_repartition(state, meta)
            # report is None unless boundaries moved this batch

    The controller never touches device state except through
    :func:`install_boundaries`, and survives ``drain_splits`` pool rebuilds
    (stats carry over; node ranges are re-derived from the current pool at
    install time).
    """

    def __init__(
        self,
        parts: LogicalPartitions,
        *,
        n_memory: int,
        cfg: Optional[RepartitionConfig] = None,
    ):
        self.parts = parts
        self.n_memory = int(n_memory)
        self.cfg = cfg or RepartitionConfig()
        self._last_stats: Optional[np.ndarray] = None
        self._last_demand: Optional[np.ndarray] = None
        self._loads = np.zeros((parts.num_partitions,), np.float64)
        self._drops = 0
        self._ops = 0
        self._cooldown = 0
        self._key_lo: Optional[int] = None
        self._key_hi: Optional[int] = None
        self.reports: List[RepartitionReport] = []

    # -- accumulation --------------------------------------------------------

    def observe(
        self,
        stats: np.ndarray,
        keys: Optional[np.ndarray] = None,
        demand: Optional[np.ndarray] = None,
    ):
        """Fold one batch's cumulative counters into the window.

        ``stats`` is ``DexState.stats`` (``[Dev, N_STATS]``); ``demand`` is
        ``DexState.route_demand`` (``[Dev, n_route]``), the preferred load
        signal — without it the controller falls back to the served
        ``STAT_OPS``, which saturates at bucket capacity under heavy skew.
        ``keys`` (the batch's routed keys) tightens the key hull used to
        confine the rebalance walk — always pass it when available: without
        an observed hull a two-partition table has no data-extent
        information at all and its boundary barely moves (see
        :meth:`LogicalPartitions.rebalance`).
        """
        stats = np.asarray(stats, dtype=np.int64)
        assert stats.ndim == 2 and stats.shape[1] == N_STATS
        if self._last_stats is None:
            delta = stats
        else:
            delta = stats - self._last_stats
        self._last_stats = stats.copy()
        n_route = self.parts.num_partitions
        per_dev = delta.reshape(n_route, self.n_memory, N_STATS)
        if demand is not None:
            demand = np.asarray(demand, dtype=np.int64)
            prev = (
                self._last_demand
                if self._last_demand is not None
                else np.zeros_like(demand)
            )
            d_delta = demand - prev
            self._last_demand = demand.copy()
            self._loads += d_delta.sum(axis=0).astype(np.float64)
            # gate the window on demand, not served ops: under heavy skew
            # the served count loses exactly the dropped lanes whose load
            # signal we are here to act on
            self._ops += int(d_delta.sum())
        else:
            self._loads += per_dev[:, :, STAT_OPS].sum(axis=1).astype(
                np.float64
            )
            self._ops += int(per_dev[:, :, STAT_OPS].sum())
        self._drops += int(per_dev[:, :, STAT_DROPS].sum())
        if keys is not None:
            keys = np.asarray(keys, dtype=np.int64)
            keys = keys[keys != KEY_MAX]                 # inactive lanes
            if keys.size:
                lo, hi = int(keys.min()), int(keys.max())
                self._key_lo = lo if self._key_lo is None else min(self._key_lo, lo)
                self._key_hi = hi if self._key_hi is None else max(self._key_hi, hi)

    @property
    def imbalance(self) -> float:
        """Max/mean served-load ratio of the current window."""
        if self._loads.sum() <= 0:
            return 1.0
        return float(self._loads.max() / self._loads.mean())

    def should_repartition(self) -> bool:
        if self._cooldown > 0 or self._ops < self.cfg.min_ops:
            return False
        if self.imbalance >= self.cfg.imbalance_threshold:
            return True
        return self._drops > self.cfg.drop_frac * max(self._ops, 1)

    # -- the decision + install ---------------------------------------------

    def propose(self) -> LogicalPartitions:
        """New boundary table for the accumulated window's loads."""
        key_range = (
            (self._key_lo, self._key_hi)
            if self._key_lo is not None and self._key_lo < self._key_hi
            else None
        )
        return self.parts.rebalance(self._loads, key_range=key_range)

    def maybe_repartition(
        self, state: DexState, meta: PoolMeta, *, obs=None
    ) -> Tuple[DexState, Optional[RepartitionReport]]:
        """Repartition if the trigger fires; returns the (possibly new)
        state and a report when boundaries actually moved.  The first
        ``cooldown_batches`` calls after an install are skipped (and spend
        the cooldown), so ``cooldown_batches=1`` skips exactly one
        decision.  ``obs`` is an optional telemetry batch
        (repro/obs/timeline.py); the boundary install becomes a fenced
        phase in the trace."""
        if self._cooldown > 0:
            self._cooldown -= 1
            return state, None
        if not self.should_repartition():
            return state, None
        new_parts = self.propose()
        if np.array_equal(new_parts.boundaries, self.parts.boundaries):
            self._reset_window()
            return state, None
        from repro.obs.timeline import obs_phase

        with obs_phase(obs, "repartition/install") as _ph:
            new_state, n_inval, sh_before, sh_after = install_boundaries(
                state, meta, self.parts, new_parts
            )
            # a boundary install bumps versions for every moved node, which
            # already fences off the leaf-direct route table's stale entries
            # (correctness); retraining here restores the *performance* of
            # the fast path under the new ownership without a separate
            # controller (DESIGN.md §13)
            from repro.core import route_table as _route_table

            if _route_table.route_table_active(new_state):
                new_state = _route_table.train_route_table(new_state, meta)
            if _ph is not None and hasattr(_ph, "fence"):
                _ph.fence(new_state.boundaries)
        report = RepartitionReport(
            old_boundaries=self.parts.boundaries.copy(),
            new_boundaries=new_parts.boundaries.copy(),
            loads=self._loads.copy(),
            drops=self._drops,
            imbalance=self.imbalance,
            fraction_keyspace_moved=self.parts.assignment_diff(new_parts),
            nodes_invalidated=n_inval,
            shared_nodes_before=sh_before,
            shared_nodes_after=sh_after,
        )
        self.reports.append(report)
        self.parts = new_parts
        self._reset_window()
        self._cooldown = self.cfg.cooldown_batches
        return new_state, report

    def _reset_window(self) -> None:
        self._loads = np.zeros_like(self._loads)
        self._drops = 0
        self._ops = 0
