"""Batched writes on the TPU mesh (Plane B): the paper's update/insert
protocols (§7) as SPMD collectives.

Two operations share the unified mixed-op engine's dataflow
(:mod:`repro.core.engine`: one route round, one version-checked cached
descent, one fused tagged ``all_to_all`` round); this module holds the thin
single-opcode builders, the owner-side apply (``_apply_leaf_writes``,
called from the engine's fused round) and the host-side SMO drain:

* ``make_dex_update`` — in-place value overwrite.  The engine routes each
  ``(key, value)`` to the partition owning the key, descends through the
  per-chip cache to the target leaf, and ships a tagged ``(leaf_gid, key,
  value, prio)`` record in the fused round.  The owning memory column
  applies it CAS-style — the authoritative leaf row is re-searched at
  apply time and the write lands at the key's current slot — conflicting
  writers of one key are resolved by batch priority (updates replay before
  inserts, last-in-batch wins within a phase, matching sequential replay),
  and the response carries the leaf's merged post-batch value row.
* ``make_dex_insert`` — append into leaf slack slots.  Same engine descent
  (inner levels only); the owning memory column groups incoming keys by
  target leaf, converts duplicates of existing keys into value updates, and
  merges fresh keys into the leaf's slack via the ``leaf_write`` Pallas
  kernel, bumping the per-leaf occupancy array.  **Leaves that would
  overflow are shed**: none of their staged inserts apply, the lanes come
  back with status ``STATUS_SPLIT`` and are counted in ``STAT_SPLITS`` —
  mirroring the scan subsystem's load-shed discipline — and the caller
  replays them through the on-mesh SMO engine or the host tree's true
  structural-modification path between batches (:func:`drain_splits`).
  This replaces the paper's latch-based SMOs: an SPMD batch cannot take
  per-node latches, but it can refuse the structural change and let the
  SMO ladder replay it.

When a key's destination column's cost group picks the two-sided path
(core/engine.py §6.1 refinement), the same records travel as *offloaded*
tags: the owner walks its own block to the leaf first, then applies the
identical CAS/merge — and an offloaded insert that would split sheds
``STATUS_SPLIT`` exactly like a fetched-path one (the paper's rule that
offloaded writes fall back to the normal path for SMOs).

Cache coherence is **write-through-and-invalidate** with per-leaf versions:
the writing chip refreshes (update) or drops (insert) its *own* cached row
and bumps the leaf's entry in the replicated per-node version table
(``DexState.versions``, pmax-synchronized across the mesh each batch), so
*other* chips' stale rows fail the version check inside ``_cache_probe`` on
their next hit and are re-fetched.

Replica consistency: the pool shards only over the memory axis, so devices
along the route axes hold replicas of each memory column.  The write round
all-gathers the request buffers across the route axes
(:func:`repro.core.routing.gather_route`) so every replica applies the
identical batch.

Result status codes (per lane): ``STATUS_OK`` applied; ``STATUS_MISS``
no-op (update of an absent key / inactive lane); ``STATUS_SHED`` load-shed
by a routing bucket (retryable, counted in ``STAT_DROPS``);
``STATUS_SPLIT`` insert shed to the host SMO path (feed to
:func:`drain_splits`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dex import (
    STAT_DRAINS,
    DexMeshConfig,
    DexState,
    init_state,
)
from repro.core.nodes import FANOUT, KEY_MAX
from repro.core.pool import PoolMeta, build_pool, node_rows, set_node_rows
from repro.kernels.leaf_write import leaf_write
from repro.kernels.ref import leaf_write_ref

STATUS_MISS = 0    # update of an absent key / inactive lane: no-op
STATUS_OK = 1      # write applied by the owning memory column
STATUS_SPLIT = 2   # insert shed to the host SMO path (drain_splits)
STATUS_SHED = -1   # routing-bucket load shed; retry (STAT_DROPS)


def _seg_positions(mask: jax.Array, new_seg: jax.Array) -> jax.Array:
    """Rank of each ``mask``-lane within its segment (segments are runs
    delimited by ``new_seg`` over a sorted lane order)."""
    inc = mask.astype(jnp.int32)
    c = jnp.cumsum(inc)
    base = jax.lax.cummax(jnp.where(new_seg, c - inc, 0), axis=0)
    return c - inc - base


def _apply_leaf_writes(
    pool_keys: jax.Array,    # [S_local, C * F] this memory column's shard
    pool_values: jax.Array,  # [S_local, C * F]
    occupancy: jax.Array,    # [S_local, C]
    meta: PoolMeta,
    cfg: DexMeshConfig,
    gid: jax.Array,          # [N] int64 leaf gids (KEY_MAX = inactive lane)
    key: jax.Array,          # [N] int64
    value: jax.Array,        # [N] int64
    prio: jax.Array,         # [N] int64 globally unique batch priority
    allow_insert: jax.Array,  # [N] bool: absent keys may claim a slack slot
    *,
    use_kernel: bool,
    interpret: "bool | None",
):
    """Apply one flat *mixed* batch of leaf-write requests to the local pool
    shard.  A lane whose key already sits in the leaf becomes an in-place
    value write (CAS-style: the authoritative row is re-searched at apply
    time); an absent key claims a slack slot when ``allow_insert`` (insert
    lanes — fetched-path and offloaded alike) and is a ``STATUS_MISS``
    no-op otherwise (update of an absent key).

    Every route-replica of this memory column calls this with identical
    inputs (see ``gather_route``), so the replicas stay consistent.  Returns
    ``(new_pool_keys, new_pool_values, new_occupancy, status [N] int32,
    rows_v_out [N, F] post-batch value rows, ins_in_leaf [N] bool)`` —
    ``ins_in_leaf`` marks lanes whose target leaf took at least one fresh
    insert this batch (its key set shifted, so an updater's cached copy
    must NOT be version-refreshed in place: the keys plane it holds is
    stale even though the response's value row is authoritative).
    """
    n = gid.shape[0]
    s_per = meta.n_subtrees_padded // cfg.n_memory
    valid = gid != KEY_MAX
    st = jnp.where(valid, (gid // meta.subtree_cap) % s_per, 0).astype(jnp.int32)
    lo = jnp.where(valid, gid % meta.subtree_cap, 0).astype(jnp.int32)
    row_k0 = node_rows(pool_keys, st, lo)                   # [N, F] pre-batch

    eqk = row_k0 == key[:, None]
    exists = jnp.any(eqk, axis=-1) & valid
    slot32 = jnp.argmax(eqk, axis=-1).astype(jnp.int32)
    live = valid & (exists | allow_insert)
    is_upd = exists  # staged as in-place value write (vs slack-slot insert)

    # ---- conflict resolution: sort by (gid, key, prio); the last writer of
    # each (gid, key) run wins, everything else is superseded (still counts
    # as applied — sequential replay would have applied then overwritten it)
    route_gid = jnp.where(live, gid, KEY_MAX)
    order = jnp.lexsort((prio, key, route_gid))
    g_s = route_gid[order]
    k_s = key[order]
    live_s = live[order]
    diff = (g_s[1:] != g_s[:-1]) | (k_s[1:] != k_s[:-1])
    new_run = jnp.concatenate([jnp.ones((1,), bool), diff])
    run_id = jnp.cumsum(new_run) - 1
    winner = jnp.concatenate([diff, jnp.ones((1,), bool)]) & live_s

    # ---- segments: one per distinct target leaf ---------------------------
    new_seg = jnp.concatenate([jnp.ones((1,), bool), g_s[1:] != g_s[:-1]])
    seg_id = jnp.cumsum(new_seg) - 1
    st_s = st[order]
    lo_s = lo[order]
    seg_st = (
        jnp.zeros((n,), jnp.int32).at[seg_id].max(jnp.where(live_s, st_s, 0))
    )
    seg_lo = (
        jnp.zeros((n,), jnp.int32).at[seg_id].max(jnp.where(live_s, lo_s, 0))
    )

    upd_w = winner & is_upd[order]
    ins_w = winner & live_s & ~is_upd[order]                # insert mode only
    # ---- overflow check: leaves whose fresh keys exceed the slack are shed
    occ_lane = occupancy[st_s, lo_s]                        # [N]
    n_new_seg = (
        jnp.zeros((n,), jnp.int32).at[seg_id].add(ins_w.astype(jnp.int32))
    )
    over_lane = (occ_lane + n_new_seg[seg_id]) > FANOUT
    ins_apply = ins_w & ~over_lane
    upd_apply = upd_w  # in-place updates apply even when the leaf overflows

    # ---- staged write matrices, one row per segment -----------------------
    s_width = FANOUT
    pos_u = _seg_positions(upd_apply, new_seg)
    pos_i = _seg_positions(ins_apply, new_seg)
    v_s = value[order]
    slot_ss = slot32[order]
    ur = jnp.where(upd_apply, seg_id, n)
    uc = jnp.where(upd_apply, pos_u, s_width)
    upd_slot_st = (
        jnp.full((n, s_width), -1, jnp.int32)
        .at[ur, uc].set(slot_ss, mode="drop")
    )
    upd_val_st = (
        jnp.zeros((n, s_width), jnp.int64).at[ur, uc].set(v_s, mode="drop")
    )
    ir = jnp.where(ins_apply, seg_id, n)
    ic = jnp.where(ins_apply, pos_i, s_width)
    ins_key_st = (
        jnp.full((n, s_width), KEY_MAX, jnp.int64)
        .at[ir, ic].set(k_s, mode="drop")
    )
    ins_val_st = (
        jnp.zeros((n, s_width), jnp.int64).at[ir, ic].set(v_s, mode="drop")
    )

    # ---- the masked scatter + merge itself (Pallas kernel or oracle) ------
    rows_k = node_rows(pool_keys, seg_st, seg_lo)
    rows_v = node_rows(pool_values, seg_st, seg_lo)
    writer = leaf_write if use_kernel else leaf_write_ref
    kw = {"interpret": interpret} if use_kernel else {}
    new_k, new_v, new_occ = writer(
        rows_k, rows_v, upd_slot_st, upd_val_st, ins_key_st, ins_val_st, **kw
    )

    seg_active = (
        jnp.zeros((n,), bool).at[seg_id].max(upd_apply | ins_apply)
    )
    w_st = jnp.where(seg_active, seg_st, pool_keys.shape[0])  # OOB drop
    out_pk = set_node_rows(pool_keys, w_st, seg_lo, new_k)
    out_pv = set_node_rows(pool_values, w_st, seg_lo, new_v)
    out_occ = occupancy.at[w_st, seg_lo].set(new_occ, mode="drop")

    # ---- per-lane status: every lane inherits its (gid, key) winner's fate
    outcome_w = jnp.where(
        upd_apply | ins_apply,
        STATUS_OK,
        jnp.where(ins_w & over_lane, STATUS_SPLIT, STATUS_MISS),
    ).astype(jnp.int32)
    run_out = (
        jnp.zeros((n,), jnp.int32)
        .at[run_id].max(jnp.where(winner, outcome_w, 0))
    )
    status_s = jnp.where(live_s, run_out[run_id], STATUS_MISS)
    status = jnp.zeros((n,), jnp.int32).at[order].set(status_s)

    rows_v_out = node_rows(out_pv, st, lo)                  # post-batch rows
    # per-lane: did the lane's target leaf take any fresh insert this batch?
    seg_ins = jnp.zeros((n,), bool).at[seg_id].max(ins_apply)
    ins_lane_s = jnp.where(live_s, seg_ins[seg_id], False)
    ins_in_leaf = jnp.zeros((n,), bool).at[order].set(ins_lane_s)
    return out_pk, out_pv, out_occ, status, rows_v_out, ins_in_leaf


def make_dex_update(meta, cfg, mesh, *, use_kernel=True, interpret=None):
    """Build the sharded in-place update:
    ``(state, keys, values) -> (state, status)``.

    A thin single-opcode wrapper over the unified mixed-op engine
    (:func:`repro.core.engine.make_dex_engine`): route + cached descent are
    shared machinery, and the CAS-style write records travel as tagged
    messages in the engine's one fused request/response ``all_to_all``
    round (offloaded when the key's column's cost group picks the
    two-sided path).  ``keys``/``values`` are [B] globally sharded over all
    mesh axes; ``status`` comes back in the caller's lane order
    (``STATUS_OK`` / ``STATUS_MISS`` / ``STATUS_SHED``).  ``keys ==
    KEY_MAX`` lanes are inactive no-ops (useful for op-type-masked mixed
    batches).  Wrap with ``jax.jit``."""
    from repro.core import engine as engine_mod  # deferred: engine imports us

    eng = engine_mod.make_dex_engine(
        meta, cfg, mesh, ops=("update",),
        use_kernel=use_kernel, interpret=interpret,
    )

    def update(state, keys, values):
        keys = keys.astype(jnp.int64)
        opcodes = jnp.full(keys.shape, engine_mod.OP_UPDATE, jnp.int32)
        new_state, r = eng(state, opcodes, keys, values.astype(jnp.int64))
        return new_state, r.status

    return update


def make_dex_insert(meta, cfg, mesh, *, use_kernel=True, interpret=None):
    """Build the sharded insert: ``(state, keys, values) -> (state, status)``.

    A thin single-opcode wrapper over the unified mixed-op engine (see
    :func:`make_dex_update`).  Fresh keys append into their leaf's slack
    slots (occupancy-tracked); keys that already exist become value
    updates; leaves that would overflow shed their inserts with
    ``STATUS_SPLIT`` (counted in ``STAT_SPLITS``) — resolve them with
    :func:`repro.core.smo.settle_splits` (or :func:`drain_splits`) between
    batches; offloaded inserts that would split shed exactly the same way
    (the paper's SMO fallback rule).  ``keys == KEY_MAX`` lanes are
    inactive no-ops.  Wrap with ``jax.jit``."""
    from repro.core import engine as engine_mod  # deferred: engine imports us

    eng = engine_mod.make_dex_engine(
        meta, cfg, mesh, ops=("insert",),
        use_kernel=use_kernel, interpret=interpret,
    )

    def insert(state, keys, values):
        keys = keys.astype(jnp.int64)
        opcodes = jnp.full(keys.shape, engine_mod.OP_INSERT, jnp.int32)
        new_state, r = eng(state, opcodes, keys, values.astype(jnp.int64))
        return new_state, r.status

    return insert


# ---------------------------------------------------------------------------
# Host-side split replay (the SMO path)
# ---------------------------------------------------------------------------


def host_items(host) -> "tuple[np.ndarray, np.ndarray]":
    """All (key, value) pairs of a :class:`repro.core.sim.HostBTree` in
    sorted key order."""
    lv = np.asarray(host.LV)
    nk = np.asarray(host.NK)
    keys, vals = [], []
    for nid in np.where(lv == 0)[0]:
        m = int(nk[nid])
        keys.append(np.asarray(host.K[nid, :m]))
        vals.append(np.asarray(host.V[nid, :m]))
    k = np.concatenate(keys) if keys else np.zeros((0,), np.int64)
    v = np.concatenate(vals) if vals else np.zeros((0,), np.int64)
    order = np.argsort(k, kind="stable")
    return k[order], v[order]


def drain_splits(
    state: DexState,
    meta: PoolMeta,
    cfg: DexMeshConfig,
    host,
    shed_keys: np.ndarray,
    shed_values: np.ndarray,
    boundaries: np.ndarray,
):
    """Replay shed inserts through the host tree's true eager-split SMO path
    and rebuild the mesh state from the result — the *bottom rung* of the
    SMO fallback ladder (core/smo.py resolves plain leaf splits device-side;
    this path remains for subtree-block overflow, exhausted free-lists and
    top-tree growth, and stays the validation oracle).

    ``host`` is the :class:`repro.core.sim.HostBTree` mirror the caller
    keeps in sync (it must already contain every *applied* mesh write);
    ``shed_keys``/``shed_values`` are the lanes that came back with
    ``STATUS_SPLIT``, in original batch order.  Returns ``(new_state,
    new_meta)`` — a freshly blocked pool (splits change the leaf layout, so
    caches/versions restart cold; accumulated stats carry over, and the
    rebuild is counted in ``STAT_DRAINS`` so benchmarks can report fallback
    frequency).  Ops built by ``make_dex_*`` must be rebuilt against
    ``new_meta``.  With no shed lanes this is a **no-op**: the existing
    state is returned untouched — no rebuild, no cache/version cold
    restart, no drain counted.
    """
    shed_keys = np.asarray(shed_keys)
    shed_values = np.asarray(shed_values)
    if shed_keys.size == 0:
        return state, meta
    for k, v in zip(shed_keys, shed_values):
        host.insert(int(k), int(v))
    items_k, items_v = host_items(host)
    pool, new_meta = build_pool(
        items_k, items_v,
        level_m=meta.level_m,
        fill=meta.per_node / FANOUT,
        n_shards=cfg.n_memory,
        headroom=meta.headroom_frac,
        subtree_leaves=meta.leaves_per_subtree,
    )
    new_state = init_state(pool, new_meta, cfg, boundaries)
    # accumulated stats and the controller's demand counters carry over
    # (their shapes don't depend on the pool layout); the rebuild itself is
    # counted so callers can report how often the fallback fired
    stats = jnp.asarray(state.stats).at[0, STAT_DRAINS].add(1)
    return new_state._replace(
        stats=stats, route_demand=state.route_demand
    ), new_meta
