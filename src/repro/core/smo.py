"""On-mesh structural-modification engine (Plane B): device-side leaf
splits between batches, without rebuilding the pool.

``core/write.py`` sheds an insert whose leaf would overflow
(``STATUS_SPLIT``): an SPMD batch cannot take the paper's per-node latches,
so it refuses the structural change.  Until this module existed every shed
lane drained through :func:`repro.core.write.drain_splits`, which replays
on the host tree and rebuilds the *entire* blocked pool — restarting all
caches and versions cold.  That inverts the paper's economy (§6 falls back
to the normal path only for the SMO itself, not the whole index); FlexKV
and Outback both make the same point — keep structural maintenance next to
the data and ship only tiny fixed-size messages.

:func:`make_dex_smo` builds the collective SMO round that does exactly
that.  Per round:

  1. shed ``(key, value)`` lanes are routed to the memory column owning
     their level-M subtree (24B messages — the "tiny fixed-size" write of
     the disaggregated protocol) and all-gathered across the route axes so
     every pool replica applies the identical round;
  2. the owner walks its local block to each target leaf, groups lanes by
     leaf, resolves duplicate writers by global batch priority and turns
     already-present keys into value updates;
  3. each target leaf goes through the ``leaf_split`` Pallas kernel
     (kernels/leaf_split.py, oracle ``leaf_split_ref``): pending inserts
     are rank-merged and a leaf whose merged count exceeds FANOUT is cut
     into two half-full rows.  The sibling slot comes from the subtree's
     free-list headroom (``DexState.n_alloc`` watermark, capacity reserved
     at build time — core/pool.py), the leaf-successor table is re-linked
     so scans keep walking leaves in key order, and the separator is
     rank-merged into the parent row (reusing the ``leaf_write`` kernel
     with children as the value plane);
  4. full parents are split by a dense in-block pass (one split per parent
     per sweep, recursing toward the subtree root across sweeps/rounds).

Coherence reuses the write path's machinery: only the split leaf, its new
sibling and the touched ancestors get ``DexState.versions`` bumps, so
unrelated cached rows on every chip stay warm — versus the drain path's
global cold restart.  The fallback ladder is now graded the way the paper's
is: leaf split (device-side) -> subtree-block overflow / top-tree growth
(``drain_splits`` host rebuild, counted in ``STAT_DRAINS``) — and the host
replay remains the validation oracle.

Drivers: :func:`run_smo` iterates rounds until the pending set stops
shrinking; :func:`settle_splits` adds the host-mirror replay and the
``drain_splits`` fallback for whatever a bounded number of rounds could not
place (exhausted free-lists, subtree-root splits).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import routing
from repro.core.dex import (
    N_STATS,
    STAT_SMO_SPLITS,
    DexMeshConfig,
    DexState,
)
from repro.core.nodes import FANOUT, KEY_MAX, NULL
from repro.core.pool import (
    PoolMeta,
    SepPlanes,
    blocks,
    compress_rows,
    join_words,
    keys_i64,
    node_rows,
    pool_specs,
    set_node_rows,
    split_words,
    top_walk,
)
from repro.core.write import (
    STATUS_MISS,
    STATUS_OK,
    STATUS_SPLIT,
    _seg_positions,
    drain_splits,
)
from repro.kernels.leaf_split import leaf_split
from repro.kernels.leaf_write import leaf_write
from repro.kernels.ref import leaf_split_ref, leaf_write_ref


def _dense_parents(pool_children: jax.Array) -> jax.Array:
    """Per-node parent local id (-1 for roots/leaves' absent parents),
    derived from the children arrays of one pool shard [S, C, F]."""
    s, c, f = pool_children.shape
    node = jnp.broadcast_to(
        jnp.arange(c, dtype=jnp.int32)[None, :, None], (s, c, f)
    )
    row = jnp.broadcast_to(jnp.arange(s)[:, None, None], (s, c, f))
    valid = (pool_children != NULL) & (pool_children >= 0) & (
        pool_children < c
    )
    ch = jnp.where(valid, pool_children, c)  # OOB -> dropped
    return (
        jnp.full((s, c), -1, jnp.int32).at[row, ch].set(node, mode="drop")
    )


def make_dex_smo(
    meta: PoolMeta,
    cfg: DexMeshConfig,
    mesh,
    *,
    use_kernel: bool = True,
    interpret: "bool | None" = None,
):
    """Build one collective SMO round:
    ``(state, keys, values) -> (state, status)``.

    ``keys``/``values`` are [B] globally sharded over all mesh axes —
    normally the lanes a ``make_dex_insert`` batch returned with
    ``STATUS_SPLIT`` (``KEY_MAX`` lanes are inactive no-ops).  Each live
    lane comes back ``STATUS_OK`` (applied: split executed device-side, or
    the leaf meanwhile had room and the insert merged in place, or the key
    already existed and its value was updated) or ``STATUS_SPLIT`` (still
    pending: staging overflow, a full parent that split this round, or an
    exhausted subtree — retry with another round / fall back to
    ``drain_splits``).  Wrap with ``jax.jit``; drive with :func:`run_smo`
    or :func:`settle_splits`.
    """
    levels = meta.levels_in_subtree
    cap_nodes = meta.subtree_cap
    SW = FANOUT  # staged inserts per leaf per round

    def local_fn(pool, occupancy, n_alloc, versions, succ, stats,
                 keys, values):
        b = keys.shape[0]
        s_per = meta.n_subtrees_padded // cfg.n_memory
        s_local = occupancy.shape[0]
        n_nodes_total = versions.shape[-1]
        vers = versions[0]
        succ_t = succ[0]

        # --- 1. route to the owning memory column, replicate the round ----
        dev = routing.device_linear_index(cfg, mesh)
        prio = dev.astype(jnp.int64) * b + jnp.arange(b, dtype=jnp.int64)
        live0 = keys != KEY_MAX
        st0 = top_walk(pool, meta, keys)
        owner = jnp.where(live0, st0 // s_per, cfg.n_memory)
        payload = jnp.stack([keys, values, prio], axis=-1)      # [B, 3]
        # bucket capacity = the full per-device batch: an SMO round is rare
        # (between batches) and must never load-shed its own repair work
        buf, lane, dropped = routing.pack_by_dest(
            payload, owner.astype(jnp.int32), cfg.n_memory, b
        )
        req = routing.a2a(buf, cfg.memory_axis)                 # [n_mem, b, 3]
        req_all = routing.gather_route(req, cfg)                # [R, n_mem, b, 3]
        flat = req_all.reshape(-1, 3)
        k = flat[:, 0]
        v = flat[:, 1]
        pr = flat[:, 2]
        n = k.shape[0]
        live = k != KEY_MAX

        # --- 2. walk the local block to the leaf, recording the path ------
        stg = jnp.where(live, top_walk(pool, meta, k), 0)       # global id
        st = (stg % s_per).astype(jnp.int32)                    # shard row
        plocals = [jnp.zeros((n,), jnp.int32)]
        local = jnp.zeros((n,), jnp.int32)
        for _ in range(levels - 1):
            rows = node_rows(pool.pool_keys, st, local)
            slot = jnp.maximum(
                jnp.sum(rows <= k[:, None], axis=-1) - 1, 0
            ).astype(jnp.int32)
            local = pool.pool_children[st, local * FANOUT + slot]
            plocals.append(local)
        leaf_lo = plocals[-1]
        gid_leaf = meta.node_gid(stg, leaf_lo)

        # --- 3. conflict resolution + existing keys become value updates --
        row_k0 = node_rows(pool.pool_keys, st, leaf_lo)
        eqk = row_k0 == k[:, None]
        exists = jnp.any(eqk, axis=-1) & live
        uslot = jnp.argmax(eqk, axis=-1).astype(jnp.int32)
        route_gid = jnp.where(live, gid_leaf, KEY_MAX)
        order = jnp.lexsort((pr, k, route_gid))
        g_s = route_gid[order]
        k_s = k[order]
        v_s = v[order]
        live_s = live[order]
        st_s = st[order]
        lo_s = leaf_lo[order]
        diff = (g_s[1:] != g_s[:-1]) | (k_s[1:] != k_s[:-1])
        new_run = jnp.concatenate([jnp.ones((1,), bool), diff])
        run_id = jnp.cumsum(new_run) - 1
        winner = jnp.concatenate([diff, jnp.ones((1,), bool)]) & live_s
        upd_w = winner & exists[order]
        ust = jnp.where(upd_w, st_s, s_local)                   # OOB drop
        new_pv = jax.tree.map(
            lambda w, v: w.at[ust, lo_s * FANOUT + uslot[order]].set(
                v, mode="drop"),
            pool.pool_values, split_words(v_s),
        )

        # --- 4. per-leaf staging of fresh inserts -------------------------
        new_seg = jnp.concatenate([jnp.ones((1,), bool), g_s[1:] != g_s[:-1]])
        seg_id = jnp.cumsum(new_seg) - 1
        ins_w = winner & ~exists[order]
        pos = _seg_positions(ins_w, new_seg)
        staged = ins_w & (pos < SW)
        ir = jnp.where(staged, seg_id, n)
        ic = jnp.where(staged, pos, SW)
        ins_key_st = (
            jnp.full((n, SW), KEY_MAX, jnp.int64)
            .at[ir, ic].set(k_s, mode="drop")
        )
        ins_val_st = (
            jnp.zeros((n, SW), jnp.int64).at[ir, ic].set(v_s, mode="drop")
        )
        n_staged = (
            jnp.zeros((n,), jnp.int32).at[seg_id].add(staged.astype(jnp.int32))
        )

        def seg_attr(x, fill=0):
            return (
                jnp.full((n,), fill, x.dtype)
                .at[seg_id].max(jnp.where(live_s, x, fill))
            )

        seg_st = seg_attr(st_s)
        seg_lo = seg_attr(lo_s)
        seg_stg = seg_attr(stg[order])
        par_lane = plocals[-2][order] if levels >= 2 else jnp.zeros(
            (n,), jnp.int32
        )
        seg_par = seg_attr(par_lane)
        seg_active = n_staged > 0
        occ_seg = occupancy[seg_st, seg_lo]
        m_seg = occ_seg + n_staged
        need_split = seg_active & (m_seg > FANOUT)
        merge_ok = seg_active & ~need_split

        # --- 5. split admission: parent room + free-list slack ------------
        if levels >= 2:
            cnt_par = (
                jnp.zeros((s_local, cap_nodes), jnp.int32)
                .at[seg_st, seg_par].add(need_split.astype(jnp.int32))
            )
            parent_room = (
                occupancy[seg_st, seg_par] + cnt_par[seg_st, seg_par]
            ) <= FANOUT
            allowed = need_split & parent_room
        else:
            # the leaf IS the subtree root: any split is subtree overflow
            parent_room = jnp.zeros((n,), bool)
            allowed = jnp.zeros((n,), bool)
        new_sub = jnp.concatenate(
            [jnp.ones((1,), bool), seg_st[1:] != seg_st[:-1]]
        )
        rank_sub = _seg_positions(allowed, new_sub)
        sib_lo = (n_alloc[seg_st] + rank_sub).astype(jnp.int32)
        can_split = allowed & (sib_lo < cap_nodes)
        apply_seg = merge_ok | can_split
        alloc_st = jnp.where(can_split, seg_st, s_local)
        new_alloc = n_alloc.at[alloc_st].add(1, mode="drop")

        # --- 6. leaf merge / split (Pallas kernel or oracle) --------------
        rows_k = node_rows(pool.pool_keys, seg_st, seg_lo)
        rows_v = node_rows(new_pv, seg_st, seg_lo)
        splitter = leaf_split if use_kernel else leaf_split_ref
        skw = {"interpret": interpret} if use_kernel else {}
        lk, lv, rk, rv, occ_l, occ_r, sep, _did = splitter(
            rows_k, rows_v, ins_key_st, ins_val_st, **skw
        )
        w_st = jnp.where(apply_seg, seg_st, s_local)
        out_pk = set_node_rows(pool.pool_keys, w_st, seg_lo, lk)
        out_pv = set_node_rows(new_pv, w_st, seg_lo, lv)
        out_occ = occupancy.at[w_st, seg_lo].set(occ_l, mode="drop")
        r_st = jnp.where(can_split, seg_st, s_local)
        out_pk = set_node_rows(out_pk, r_st, sib_lo, rk)
        out_pv = set_node_rows(out_pv, r_st, sib_lo, rv)
        out_occ = out_occ.at[r_st, sib_lo].set(occ_r, mode="drop")
        out_pc = pool.pool_children

        # successor chain: leaf -> sibling -> old successor
        gid_seg = meta.node_gid(seg_stg, seg_lo)
        gid_sib = meta.node_gid(seg_stg, sib_lo)
        old_nxt = succ_t[jnp.where(can_split, gid_seg, 0)]
        sidx_sib = jnp.where(can_split, gid_sib, n_nodes_total)
        sidx_leaf = jnp.where(can_split, gid_seg, n_nodes_total)
        succ_new = (
            succ_t.at[sidx_sib].set(old_nxt, mode="drop")
            .at[sidx_leaf].set(gid_sib, mode="drop")
        )

        # version bumps: updated leaves, applied leaves, siblings, parents
        def bump(varr, gids, mask):
            safe = jnp.where(mask, gids, n_nodes_total)
            return varr.at[safe].max(varr[jnp.where(mask, gids, 0)] + 1,
                                     mode="drop")

        # map the winner flag back to lane order for the lane-indexed gids
        upd_l = jnp.zeros((n,), bool).at[order].set(upd_w)
        vers2 = bump(vers, gid_leaf, upd_l)
        vers2 = bump(vers2, gid_seg, apply_seg)
        vers2 = bump(vers2, gid_sib, can_split)
        gid_par = meta.node_gid(seg_stg, seg_par)
        vers2 = bump(vers2, gid_par, can_split)

        # --- 7. merge separators into parent rows -------------------------
        n_leaf_splits = jnp.sum(can_split).astype(jnp.int64)
        if levels >= 2:
            pg_route = jnp.where(can_split, gid_par, KEY_MAX)
            order2 = jnp.lexsort((sep, pg_route))
            pg2 = pg_route[order2]
            sep2 = sep[order2]
            sib2 = sib_lo[order2].astype(jnp.int64)
            act2 = can_split[order2]
            new_seg2 = jnp.concatenate(
                [jnp.ones((1,), bool), pg2[1:] != pg2[:-1]]
            )
            seg2_id = jnp.cumsum(new_seg2) - 1
            pos2 = _seg_positions(act2, new_seg2)
            ir2 = jnp.where(act2, seg2_id, n)
            ic2 = jnp.where(act2, pos2, SW)
            ins_k2 = (
                jnp.full((n, SW), KEY_MAX, jnp.int64)
                .at[ir2, ic2].set(sep2, mode="drop")
            )
            ins_v2 = (
                jnp.zeros((n, SW), jnp.int64)
                .at[ir2, ic2].set(sib2, mode="drop")
            )

            def seg2_attr(x, fill=0):
                return (
                    jnp.full((n,), fill, x.dtype)
                    .at[seg2_id].max(jnp.where(act2, x, fill))
                )

            seg2_st = seg2_attr(seg_st[order2])
            seg2_lo = seg2_attr(seg_par[order2])
            seg2_active = (
                jnp.zeros((n,), bool).at[seg2_id].max(act2)
            )
            rows_pk = node_rows(out_pk, seg2_st, seg2_lo)
            rows_pc = node_rows(out_pc, seg2_st, seg2_lo).astype(jnp.int64)
            writer = leaf_write if use_kernel else leaf_write_ref
            wkw = {"interpret": interpret} if use_kernel else {}
            no_us = jnp.full((n, SW), -1, jnp.int32)
            no_uv = jnp.zeros((n, SW), jnp.int64)
            nk2, nc2, nocc2 = writer(
                rows_pk, rows_pc, no_us, no_uv, ins_k2, ins_v2, **wkw
            )
            w2 = jnp.where(seg2_active, seg2_st, s_local)
            out_pk = set_node_rows(out_pk, w2, seg2_lo, nk2)
            out_pc = set_node_rows(out_pc, w2, seg2_lo, nc2)
            out_occ = out_occ.at[w2, seg2_lo].set(nocc2, mode="drop")

        # --- 8. dense inner pass: split full parents toward the root ------
        n_inner_splits = jnp.int64(0)
        if levels >= 3:
            # whole-block passes work on the [S, C, F] node view, the keys
            # as int64 (the pass compares and moves them over whole blocks
            # anyway) and the values as words (it only clears sibling rows)
            out_pk = blocks(join_words(out_pk))
            out_pc, out_pv = blocks(out_pc), jax.tree.map(blocks, out_pv)
            flagged0 = need_split & ~parent_room & (m_seg > 0)
            f_st = jnp.where(flagged0, seg_st, s_local)
            flag = (
                jnp.zeros((s_local, cap_nodes), bool)
                .at[f_st, seg_par].set(True, mode="drop")
            )
            col_ix = jax.lax.axis_index(cfg.memory_axis).astype(jnp.int64)
            row_ix = jnp.broadcast_to(
                jnp.arange(s_local)[:, None], (s_local, cap_nodes)
            )
            lo_ix = jnp.broadcast_to(
                jnp.arange(cap_nodes, dtype=jnp.int32)[None, :],
                (s_local, cap_nodes),
            )
            gid_grid = (
                (col_ix * s_per + row_ix.astype(jnp.int64)) * cap_nodes
                + lo_ix.astype(jnp.int64)
            )
            colF = jnp.arange(FANOUT, dtype=jnp.int32)[None, None, :]
            alloc_g = new_alloc
            for _sweep in range(levels - 2):
                par = _dense_parents(out_pc)                # [S, C]
                par_safe = jnp.where(par >= 0, par, 0)
                par_occ = out_occ[row_ix, par_safe]
                can = flag & (lo_ix != 0) & (par >= 0)
                room = can & (par_occ < FANOUT)
                # one split per parent per sweep: lowest flagged child wins
                min_lo = (
                    jnp.full((s_local, cap_nodes), cap_nodes, jnp.int32)
                    .at[row_ix, jnp.where(room, par_safe, cap_nodes)]
                    .min(lo_ix, mode="drop")
                )
                m_g = out_occ
                win = room & (min_lo[row_ix, par_safe] == lo_ix) & (m_g >= 2)
                rank = jnp.cumsum(win.astype(jnp.int32), axis=1) - win
                sib_g = alloc_g[:, None] + rank
                ok = win & (sib_g < cap_nodes)
                left_n = m_g // 2
                idx = jnp.clip(colF + left_n[:, :, None], 0, FANOUT - 1)
                right_k = jnp.take_along_axis(out_pk, idx, axis=2)
                right_c = jnp.take_along_axis(out_pc, idx, axis=2)
                mask_r = colF < (m_g - left_n)[:, :, None]
                right_k = jnp.where(mask_r, right_k, KEY_MAX)
                right_c = jnp.where(mask_r, right_c, NULL)
                sep_g = jnp.take_along_axis(
                    out_pk, left_n[:, :, None], axis=2
                )[..., 0]
                left_mask = colF < left_n[:, :, None]
                okk = ok[:, :, None]
                out_pk = jnp.where(
                    okk, jnp.where(left_mask, out_pk, KEY_MAX), out_pk
                )
                out_pc = jnp.where(
                    okk, jnp.where(left_mask, out_pc, NULL), out_pc
                )
                out_occ = jnp.where(ok, left_n, out_occ)
                sib_safe = jnp.where(ok, sib_g, cap_nodes)
                out_pk = out_pk.at[row_ix, sib_safe].set(right_k, mode="drop")
                out_pc = out_pc.at[row_ix, sib_safe].set(right_c, mode="drop")
                out_occ = out_occ.at[row_ix, sib_safe].set(
                    m_g - left_n, mode="drop"
                )
                out_pv = jax.tree.map(
                    lambda w: w.at[row_ix, sib_safe].set(0, mode="drop"),
                    out_pv,
                )
                alloc_g = alloc_g + jnp.sum(ok.astype(jnp.int32), axis=1)
                # single separator insert into each winner's parent row
                psep = (
                    jnp.full((s_local, cap_nodes), KEY_MAX, jnp.int64)
                    .at[row_ix, jnp.where(ok, par_safe, cap_nodes)]
                    .set(sep_g, mode="drop")
                )
                pchild = (
                    jnp.full((s_local, cap_nodes), NULL, jnp.int32)
                    .at[row_ix, jnp.where(ok, par_safe, cap_nodes)]
                    .set(sib_g.astype(jnp.int32), mode="drop")
                )
                has = psep != KEY_MAX
                ppos = jnp.sum(
                    (out_pk < psep[:, :, None]).astype(jnp.int32), axis=2
                )
                shift = jnp.clip(
                    colF - (colF > ppos[:, :, None]).astype(jnp.int32),
                    0, FANOUT - 1,
                )
                base_k = jnp.take_along_axis(out_pk, shift, axis=2)
                base_c = jnp.take_along_axis(out_pc, shift, axis=2)
                ins_here = colF == ppos[:, :, None]
                new_k = jnp.where(ins_here, psep[:, :, None], base_k)
                new_c = jnp.where(ins_here, pchild[:, :, None], base_c)
                hask = has[:, :, None]
                out_pk = jnp.where(hask, new_k, out_pk)
                out_pc = jnp.where(hask, new_c, out_pc)
                out_occ = out_occ + has.astype(jnp.int32)
                # version bumps: split node, sibling, parent
                bump_grid = ok | has
                bump_grid = (
                    bump_grid.at[row_ix, sib_safe].set(True, mode="drop")
                )
                gflat = gid_grid.reshape(-1)
                bflat = bump_grid.reshape(-1)
                safe = jnp.where(bflat, gflat, n_nodes_total)
                vers2 = vers2.at[safe].max(
                    vers2[jnp.where(bflat, gflat, 0)] + 1, mode="drop"
                )
                n_inner_splits = n_inner_splits + jnp.sum(ok).astype(
                    jnp.int64
                )
                # parents that were full re-flag for the next sweep; losers
                # (multiple flagged children of one parent) retry next round
                nf_par = jnp.where(
                    can & (par_occ >= FANOUT), par_safe, cap_nodes
                )
                flag = (
                    jnp.zeros((s_local, cap_nodes), bool)
                    .at[row_ix, nf_par].set(True, mode="drop")
                )
            new_alloc = alloc_g
            out_pk = split_words(out_pk.reshape(s_local, -1))
            out_pc = out_pc.reshape(s_local, -1)
            out_pv = jax.tree.map(lambda w: w.reshape(s_local, -1), out_pv)

        # --- 9. statuses back to the requesting lanes ---------------------
        outcome_w = jnp.where(
            upd_w | (staged & apply_seg[seg_id]),
            STATUS_OK, STATUS_SPLIT,
        ).astype(jnp.int32)
        run_out = (
            jnp.zeros((n,), jnp.int32)
            .at[run_id].max(jnp.where(winner, outcome_w, 0))
        )
        status_s = jnp.where(live_s, run_out[run_id], STATUS_MISS)
        status = jnp.zeros((n,), jnp.int32).at[order].set(status_s)
        r_lin = routing.route_linear_index(cfg, mesh)
        status_own = jnp.take(
            status.reshape(cfg.n_route, cfg.n_memory, b), r_lin, axis=0
        )
        resp = routing.a2a(
            status_own[..., None].astype(jnp.int64), cfg.memory_axis
        )
        back = routing.unpack_to_lanes(resp, lane, b, 0)
        out_status = back[..., 0].astype(jnp.int32)
        out_status = jnp.where(
            dropped & live0, STATUS_SPLIT, out_status
        )
        out_status = jnp.where(live0, out_status, STATUS_MISS)

        # --- 10. sync replicated tables + stats ---------------------------
        new_versions = jax.lax.pmax(vers2[None, :], cfg.all_axes)
        succ_all = jax.lax.all_gather(succ_new, cfg.memory_axis, axis=0)
        owner_col = (
            jnp.arange(n_nodes_total) // meta.subtree_cap
        ) // s_per
        new_succ = jnp.take_along_axis(
            succ_all, owner_col[None, :], axis=0
        )
        # count splits once per memory column (route rows are replicas)
        n_splits = jnp.where(
            r_lin == 0, n_leaf_splits + n_inner_splits, 0
        )
        upd = jnp.zeros((1, N_STATS), jnp.int64)
        upd = upd.at[0, STAT_SMO_SPLITS].set(n_splits)
        new_stats = stats + upd

        return (out_pk, out_pc, out_pv, out_occ, new_alloc, new_versions,
                new_succ, new_stats, out_status)

    dev = P(cfg.all_axes)
    pool_in = pool_specs(cfg.memory_axis)
    mem = P(cfg.memory_axis)
    words = pool_in.pool_keys

    sharded = routing.shard_map_compat(
        local_fn,
        mesh=mesh,
        in_specs=(pool_in, mem, mem, dev, dev, dev,
                  P(cfg.all_axes), P(cfg.all_axes)),
        out_specs=(words, mem, words, mem, mem, dev, dev, dev,
                   P(cfg.all_axes)),
    )

    def smo(state: DexState, keys: jax.Array, values: jax.Array):
        (new_pk, new_pc, new_pv, new_occ, new_alloc, new_versions, new_succ,
         new_stats, status) = sharded(
            state.pool, state.occupancy, state.n_alloc, state.versions,
            state.succ, state.stats,
            keys.astype(jnp.int64), values.astype(jnp.int64),
        )
        new_pool = state.pool._replace(
            pool_keys=new_pk, pool_children=new_pc, pool_values=new_pv
        )
        new_state = state._replace(
            pool=new_pool,
            occupancy=new_occ,
            n_alloc=new_alloc,
            versions=new_versions,
            succ=new_succ,
            stats=new_stats,
        )
        return new_state, status

    return smo


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


def run_smo(
    smo,
    state: DexState,
    keys: np.ndarray,
    values: np.ndarray,
    *,
    max_rounds: "int | None" = None,
    levels: int = 2,
    obs=None,
):
    """Drive bounded SMO rounds until every live lane settles or the pending
    set stops shrinking (exhausted free-list / subtree-root split).

    ``keys``/``values`` keep the originating batch's lane layout with
    non-pending lanes set to ``KEY_MAX`` (exactly how ``make_dex_insert``
    hands back ``STATUS_SPLIT`` lanes) — on a multi-device mesh the width
    must stay divisible by the device count, and reusing the batch width
    avoids a fresh compile per distinct shed count.  Returns ``(state,
    status [B] int32, rounds_run)`` — lanes still ``STATUS_SPLIT`` need the
    host fallback (:func:`settle_splits` wires that up)."""
    keys = np.asarray(keys, np.int64)
    values = np.asarray(values, np.int64)
    if max_rounds is None:
        # a worst-case chain defers one level per round (the leaf waits for
        # its full parent's split, the parent for the grandparent's, ...)
        # and a leaf with > FANOUT pending keys re-splits once per round;
        # scale with both, bounded so a stuck batch still exits promptly
        max_rounds = 2 * levels + 6
    pending = keys != KEY_MAX
    status = np.full(keys.shape, STATUS_MISS, np.int32)
    rounds = 0

    def splits_done(st):
        return int(np.asarray(st.stats)[:, STAT_SMO_SPLITS].sum())

    from repro.obs.timeline import obs_phase

    while pending.any() and rounds < max_rounds:
        before = splits_done(state)
        # obs is an optional telemetry batch (repro/obs/timeline.py); each
        # SMO round is a separate fenced host phase in the timeline, and a
        # ``dex/smo/round`` span in a profiler trace
        with obs_phase(obs, f"smo/round{rounds}", span="dex/smo/round",
                       round=rounds):
            state, st_r = smo(
                state,
                jnp.asarray(np.where(pending, keys, KEY_MAX)),
                jnp.asarray(np.where(pending, values, 0)),
            )
            st_np = np.asarray(st_r)
        rounds += 1
        settled = pending & (st_np != STATUS_SPLIT)
        status[settled] = st_np[settled]
        still = pending & (st_np == STATUS_SPLIT)
        # progress = lanes settled OR structural splits executed (a round
        # that only split a full parent settles nothing but unblocks the
        # deferred leaves for the next round); neither -> host fallback
        if still.sum() >= pending.sum() and splits_done(state) <= before:
            pending = still
            break
        pending = still
    status[pending] = STATUS_SPLIT
    return state, status, rounds


def settle_splits(
    state: DexState,
    meta: PoolMeta,
    cfg: DexMeshConfig,
    smo,
    host,
    shed_keys: np.ndarray,
    shed_values: np.ndarray,
    boundaries: np.ndarray,
    *,
    max_rounds: "int | None" = None,
    obs=None,
):
    """Resolve one batch of ``STATUS_SPLIT`` lanes: bounded on-mesh SMO
    rounds first, host ``drain_splits`` rebuild only for the residue.

    ``host`` is the caller's :class:`HostBTree` mirror; lanes the SMO engine
    applies are replayed into it here (keeping the mirror the validation
    oracle), and the residue goes through the host's true eager-split path.
    Returns ``(state, meta, info)`` — ``meta`` changes only when the drain
    fallback rebuilt the pool (rebuild ops against it then), and ``info``
    reports ``{"onmesh": lanes applied device-side, "residual": lanes
    drained, "rounds": smo rounds run, "drained": bool}``."""
    shed_keys = np.asarray(shed_keys, np.int64)
    shed_values = np.asarray(shed_values, np.int64)
    if shed_keys.size == 0:
        return state, meta, {
            "onmesh": 0, "residual": 0, "rounds": 0, "drained": False,
        }
    from repro.obs.timeline import obs_phase

    state, status, rounds = run_smo(
        smo, state, shed_keys, shed_values,
        max_rounds=max_rounds, levels=meta.levels_in_subtree, obs=obs,
    )
    ok = status == STATUS_OK
    for kk, vv in zip(shed_keys[ok], shed_values[ok]):
        host.insert(int(kk), int(vv))
    residual = status == STATUS_SPLIT
    drained = bool(residual.any())
    if drained:
        with obs_phase(obs, "smo/drain"):
            state, meta = drain_splits(
                state, meta, cfg, host,
                shed_keys[residual], shed_values[residual], boundaries,
            )
    return state, meta, {
        "onmesh": int(ok.sum()),
        "residual": int(residual.sum()),
        "rounds": rounds,
        "drained": drained,
    }


# ---------------------------------------------------------------------------
# compressed-separator maintenance (core/pool.py SepPlanes)
# ---------------------------------------------------------------------------


def refresh_sep_planes(
    sep: SepPlanes,
    state: DexState,
    meta: PoolMeta,
    old_versions,
) -> SepPlanes:
    """Incrementally re-compress the separator planes after on-mesh SMO
    rounds: every row a split touched (the split node, its new sibling, the
    ancestors the separator merged into) got a ``DexState.versions`` bump,
    so the version delta against ``old_versions`` names exactly the rows to
    recompute from the canonical key plane — no full rebuild.  Rows the
    rounds never touched come back bit-identical.  After a
    ``drain_splits`` host rebuild the pool geometry itself changes; rebuild
    from scratch with :func:`repro.core.pool.compress_separators` instead.
    """
    vers = np.asarray(state.versions)
    old = np.asarray(old_versions)
    if vers.ndim == 2:
        vers = vers[0]
    if old.ndim == 2:
        old = old[0]
    changed = np.nonzero(vers != old)[0]
    if changed.size == 0:
        return sep
    cap = meta.subtree_cap
    s_idx = changed // cap
    l_idx = changed % cap
    pk = blocks(keys_i64(state.pool))
    prefix = np.asarray(sep.prefix).copy()
    nbits = np.asarray(sep.nbits).copy()
    suffix = np.asarray(sep.suffix).copy()
    p, nb, sf = compress_rows(pk[s_idx, l_idx])
    prefix[s_idx, l_idx] = p
    nbits[s_idx, l_idx] = nb
    suffix[s_idx, l_idx] = sf
    return SepPlanes(
        prefix=jnp.asarray(prefix),
        nbits=jnp.asarray(nbits),
        suffix=jnp.asarray(suffix),
    )
