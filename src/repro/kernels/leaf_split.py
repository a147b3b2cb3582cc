"""Pallas kernel: leaf split + pending-insert merge for the on-mesh SMO
engine (core/smo.py).

Given one leaf row per lane plus the staged inserts that made it overflow,
the kernel rank-merges row keys and staged keys into one sorted sequence of
``m`` records and emits it as **two** rows:

  * ``m <= FANOUT``: everything lands in the *left* row (a plain merge, the
    same result as ``leaf_write`` with no updates staged) and the right row
    comes back empty — the caller applies the left row in place and no
    structural change happens;
  * ``m > FANOUT``: the sequence is cut at ``m // 2`` — the left row keeps
    the lower half (matching ``HostBTree._split_child``), the right row gets
    the upper half, and ``sep`` carries the right row's first key (the
    separator the parent absorbs).  ``did_split`` marks the lane.

The caller (core/smo.py) allocates the sibling slot from the subtree's
free-list headroom, writes the right row there, links the leaf-successor
table and merges ``(sep, sibling)`` into the parent node — the kernel is
purely the in-VMEM cut + merge.

Caller contract (mirroring kernels/leaf_write.py): active staged keys are
strictly ascending within a lane, distinct from each other and from the
row's keys; at most ``FANOUT`` staged keys per lane, so ``m <= 2 * FANOUT``
and one split always absorbs the whole batch.

int64 keys/values travel as (hi, lo) int32 planes (the TPU VPU has no
native 64-bit lanes).  The pure-jnp oracle is
``kernels/ref.py::leaf_split_ref``; ``interpret=None`` resolves to the
compiled kernel on a TPU and the Pallas interpreter elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.nodes import KEY_MAX
from repro.core.pool import join_words, split_words
from repro.kernels import use_interpret
from repro.kernels.leaf_write import (
    _KMAX_HI,
    _KMAX_LO,
    _ZERO,
    _any,
    _count,
    _exclusive_prefix_count,
    _live,
    _lt_planes,
    _row_block,
)

BLOCK_B = 8


def _make_kernel(fanout: int):
    def kernel(
        khi_ref, klo_ref, vhi_ref, vlo_ref,
        ikh_ref, ikl_ref, ivh_ref, ivl_ref,
        lkh_ref, lkl_ref, lvh_ref, lvl_ref,
        rkh_ref, rkl_ref, rvh_ref, rvl_ref,
        occl_ref, occr_ref, sep_hi_ref, sep_lo_ref, did_ref,
    ):
        khi = khi_ref[...]                     # [B, F] int32 planes
        klo = klo_ref[...]
        ikh = ikh_ref[...]                     # [B, S]
        ikl = ikl_ref[...]

        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, fanout), 2)
        # bool vectors cannot be reshaped on Mosaic: 3-D masks come from
        # int32 planes already broadcast to their position
        ikh3, ikl3 = ikh[:, :, None], ikl[:, :, None]             # [B, S, 1]
        khr, klr = khi[:, None, :], klo[:, None, :]               # [B, 1, F]

        # merged rank of every element (same branchless pairwise compares as
        # kernels/leaf_write.py: actives are distinct, KEY_MAX never counts)
        act = _live(ikh, ikl)                                     # [B, S]
        validr = _live(khi, klo)                                  # [B, F]
        validr3 = _live(khr, klr)                                 # [B, 1, F]
        act3 = _live(ikh[:, None, :], ikl[:, None, :])            # [B, 1, S]
        ins_below_row = _live(ikh3, ikl3) & _lt_planes(
            ikh3, ikl3, khr, klr
        )                                                         # [B, S, F]
        rank_row = col[0] + _count(ins_below_row, axis=1)
        before = _exclusive_prefix_count(act)                     # [B, S]
        row_below_ins = validr3 & _lt_planes(khr, klr, ikh3, ikl3)
        rank_ins = before + _count(row_below_ins, axis=2)         # [B, S]

        # cut point: m <= F keeps everything left; m > F cuts at m // 2
        m = (
            _count(validr, axis=-1, keepdims=True)
            + _count(act, axis=-1, keepdims=True)
        )                                                         # [B, 1]
        split = m > np.int32(fanout)
        left_n = jnp.where(split, m // np.int32(2), m)            # [B, 1]

        out_col = jax.lax.broadcasted_iota(jnp.int32, (1, fanout, 1), 1)
        ln = left_n[:, :, None]                                   # [B, 1, 1]

        def gather(sel_rank_row, sel_rank_ins, target):
            """One-hot gather of elements whose shifted rank hits ``target``
            output columns; returns the pick masks [B, F, F|S]."""
            pr = validr3 & (sel_rank_row[:, None, :] == target)
            pi = act3 & (sel_rank_ins[:, None, :] == target)
            return pr, pi

        # left side: rank < left_n at column rank
        pick_row_l, pick_ins_l = gather(rank_row, rank_ins, out_col)
        keep_l = out_col < ln
        pick_row_l = pick_row_l & keep_l
        pick_ins_l = pick_ins_l & keep_l
        # right side: rank >= left_n at column rank - left_n; lanes that do
        # not split shift their ranks out of every column
        shift = jnp.where(split, left_n, np.int32(2 * fanout))    # [B, 1]
        pick_row_r, pick_ins_r = gather(
            rank_row - shift, rank_ins - shift, out_col
        )

        hit_l = _any(pick_row_l, axis=-1) | _any(pick_ins_l, axis=-1)
        hit_r = _any(pick_row_r, axis=-1) | _any(pick_ins_r, axis=-1)

        def compact(pick_row, pick_ins, hit, plane_row, plane_ins, fill):
            got = jnp.sum(
                jnp.where(pick_row, plane_row[:, None, :], _ZERO), axis=-1,
                dtype=jnp.int32,
            ) + jnp.sum(
                jnp.where(pick_ins, plane_ins[:, None, :], _ZERO), axis=-1,
                dtype=jnp.int32,
            )
            return jnp.where(hit, got, fill)

        vhi = vhi_ref[...]
        vlo = vlo_ref[...]
        ivh = ivh_ref[...]
        ivl = ivl_ref[...]
        lkh_ref[...] = compact(pick_row_l, pick_ins_l, hit_l, khi, ikh, _KMAX_HI)
        lkl_ref[...] = compact(pick_row_l, pick_ins_l, hit_l, klo, ikl, _KMAX_LO)
        lvh_ref[...] = compact(pick_row_l, pick_ins_l, hit_l, vhi, ivh, _ZERO)
        lvl_ref[...] = compact(pick_row_l, pick_ins_l, hit_l, vlo, ivl, _ZERO)
        rkh_ref[...] = compact(pick_row_r, pick_ins_r, hit_r, khi, ikh, _KMAX_HI)
        rkl_ref[...] = compact(pick_row_r, pick_ins_r, hit_r, klo, ikl, _KMAX_LO)
        rvh_ref[...] = compact(pick_row_r, pick_ins_r, hit_r, vhi, ivh, _ZERO)
        rvl_ref[...] = compact(pick_row_r, pick_ins_r, hit_r, vlo, ivl, _ZERO)
        occl_ref[...] = _count(hit_l, axis=-1, keepdims=True)
        occr_ref[...] = _count(hit_r, axis=-1, keepdims=True)

        # separator = the merged element of rank left_n (right row's head)
        sep_row = validr & (rank_row == left_n)                   # [B, F]
        sep_ins = act & (rank_ins == left_n)                      # [B, S]

        def pick_sep(plane_row, plane_ins, fill):
            got = jnp.sum(
                jnp.where(sep_row, plane_row, _ZERO), axis=-1, keepdims=True,
                dtype=jnp.int32,
            ) + jnp.sum(
                jnp.where(sep_ins, plane_ins, _ZERO), axis=-1, keepdims=True,
                dtype=jnp.int32,
            )
            has = (
                _count(sep_row, axis=-1, keepdims=True)
                + _count(sep_ins, axis=-1, keepdims=True)
            ) > _ZERO
            return jnp.where(split & has, got, fill)

        sep_hi_ref[...] = pick_sep(khi, ikh, _KMAX_HI)
        sep_lo_ref[...] = pick_sep(klo, ikl, _KMAX_LO)
        did_ref[...] = split.astype(jnp.int32)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def leaf_split(
    rows_k: jax.Array,   # [Q, F] int64 leaf key rows (KEY_MAX padding)
    rows_v: jax.Array,   # [Q, F] int64 leaf value rows
    ins_key: jax.Array,  # [Q, S] int64 staged insert keys (KEY_MAX inactive)
    ins_val: jax.Array,  # [Q, S] int64 staged insert values
    *,
    interpret: "bool | None" = None,
    block_b: int = BLOCK_B,
):
    """Merge staged inserts into each leaf row, splitting rows that
    overflow.  Returns ``(left_k [Q, F], left_v [Q, F], right_k [Q, F],
    right_v [Q, F], occ_l [Q] int32, occ_r [Q] int32, sep [Q] int64,
    did_split [Q] int32)`` — ``sep`` is ``KEY_MAX`` and the right row empty
    for lanes that did not split."""
    q, f = rows_k.shape
    s = ins_key.shape[1]
    pad = (-q) % block_b
    if pad:
        rows_k = jnp.pad(rows_k, ((0, pad), (0, 0)), constant_values=KEY_MAX)
        rows_v = jnp.pad(rows_v, ((0, pad), (0, 0)))
        ins_key = jnp.pad(ins_key, ((0, pad), (0, 0)), constant_values=KEY_MAX)
        ins_val = jnp.pad(ins_val, ((0, pad), (0, 0)))
    qp = rows_k.shape[0]

    khi, klo = split_words(rows_k)
    vhi, vlo = split_words(rows_v)
    ikh, ikl = split_words(ins_key)
    ivh, ivl = split_words(ins_val)

    grid = (qp // block_b,)
    row = pl.BlockSpec((block_b, f), _row_block)
    staged = pl.BlockSpec((block_b, s), _row_block)
    lane = pl.BlockSpec((block_b, 1), _row_block)
    outs = pl.pallas_call(
        _make_kernel(f),
        grid=grid,
        in_specs=[row, row, row, row, staged, staged, staged, staged],
        out_specs=[row, row, row, row, row, row, row, row,
                   lane, lane, lane, lane, lane],
        out_shape=[jax.ShapeDtypeStruct((qp, f), jnp.int32)] * 8
        + [jax.ShapeDtypeStruct((qp, 1), jnp.int32)] * 5,
        interpret=use_interpret() if interpret is None else interpret,
        name="leaf_split",
    )(khi, klo, vhi, vlo, ikh, ikl, ivh, ivl)
    lkh, lkl, lvh, lvl, rkh, rkl, rvh, rvl, occl, occr, sh, sl, did = outs
    return (
        join_words((lkh, lkl))[:q],
        join_words((lvh, lvl))[:q],
        join_words((rkh, rkl))[:q],
        join_words((rvh, rvl))[:q],
        occl[:q, 0],
        occr[:q, 0],
        join_words((sh, sl))[:q, 0],
        did[:q, 0],
    )
