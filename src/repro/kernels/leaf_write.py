"""Pallas kernel: batched leaf mutation for the mesh-plane write path.

The compute core of ``core/write.py``: after the owning memory column has
grouped a batch of write requests by target leaf (one row per touched leaf),
this kernel applies, per 1KB leaf row,

  1. a *masked value scatter* — staged in-place updates ``(slot, value)``
     land at their slot via a one-hot compare+reduce (no scatter primitive);
  2. a *rank-based insert merge* — staged new keys (pre-sorted and
     deduplicated by the caller) are merged into the row's slack slots while
     keeping the row sorted: every element's output column is its rank,
     computed with branchless pairwise compares (row-vs-staged both ways),
     then gathered one-hot.  This is the SPMD form of "append into the leaf's
     slack space";
  3. an *occupancy bump* — the new number of live keys per row.

Caller contract (enforced by core/write.py): active staged insert keys are
strictly ascending within a row, distinct from the row's existing keys, and
the row has enough slack (overflowing leaves are shed *before* the kernel —
the host SMO path replays them).  Staged updates target distinct slots.

int64 keys/values travel as (hi, lo) int32 planes like kernels/leaf_scan.py
(the TPU VPU has no native 64-bit lanes).  Inside the kernel body every
value is 32-bit — iotas, literals, index maps and reductions are spelled
int32 so the package-wide x64 flag cannot reach Mosaic — per-lane scalars
travel as [B, 1] columns, and bool masks are never reshaped (Mosaic cannot);
the helpers below are shared by the three leaf kernels.  The pure-jnp oracle is
``kernels/ref.py::leaf_write_ref``; ``interpret=None`` resolves to the
compiled kernel on a TPU and the Pallas interpreter elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.nodes import KEY_MAX
from repro.kernels import use_interpret

BLOCK_B = 8

# KEY_MAX = 0x7FFF_FFFF_FFFF_FFFF as (hi, lo-reinterpreted-signed) planes
_KMAX_HI = np.int32(0x7FFFFFFF)
_KMAX_LO = np.int32(-1)
_ZERO = np.int32(0)


def _split_i64(x: jax.Array):
    """int64 -> (hi int32, lo uint32-as-int32) planes."""
    hi = (x >> 32).astype(jnp.int32)
    lo = (x & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32).astype(jnp.int32)
    return hi, lo


def _join_i64(hi: jax.Array, lo: jax.Array) -> jax.Array:
    return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.uint32).astype(jnp.int64)


def _row_block(i):
    """Index map of a row-blocked operand: block ``i`` along the batch, the
    whole row (an int32 literal — the global x64 flag must not reach the
    kernel's index arithmetic)."""
    return i, jnp.int32(0)


def _any(x: jax.Array, axis: int) -> jax.Array:
    """``jnp.any`` spelled as an int32 max (Mosaic reduces bools through
    the default float type, which x64 makes 64-bit)."""
    return jnp.max(x.astype(jnp.int32), axis=axis) > _ZERO


def _count(x: jax.Array, axis: int, keepdims: bool = False) -> jax.Array:
    """int32 count of true entries (``jnp.sum`` would widen to int64)."""
    return jnp.sum(x.astype(jnp.int32), axis=axis, keepdims=keepdims,
                   dtype=jnp.int32)


def _exclusive_prefix_count(x: jax.Array) -> jax.Array:
    """Number of true entries before each position of a [B, S] mask, as a
    0/1 strictly-upper-triangular matmul (Mosaic has no ``cumsum``; exact
    in f32 since counts stay <= S)."""
    s = x.shape[-1]
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
        < jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
    ).astype(jnp.float32)
    return jnp.dot(
        x.astype(jnp.float32), tri, preferred_element_type=jnp.float32
    ).astype(jnp.int32)


def _live(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """Slots holding a real key (not the KEY_MAX padding)."""
    return ~((hi == _KMAX_HI) & (lo == _KMAX_LO))


def _lt_planes(ahi, alo, bhi, blo):
    """(ahi,alo) < (bhi,blo) treating lo as unsigned."""
    flip = jnp.int32(-0x80000000)
    return (ahi < bhi) | ((ahi == bhi) & ((alo ^ flip) < (blo ^ flip)))


def _make_kernel(fanout: int):
    def kernel(
        khi_ref, klo_ref, vhi_ref, vlo_ref,
        us_ref, uvh_ref, uvl_ref,
        ikh_ref, ikl_ref, ivh_ref, ivl_ref,
        okh_ref, okl_ref, ovh_ref, ovl_ref, occ_ref,
    ):
        khi = khi_ref[...]                     # [B, F] int32 planes
        klo = klo_ref[...]
        vhi = vhi_ref[...]
        vlo = vlo_ref[...]
        us = us_ref[...]                       # [B, S] int32 (-1 inactive)
        ikh = ikh_ref[...]                     # [B, S]
        ikl = ikl_ref[...]

        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, fanout), 2)
        # Mosaic cannot reshape bool vectors, so every [B, S, F] mask is
        # built from int32 planes already broadcast to their 3-D position
        ikh3, ikl3 = ikh[:, :, None], ikl[:, :, None]             # [B, S, 1]
        khr, klr = khi[:, None, :], klo[:, None, :]               # [B, 1, F]

        # 1. masked value scatter: staged update j lands at column us[j]
        #    (one-hot compare + reduce; staged slots are distinct per row,
        #    and an inactive slot of -1 matches no column)
        onehot = us[:, :, None] == col                            # [B, S, F]
        has_u = _any(onehot, axis=1)                              # [B, F]

        def upd_pick(plane):
            return jnp.sum(jnp.where(onehot, plane[:, :, None], _ZERO),
                           axis=1, dtype=jnp.int32)

        v1h = jnp.where(has_u, upd_pick(uvh_ref[...]), vhi)
        v1l = jnp.where(has_u, upd_pick(uvl_ref[...]), vlo)

        # 2. rank-based insert merge.  Active staged keys are distinct from
        #    each other and from the row's keys, so strict compares give a
        #    total order; KEY_MAX padding never participates.
        act = _live(ikh, ikl)                                     # [B, S]
        validr = _live(khr, klr)                                  # [B, 1, F]
        # row element i keeps its index plus the staged keys below it
        ins_below_row = _live(ikh3, ikl3) & _lt_planes(
            ikh3, ikl3, khr, klr
        )                                                         # [B, S, F]
        rank_row = col[0] + _count(ins_below_row, axis=1)
        # staged element j: actives before it plus the row keys below it
        before = _exclusive_prefix_count(act)                     # [B, S]
        row_below_ins = validr & _lt_planes(khr, klr, ikh3, ikl3)  # [B, S, F]
        rank_ins = before + _count(row_below_ins, axis=2)

        # 3. one-hot rank gather into the F output columns + occupancy bump
        out_col = jax.lax.broadcasted_iota(jnp.int32, (1, fanout, 1), 1)
        pick_row = validr & (rank_row[:, None, :] == out_col)
        pick_ins = _live(ikh[:, None, :], ikl[:, None, :]) & (
            rank_ins[:, None, :] == out_col
        )
        hit = _any(pick_row, axis=-1) | _any(pick_ins, axis=-1)

        def compact(plane_row, plane_ins, fill):
            got = jnp.sum(
                jnp.where(pick_row, plane_row[:, None, :], _ZERO), axis=-1,
                dtype=jnp.int32,
            ) + jnp.sum(
                jnp.where(pick_ins, plane_ins[:, None, :], _ZERO), axis=-1,
                dtype=jnp.int32,
            )
            return jnp.where(hit, got, fill)

        okh_ref[...] = compact(khi, ikh, _KMAX_HI)
        okl_ref[...] = compact(klo, ikl, _KMAX_LO)
        ovh_ref[...] = compact(v1h, ivh_ref[...], _ZERO)
        ovl_ref[...] = compact(v1l, ivl_ref[...], _ZERO)
        occ_ref[...] = _count(hit, axis=-1, keepdims=True)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def leaf_write(
    rows_k: jax.Array,    # [Q, F] int64 leaf key rows (KEY_MAX padding)
    rows_v: jax.Array,    # [Q, F] int64 leaf value rows
    upd_slot: jax.Array,  # [Q, S] int32 staged update slots (-1 inactive)
    upd_val: jax.Array,   # [Q, S] int64 staged update values
    ins_key: jax.Array,   # [Q, S] int64 staged insert keys (KEY_MAX inactive)
    ins_val: jax.Array,   # [Q, S] int64 staged insert values
    *,
    interpret: "bool | None" = None,
    block_b: int = BLOCK_B,
):
    """Apply one batch of staged writes per leaf row.  Returns ``(new_keys
    [Q, F] int64, new_values [Q, F] int64, new_occupancy [Q] int32)``."""
    q, f = rows_k.shape
    s = upd_slot.shape[1]
    pad = (-q) % block_b
    if pad:
        rows_k = jnp.pad(rows_k, ((0, pad), (0, 0)), constant_values=KEY_MAX)
        rows_v = jnp.pad(rows_v, ((0, pad), (0, 0)))
        upd_slot = jnp.pad(upd_slot, ((0, pad), (0, 0)), constant_values=-1)
        upd_val = jnp.pad(upd_val, ((0, pad), (0, 0)))
        ins_key = jnp.pad(ins_key, ((0, pad), (0, 0)), constant_values=KEY_MAX)
        ins_val = jnp.pad(ins_val, ((0, pad), (0, 0)))
    qp = rows_k.shape[0]

    khi, klo = _split_i64(rows_k)
    vhi, vlo = _split_i64(rows_v)
    uvh, uvl = _split_i64(upd_val)
    ikh, ikl = _split_i64(ins_key)
    ivh, ivl = _split_i64(ins_val)

    grid = (qp // block_b,)
    row = pl.BlockSpec((block_b, f), _row_block)
    staged = pl.BlockSpec((block_b, s), _row_block)
    lane = pl.BlockSpec((block_b, 1), _row_block)
    okh, okl, ovh, ovl, occ = pl.pallas_call(
        _make_kernel(f),
        grid=grid,
        in_specs=[row, row, row, row,
                  staged, staged, staged,
                  staged, staged, staged, staged],
        out_specs=[row, row, row, row, lane],
        out_shape=[
            jax.ShapeDtypeStruct((qp, f), jnp.int32),
            jax.ShapeDtypeStruct((qp, f), jnp.int32),
            jax.ShapeDtypeStruct((qp, f), jnp.int32),
            jax.ShapeDtypeStruct((qp, f), jnp.int32),
            jax.ShapeDtypeStruct((qp, 1), jnp.int32),
        ],
        interpret=use_interpret() if interpret is None else interpret,
    )(khi, klo, vhi, vlo, upd_slot.astype(jnp.int32), uvh, uvl,
      ikh, ikl, ivh, ivl)
    out_k = _join_i64(okh, okl)
    out_v = _join_i64(ovh, ovl)
    return out_k[:q], out_v[:q], occ[:q, 0]
