"""Pallas kernel: fence-key-subdivided multi-leaf range-scan compaction.

The compute core of the mesh-plane range scan (paper §7 Range Query): after
the traversal layer has assembled, per scan lane, a *window* of consecutive
leaf rows (the start leaf plus its successors in global leaf order), this
kernel performs

  1. a vectorized in-leaf lower bound — mask out keys below the start key and
     KEY_MAX padding (empty slots / out-of-range leaves);
  2. a masked gather ("compaction") of up to ``count`` surviving rows into a
     dense [B, max_count] result, preserving ascending key order.

Because leaves are consecutive in key order, the surviving keys are already
sorted in window-slot order, so the gather is rank-based: element with
selection rank ``j`` lands in output column ``j``.  On TPU the rank is a
0/1 triangular matmul over the row (Mosaic lowers no ``cumsum``) and the
gather a one-hot compare+reduce over the window — branchless VPU work, no
scatter (DESIGN.md §3).  Per-lane scalars travel as [B, 1] columns so every
block obeys the TPU's (8, 128) tiling.

int64 keys/values travel as (hi, lo) int32 planes like kernels/node_search.py
(the TPU VPU has no native 64-bit lanes).  The pure-jnp oracle is
``kernels/ref.py::leaf_scan_ref``; ``interpret=None`` resolves to the
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
from repro.kernels.leaf_write import (
    _KMAX_HI,
    _KMAX_LO,
    _ZERO,
    _count,
    _exclusive_prefix_count,
    _join_i64,
    _live,
    _lt_planes,
    _row_block,
    _split_i64,
)

BLOCK_B = 8


def _make_kernel(max_count: int):
    def kernel(
        khi_ref, klo_ref, vhi_ref, vlo_ref, shi_ref, slo_ref, cnt_ref,
        okhi_ref, oklo_ref, ovhi_ref, ovlo_ref, taken_ref,
    ):
        khi = khi_ref[...]                     # [B, W] int32
        klo = klo_ref[...]
        shi = shi_ref[...]                     # [B, 1] int32
        slo = slo_ref[...]
        cnt = cnt_ref[...]                     # [B, 1] int32

        # 1. in-leaf lower bound, vectorized over the whole window: drop
        #    KEY_MAX padding and keys below the start key
        mask = _live(khi, klo) & ~_lt_planes(khi, klo, shi, slo)
        rank = _exclusive_prefix_count(mask) + mask.astype(jnp.int32)
        sel = mask & (rank <= cnt)                           # [B, W]
        taken = _count(sel, axis=-1, keepdims=True)          # [B, 1]
        taken_ref[...] = taken

        # 2. rank-based masked gather: window element with selection rank
        #    j+1 -> output column j (one-hot compare + reduce, no scatter);
        #    ranks 1..taken are all present, so column j is filled iff
        #    j < taken
        srank = jnp.where(sel, rank, _ZERO)                  # [B, W]
        jcol = jax.lax.broadcasted_iota(
            jnp.int32, (1, max_count, 1), 1
        ) + np.int32(1)                                       # [1, MC, 1]
        pick = srank[:, None, :] == jcol                     # [B, MC, W]
        hit = (
            jax.lax.broadcasted_iota(jnp.int32, (1, max_count), 1) < taken
        )                                                    # [B, MC]

        def compact(plane, fill):
            got = jnp.sum(
                jnp.where(pick, plane[:, None, :], _ZERO), axis=-1,
                dtype=jnp.int32,
            )
            return jnp.where(hit, got, fill)

        okhi_ref[...] = compact(khi, _KMAX_HI)
        oklo_ref[...] = compact(klo, _KMAX_LO)
        ovhi_ref[...] = compact(vhi_ref[...], _ZERO)
        ovlo_ref[...] = compact(vlo_ref[...], _ZERO)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("max_count", "interpret", "block_b")
)
def leaf_scan(
    window_keys: jax.Array,    # [B, W] int64, W = hops * FANOUT
    window_values: jax.Array,  # [B, W] int64
    start_keys: jax.Array,     # [B] int64
    counts: jax.Array,         # [B] int32/int64
    *,
    max_count: int,
    interpret: "bool | None" = None,
    block_b: int = BLOCK_B,
):
    """Compact up to ``counts[b]`` records with key >= ``start_keys[b]`` out
    of each lane's leaf window.  Returns ``(keys [B, max_count] int64
    KEY_MAX-padded, values [B, max_count] int64, taken [B] int32)``."""
    b, w = window_keys.shape
    counts = jnp.clip(counts.astype(jnp.int32), 0, max_count)
    pad = (-b) % block_b
    if pad:
        window_keys = jnp.pad(window_keys, ((0, pad), (0, 0)),
                              constant_values=KEY_MAX)
        window_values = jnp.pad(window_values, ((0, pad), (0, 0)))
        start_keys = jnp.pad(start_keys, (0, pad))
        counts = jnp.pad(counts, (0, pad))
    bp = window_keys.shape[0]

    khi, klo = _split_i64(window_keys)
    vhi, vlo = _split_i64(window_values)
    shi, slo = _split_i64(start_keys.astype(jnp.int64))

    grid = (bp // block_b,)
    row = pl.BlockSpec((block_b, w), _row_block)
    out_row = pl.BlockSpec((block_b, max_count), _row_block)
    lane = pl.BlockSpec((block_b, 1), _row_block)
    okhi, oklo, ovhi, ovlo, taken = pl.pallas_call(
        _make_kernel(max_count),
        grid=grid,
        in_specs=[row, row, row, row, lane, lane, lane],
        out_specs=[out_row, out_row, out_row, out_row, lane],
        out_shape=[
            jax.ShapeDtypeStruct((bp, max_count), jnp.int32),
            jax.ShapeDtypeStruct((bp, max_count), jnp.int32),
            jax.ShapeDtypeStruct((bp, max_count), jnp.int32),
            jax.ShapeDtypeStruct((bp, max_count), jnp.int32),
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
        ],
        interpret=use_interpret() if interpret is None else interpret,
    )(khi, klo, vhi, vlo, shi[:, None], slo[:, None], counts[:, None])
    out_k = _join_i64(okhi, oklo)
    out_v = _join_i64(ovhi, ovlo)
    return out_k[:b], out_v[:b], taken[:b, 0]
