"""Pallas kernel: batched in-node lower-bound search.

The innermost compute of every B+-tree traversal (paper Algorithm 1's
``parent.search(key)``): given one 1KB node row per query lane, find the
rightmost separator <= key, plus exact-match hit/value for leaves.

TPU mapping: the 64-wide key row is one VPU vector register row; the
comparison + popcount is branchless lane arithmetic.  We tile the batch over
the grid with BlockSpec so each program works on a [BLOCK_B, FANOUT] VMEM
tile.  int64 keys are carried as (hi, lo) int32 planes because the TPU VPU
has no native 64-bit lanes (DESIGN.md §2: hardware adaptation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.nodes import FANOUT
from repro.core.pool import SEP_SUFFIX_SENTINEL, split_words
from repro.kernels import use_interpret

BLOCK_B = 256


def _leq_planes(khi, klo, qhi, qlo):
    """(khi,klo) <= (qhi,qlo) treating lo as unsigned."""
    # compare lo as unsigned by flipping the sign bit into signed order
    flip = jnp.int32(-0x80000000)
    klo_s = klo ^ flip
    qlo_s = qlo ^ flip
    return (khi < qhi) | ((khi == qhi) & (klo_s <= qlo_s))


def _node_search_kernel(
    keys_hi_ref, keys_lo_ref, q_hi_ref, q_lo_ref, vals_ref,
    slot_ref, found_ref, out_val_ref,
):
    khi = keys_hi_ref[...]            # [B, F] int32
    klo = keys_lo_ref[...]
    qhi = q_hi_ref[...]               # [B] int32
    qlo = q_lo_ref[...]
    leq = _leq_planes(khi, klo, qhi[:, None], qlo[:, None])
    cnt = jnp.sum(leq, axis=-1, dtype=jnp.int32)
    slot_ref[...] = jnp.maximum(cnt - 1, 0).astype(jnp.int32)
    eq = (khi == qhi[:, None]) & (klo == qlo[:, None])
    found_ref[...] = jnp.any(eq, axis=-1)
    vhi = jnp.sum(jnp.where(eq, vals_ref[..., 0], 0), axis=-1, dtype=jnp.int32)
    vlo = jnp.sum(jnp.where(eq, vals_ref[..., 1], 0), axis=-1, dtype=jnp.int32)
    out_val_ref[..., 0] = vhi
    out_val_ref[..., 1] = vlo


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def node_search(
    node_keys: jax.Array,   # [B, FANOUT] int64
    queries: jax.Array,     # [B] int64
    node_values: jax.Array, # [B, FANOUT] int64
    *,
    interpret: "bool | None" = None,
    block_b: int = BLOCK_B,
):
    """Batched lower-bound + exact-match.  Returns (slot, found, value)."""
    b = node_keys.shape[0]
    pad = (-b) % block_b
    if pad:
        node_keys = jnp.pad(node_keys, ((0, pad), (0, 0)), constant_values=0)
        node_values = jnp.pad(node_values, ((0, pad), (0, 0)))
        queries = jnp.pad(queries, (0, pad), constant_values=-1)
    bp = node_keys.shape[0]

    khi, klo = split_words(node_keys)
    qhi, qlo = split_words(queries)
    vhi, vlo = split_words(node_values)
    vplanes = jnp.stack([vhi, vlo], axis=-1)  # [B, F, 2]

    grid = (bp // block_b,)
    out = pl.pallas_call(
        _node_search_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, FANOUT), lambda i: (i, 0)),
            pl.BlockSpec((block_b, FANOUT), lambda i: (i, 0)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b, FANOUT, 2), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b, 2), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp,), jnp.int32),
            jax.ShapeDtypeStruct((bp,), jnp.bool_),
            jax.ShapeDtypeStruct((bp, 2), jnp.int32),
        ],
        interpret=use_interpret() if interpret is None else interpret,
    )(khi, klo, qhi, qlo, vplanes)
    slot, found, vpl = out
    value = (vpl[:, 0].astype(jnp.int64) << 32) | (
        vpl[:, 1].astype(jnp.uint32).astype(jnp.int64)
    )
    return slot[:b], found[:b], value[:b]


# ---------------------------------------------------------------------------
# Prefix-compressed separator search (DESIGN.md §13)
# ---------------------------------------------------------------------------


def _prefix_search_kernel(
    p_hi_ref, p_lo_ref, nbits_ref, suffix_ref,
    keys_hi_ref, keys_lo_ref, q_hi_ref, q_lo_ref,
    slot_ref,
):
    phi = p_hi_ref[...]               # [B] int32
    plo = p_lo_ref[...]
    nb = nbits_ref[...]               # [B] int32
    suf = suffix_ref[...]             # [B, F] int32
    qhi = q_hi_ref[...]
    qlo = q_lo_ref[...]
    good = nb >= 0
    nb0 = jnp.maximum(nb, 0)
    # nbits <= 30 < 32, so the retained-bit mask lives entirely in the lo
    # plane: the hi plane carries prefix bits only
    mask = (jnp.int32(1) << nb0) - jnp.int32(1)
    q_suf = qlo & mask                # [0, 2**30): always non-negative
    qp_lo = qlo & ~mask
    flip = jnp.int32(-0x80000000)
    eq = (phi == qhi) & (plo == qp_lo)
    lt = (phi < qhi) | ((phi == qhi) & ((plo ^ flip) < (qp_lo ^ flip)))
    # the pad sentinel exceeds every real (< 2**30) suffix AND every masked
    # query, so both sums count real separators only
    nreal = jnp.sum(
        (suf != SEP_SUFFIX_SENTINEL).astype(jnp.int32), axis=-1
    )
    cnt_sfx = jnp.sum((suf <= q_suf[:, None]).astype(jnp.int32), axis=-1)
    cnt_c = jnp.where(eq, cnt_sfx, jnp.where(lt, nreal, 0))
    # incompressible rows (nbits = -1) fall back to the canonical key row
    leq = _leq_planes(
        keys_hi_ref[...], keys_lo_ref[...], qhi[:, None], qlo[:, None]
    )
    cnt_f = jnp.sum(leq.astype(jnp.int32), axis=-1)
    cnt = jnp.where(good, cnt_c, cnt_f)
    slot_ref[...] = jnp.maximum(cnt - 1, 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def node_search_prefix(
    prefix: jax.Array,      # [B] int64 per-row shared prefix (low bits 0)
    nbits: jax.Array,       # [B] int32 retained low bits (-1 incompressible)
    suffix: jax.Array,      # [B, FANOUT] int32 truncated separators
    node_keys: jax.Array,   # [B, FANOUT] int64 canonical rows (fallback)
    queries: jax.Array,     # [B] int64
    *,
    interpret: "bool | None" = None,
    block_b: int = BLOCK_B,
):
    """Batched lower-bound over prefix-compressed separator rows
    (core/pool.py ``SepPlanes``; one gathered row triple per query lane).

    Matches ``pool._slot`` bit-for-bit for queries below KEY_MAX (the
    inactive-lane sentinel): a compressible row reduces the 64-wide int64
    compare to one 64-bit prefix compare plus a 64-wide *int32* suffix
    compare — half the separator bytes per row; rows whose span needs more
    than SEP_MAX_NBITS low bits take the canonical comparison.  Returns
    ``slot [B] int32``.
    """
    b = prefix.shape[0]
    pad = (-b) % block_b
    if pad:
        prefix = jnp.pad(prefix, (0, pad), constant_values=0)
        nbits = jnp.pad(nbits, (0, pad), constant_values=0)
        suffix = jnp.pad(
            suffix, ((0, pad), (0, 0)),
            constant_values=int(SEP_SUFFIX_SENTINEL),
        )
        node_keys = jnp.pad(node_keys, ((0, pad), (0, 0)), constant_values=0)
        queries = jnp.pad(queries, (0, pad), constant_values=-1)
    bp = prefix.shape[0]

    phi, plo = split_words(prefix)
    khi, klo = split_words(node_keys)
    qhi, qlo = split_words(queries)

    grid = (bp // block_b,)
    slot = pl.pallas_call(
        _prefix_search_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b, FANOUT), lambda i: (i, 0)),
            pl.BlockSpec((block_b, FANOUT), lambda i: (i, 0)),
            pl.BlockSpec((block_b, FANOUT), lambda i: (i, 0)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block_b,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((bp,), jnp.int32),
        interpret=use_interpret() if interpret is None else interpret,
    )(phi, plo, nbits.astype(jnp.int32), suffix, khi, klo, qhi, qlo)
    return slot[:b]
