"""Pallas kernel: whole-subtree traversal (the offload executor).

This is the memory-server side of the paper's opportunistic offloading (§6):
upon receiving a pushed-down operation, the owner walks the level-M subtree
locally and returns only the result.  On TPU the subtree block (paper: all
nodes below level M, grouped on one server) is staged once into VMEM and a
batch of queries walks it level-synchronously.

TPU adaptation (DESIGN.md §2): the CPU's pointer-chasing loop becomes a
*one-hot matmul gather* on the MXU — selecting node rows via
``onehot([Bq, C]) @ plane([C, F])``.  Because every one-hot row has exactly
one nonzero, f32 accumulation is exact as long as each operand plane fits the
f32 mantissa; int64 keys/values are therefore carried as four 16-bit planes
and int32 children as two.  Pointer dereference -> systolic array work, which
is the idiomatic TPU replacement for irregular memory access.

VMEM budget: a subtree block of C nodes holds 10 f32 planes of [C, 64]:
C=45 (M=1) -> 115 KiB; C=1981 (M=2) -> ~5 MiB.  Both fit v5e VMEM (~16 MiB);
M=3 blocks must stream (not needed: the serving integration uses M<=2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.nodes import FANOUT
from repro.kernels import use_interpret

BLOCK_B = 128


def _planes16_i64(x: jax.Array):
    """int64 -> four f32 planes of 16 bits each (exact in f32)."""
    x = x.astype(jnp.int64)
    return [
        ((x >> (16 * (3 - i))) & jnp.int64(0xFFFF)).astype(jnp.float32)
        for i in range(4)
    ]


def _planes16_i32(x: jax.Array):
    x = x.astype(jnp.int32)
    return [
        ((x >> (16 * (1 - i))) & jnp.int32(0xFFFF)).astype(jnp.float32)
        for i in range(2)
    ]


def _recombine_i64_hi_lo(p0, p1, p2, p3):
    """Four 16-bit planes -> (hi, lo) int32 with original bit patterns."""
    hi = (p0.astype(jnp.int32) << 16) | p1.astype(jnp.int32)
    lo = (p2.astype(jnp.int32) << 16) | p3.astype(jnp.int32)
    return hi, lo


def _leq_hi_lo(khi, klo, qhi, qlo):
    flip = jnp.int32(-0x80000000)
    return (khi < qhi) | ((khi == qhi) & ((klo ^ flip) <= (qlo ^ flip)))


def _make_kernel(levels: int, c_nodes: int):
    iota_c = None

    def kernel(
        # key planes [C, F] f32 x4, child planes x2, value planes x4
        k0, k1, k2, k3, c0, c1, v0, v1, v2, v3,
        q_hi_ref, q_lo_ref,
        found_ref, val_hi_ref, val_lo_ref,
    ):
        qhi = q_hi_ref[...]                       # [Bq] int32
        qlo = q_lo_ref[...]
        bq = qhi.shape[0]
        local = jnp.zeros((bq,), jnp.int32)       # subtree root = local id 0
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, c_nodes), 1)

        def gather(plane_ref, onehot):
            return jax.lax.dot(
                onehot, plane_ref[...], precision=jax.lax.Precision.HIGHEST
            )

        for lvl in range(levels):
            onehot = (local[:, None] == col).astype(jnp.float32)   # [Bq, C]
            khi, klo = _recombine_i64_hi_lo(
                gather(k0, onehot), gather(k1, onehot),
                gather(k2, onehot), gather(k3, onehot),
            )                                                       # [Bq, F]
            if lvl < levels - 1:
                leq = _leq_hi_lo(khi, klo, qhi[:, None], qlo[:, None])
                cnt = jnp.sum(leq, axis=-1, dtype=jnp.int32)
                slot = jnp.maximum(cnt - 1, 0)                      # [Bq]
                child = (gather(c0, onehot).astype(jnp.int32) << 16) | gather(
                    c1, onehot
                ).astype(jnp.int32)                                 # [Bq, F]
                fcol = jax.lax.broadcasted_iota(jnp.int32, child.shape, 1)
                pick = fcol == slot[:, None]
                local = jnp.sum(jnp.where(pick, child, 0), axis=-1,
                                dtype=jnp.int32)
            else:
                eq = (khi == qhi[:, None]) & (klo == qlo[:, None])
                found_ref[...] = jnp.any(eq, axis=-1)
                vhi, vlo = _recombine_i64_hi_lo(
                    gather(v0, onehot), gather(v1, onehot),
                    gather(v2, onehot), gather(v3, onehot),
                )
                val_hi_ref[...] = jnp.sum(jnp.where(eq, vhi, 0), axis=-1,
                                          dtype=jnp.int32)
                val_lo_ref[...] = jnp.sum(jnp.where(eq, vlo, 0), axis=-1,
                                          dtype=jnp.int32)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("levels", "interpret", "block_b")
)
def subtree_walk(
    block_keys: jax.Array,      # [C, FANOUT] int64 (or a [C * FANOUT] row)
    block_children: jax.Array,  # [C, FANOUT] int32
    block_values: jax.Array,    # [C, FANOUT] int64
    queries: jax.Array,         # [B] int64
    *,
    levels: int,
    interpret: "bool | None" = None,
    block_b: int = BLOCK_B,
):
    """Walk one subtree block for a batch of queries.  Returns
    (found [B] bool, values [B] int64)."""
    block_keys, block_children, block_values = (
        x.reshape(-1, FANOUT) for x in (block_keys, block_children,
                                        block_values)
    )
    c_nodes = block_keys.shape[0]
    b = queries.shape[0]
    pad = (-b) % block_b
    if pad:
        queries = jnp.pad(queries, (0, pad), constant_values=-1)
    bp = queries.shape[0]

    kp = _planes16_i64(block_keys)
    cp = _planes16_i32(block_children)
    vp = _planes16_i64(block_values)
    qhi = (queries >> 32).astype(jnp.int32)
    qlo = (queries & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32).astype(jnp.int32)

    grid = (bp // block_b,)
    block_full = pl.BlockSpec((c_nodes, FANOUT), lambda i: (0, 0))
    lane = pl.BlockSpec((block_b,), lambda i: (i,))
    found, vhi, vlo = pl.pallas_call(
        _make_kernel(levels, c_nodes),
        grid=grid,
        in_specs=[block_full] * 10 + [lane, lane],
        out_specs=[lane, lane, lane],
        out_shape=[
            jax.ShapeDtypeStruct((bp,), jnp.bool_),
            jax.ShapeDtypeStruct((bp,), jnp.int32),
            jax.ShapeDtypeStruct((bp,), jnp.int32),
        ],
        interpret=use_interpret() if interpret is None else interpret,
    )(*kp, *cp, *vp, qhi, qlo)
    values = (vhi.astype(jnp.int64) << 32) | (
        vlo.astype(jnp.uint32).astype(jnp.int64)
    )
    return found[:b], values[:b]
