"""Pallas kernel: blocked flash attention (prefill path).

Standard online-softmax tiling for the LM stack's perf-critical prefill:
grid (batch*heads, q_blocks, kv_blocks) with f32 running max/denominator/
accumulator in VMEM scratch that persists across the sequential kv grid
dimension.  Causal blocks fully above the diagonal are skipped via
``pl.when`` (halving prefill FLOPs).  GQA is handled by an index map that
points each query head at its kv group — no KV replication in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import use_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, bq, bk, nk, q_offset,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    # causal: skip kv blocks strictly above this q block's last row
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1 + q_offset)
    else:
        run = True

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale        # [bq, D]
        k = k_ref[0].astype(jnp.float32)                # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                               # [bq, bk]
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos + q_offset >= kpos, s, NEG_INF)
        m_prev = m_scr[...]                             # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                          # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)                 # [bq, 1]
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "scale"),
)
def flash_attention(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, HKV, Sk, D]
    v: jax.Array,  # [B, HKV, Sk, D]
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: "bool | None" = None,
):
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert h % hkv == 0, "query heads must be a multiple of kv heads"
    group = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, "seq lens must tile"
    nq, nk = sq // bq, sk // bk

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)

    def kv_index(bh, qi, ki):
        return (bh // h) * hkv + (bh % h) // group, ki, 0

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, bq=bq, bk=bk, nk=nk, q_offset=sk - sq,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=use_interpret() if interpret is None else interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)
