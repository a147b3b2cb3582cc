"""Pallas kernel: paged decode attention (the DEX-paged KV consumer).

Serving (serve/kv_cache.py) stores KV in fixed-size pages indexed by the DEX
B+-tree; this kernel consumes the resolved page table.  Grid is
(batch, kv_heads, pages_per_request) with the *page table prefetched as
scalars* so each kv block's index map dereferences ``table[b, p]`` — the TPU
idiom for pointer indirection (scalar prefetch + dynamic block index), i.e.
the same "resolve remote pointer, then stream the node" pattern as DEX's
fetch path, one level down the memory hierarchy.

Online softmax runs across the sequential page dimension in VMEM scratch;
positions beyond ``seq_len`` are masked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import use_interpret

NEG_INF = -1e30


def _paged_kernel(
    table_ref, seqlen_ref,            # scalar prefetch
    q_ref, k_ref, v_ref,
    o_ref,
    m_scr, l_scr, acc_scr,
    *, page, n_pages, scale,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    seq_len = seqlen_ref[b]
    # pages beyond the request's length are skipped entirely
    run = (p * page) < seq_len

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # [G, D]
        k = k_ref[0, :, 0].astype(jnp.float32)            # [page, D]
        v = v_ref[0, :, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [G, page]
        pos = p * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(pr, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            pr, v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(
    q: jax.Array,         # [B, H, D] one decode token per request
    k_pages: jax.Array,   # [P, page, HKV, D]
    v_pages: jax.Array,   # [P, page, HKV, D]
    page_table: jax.Array,  # [B, pages_per_req] int32 (DEX-resolved)
    seq_lens: jax.Array,  # [B] int32
    *,
    interpret: "bool | None" = None,
):
    b, h, d = q.shape
    _, page, hkv, _ = k_pages.shape
    assert h % hkv == 0
    group = h // hkv
    ppr = page_table.shape[1]
    scale = 1.0 / np.sqrt(d)

    # [B, HKV, G, D]: queries grouped by kv head
    qg = q.reshape(b, hkv, group, d)

    grid = (b, hkv, ppr)

    def q_index(table, b_, n, p):
        del table, p
        return (b_, n, 0, 0)

    def kv_index(table, b_, n, p):
        return (table[b_, p], 0, n, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, group, d), lambda b_, n, p, table, sl: (b_, n, 0, 0)),
            pl.BlockSpec((1, page, 1, d), lambda b_, n, p, table, sl: (table[b_, p], 0, n, 0)),
            pl.BlockSpec((1, page, 1, d), lambda b_, n, p, table, sl: (table[b_, p], 0, n, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, d), lambda b_, n, p, table, sl: (b_, n, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, page=page, n_pages=ppr, scale=scale
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=use_interpret() if interpret is None else interpret,
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32), qg, k_pages, v_pages)
    return out.reshape(b, h, d)
