"""Pallas kernel: selective-scan recurrence (Mamba-1 style, diagonal A).

Used by the ``falcon-mamba-7b`` / ``zamba2-2.7b`` architectures.  Each
program owns a [block_d] slice of channels for one batch element and runs
the time recurrence with the state held in VMEM:

    h_t = exp(delta_t * A) * h_{t-1} + delta_t * x_t * B_t
    y_t = <h_t, C_t>

The time loop is sequential (``lax.fori_loop``) with all chunk operands
staged in VMEM — the TPU-native layout puts channels on lanes so each step
is a [block_d, N] VPU update.  (Training uses the chunked associative-scan
jnp path in models/mamba.py; this kernel is the fused decode/short-sequence
executor and the oracle target.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import use_interpret

DEFAULT_BLOCK_D = 128


def _mamba_kernel(delta_ref, a_ref, b_ref, c_ref, x_ref, y_ref, h_scr, *, length):
    h_scr[...] = jnp.zeros_like(h_scr[...])
    a = a_ref[0].astype(jnp.float32)                  # [bd, N]

    def step(t, _):
        dt = delta_ref[0, t].astype(jnp.float32)      # [bd]
        bt = b_ref[0, t].astype(jnp.float32)          # [N]
        ct = c_ref[0, t].astype(jnp.float32)          # [N]
        xt = x_ref[0, t].astype(jnp.float32)          # [bd]
        da = jnp.exp(dt[:, None] * a)                 # [bd, N]
        h = da * h_scr[...] + (dt * xt)[:, None] * bt[None, :]
        h_scr[...] = h
        y_ref[0, t] = jnp.sum(h * ct[None, :], axis=-1).astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, length, step, ())


@functools.partial(jax.jit, static_argnames=("interpret", "block_d"))
def mamba_scan(
    delta: jax.Array,  # [B, L, D] f32 (post-softplus)
    A: jax.Array,      # [D, N]
    Bmat: jax.Array,   # [B, L, N]
    C: jax.Array,      # [B, L, N]
    x: jax.Array,      # [B, L, D]
    *,
    interpret: "bool | None" = None,
    block_d: int = DEFAULT_BLOCK_D,
):
    b, l, d = x.shape
    n = A.shape[1]
    bd = min(block_d, d)
    assert d % bd == 0
    nd = d // bd

    # channel-major layouts: [B, L, D] kept, A tiled per block
    grid = (b, nd)
    out = pl.pallas_call(
        functools.partial(_mamba_kernel, length=l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, l, bd), lambda i, j: (i, 0, j)),   # delta
            pl.BlockSpec((1, bd, n), lambda i, j: (0, j, 0)),   # A (broadcast B)
            pl.BlockSpec((1, l, n), lambda i, j: (i, 0, 0)),    # B
            pl.BlockSpec((1, l, n), lambda i, j: (i, 0, 0)),    # C
            pl.BlockSpec((1, l, bd), lambda i, j: (i, 0, j)),   # x
        ],
        out_specs=pl.BlockSpec((1, l, bd), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, l, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        interpret=use_interpret() if interpret is None else interpret,
    )(delta, A[None], Bmat, C, x)
    return out
