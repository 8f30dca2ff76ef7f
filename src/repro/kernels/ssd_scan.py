"""Mamba2 SSD chunk scan — Pallas TPU kernel.

One program per (batch, head); chunks are the innermost sequential grid
axis, carrying the [P, N] SSM state in fp32 VMEM scratch. Within a chunk
everything is dense MXU work:

    L      = exp(segsum(a_chunk))          [l, l] lower-triangular
    y_diag = ((C B^T) * L) x               intra-chunk
    y_off  = C state^T * exp(a_cum)        contribution of carried state
    state  = exp(a_sum) state + (B * decay)^T x

This is the TPU-native shape of the SSD algorithm (arXiv 2405.21060 §6):
instead of the paper's GPU warp-level scan, the inter-chunk recurrence is a
sequential grid axis (cheap: S/chunk steps) and all intra-chunk terms are
(l x l)/(l x N)/(P x N) matmuls sized to the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, fin_ref, state_ref, *,
                nc: int, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    P, N = state_ref.shape
    x = x_ref[...].reshape(chunk, P).astype(jnp.float32)
    a = a_ref[...].reshape(1, chunk).astype(jnp.float32)
    b = b_ref[...].reshape(chunk, N).astype(jnp.float32)
    c = c_ref[...].reshape(chunk, N).astype(jnp.float32)

    # inclusive prefix sum of the log-decays as two triangular matmuls
    # (Mosaic has no cumsum), once as a column and once as a row; HIGHEST
    # keeps the f32 sums exact instead of rounding operands to bf16
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    hi = jax.lax.Precision.HIGHEST
    a_cum_col = jax.lax.dot_general(
        (jj <= ii).astype(jnp.float32), a, (((1,), (1,)), ((), ())),
        precision=hi)                            # [l, 1]
    a_cum_row = jax.lax.dot_general(
        a, (ii <= jj).astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=hi)                            # [1, l]
    a_sum = jnp.sum(a)
    # segsum: L[i,j] = exp(sum_{j<k<=i} a_k) for j<=i else 0
    L = jnp.where(jj <= ii, jnp.exp(a_cum_col - a_cum_row), 0.0)  # [l, l]

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))   # [l, l]
    y_diag = jax.lax.dot_general((cb * L).astype(x.dtype), x,
                                 (((1,), (0,)), ((), ())))     # [l, P]

    state = state_ref[...]                       # [P, N] fp32
    y_off = jax.lax.dot_general(
        c * jnp.exp(a_cum_col), state,
        (((1,), (1,)), ((), ())))                # [l, P]
    y_ref[...] = (y_diag + y_off).astype(y_ref.dtype).reshape(y_ref.shape)

    decay = jnp.exp(a_sum - a_cum_col)           # [l, 1]
    bx = jax.lax.dot_general(b * decay, x,
                             (((0,), (0,)), ((), ())))          # [N, P]
    state_ref[...] = state * jnp.exp(a_sum) + bx.T

    @pl.when(ci == nc - 1)
    def _done():
        fin_ref[...] = state_ref[...].astype(fin_ref.dtype).reshape(
            fin_ref.shape)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, a, b, c, chunk: int = 128, *, interpret: bool = True):
    """x [B,S,H,P] (pre-multiplied by dt), a [B,S,H] log-decay, b/c [B,S,N]
    -> (y [B,S,H,P], final_state [B,H,P,N])."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    xh = x.transpose(0, 2, 1, 3).reshape(B, H, nc, chunk, P)
    # a unit dim ahead of the chunk axis: the block's last two dims are
    # (1, chunk), both full array dims, as the TPU tiling rule requires
    ah = a.transpose(0, 2, 1).reshape(B, H, nc, 1, chunk)
    bh = b.reshape(B, nc, chunk, N)
    ch = c.reshape(B, nc, chunk, N)

    kernel = functools.partial(_ssd_kernel, nc=nc, chunk=chunk)
    y, fin = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b_, h, ci: (b_, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda b_, h, ci: (b_, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b_, h, ci: (b_, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b_, h, ci: (b_, ci, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b_, h, ci: (b_, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b_, h, ci: (b_, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, chunk, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xh, ah, bh, ch)
    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    return y, fin
