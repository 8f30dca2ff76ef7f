"""Flash-decode — Pallas TPU kernel for single-token attention over a
(rolling) KV cache.

One program per (batch, kv-head); the cache-length dimension is the
innermost sequential grid axis, with fp32 (acc, m, l) scratch carrying the
online softmax across cache blocks. Masking is data-driven: the cache's
per-slot absolute positions (``pos``, -1 = empty) are streamed alongside
K/V as ``[1, block_c]`` lane rows, so rolling-buffer wraparound and sliding
windows need no index arithmetic in the host code. The query position is a
scalar-prefetched SMEM operand. All G query heads of a KV group are
processed together ([G, D] x [D, block_c] on the MXU).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(cur_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, window: int, scale: float):
    j = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # [G, D]
    k = k_ref[0].astype(jnp.float32)                    # [bc, D]
    v = v_ref[0].astype(jnp.float32)                    # [bc, Dv]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [G, bc]

    pos = pos_ref[0]                                    # [1, bc] int32
    cur = cur_ref[pl.program_id(0)]
    valid = (pos >= 0) & (pos <= cur)
    if window:
        valid &= pos > (cur - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_cur[:, None])
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_cur

    @pl.when(j == nc - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_c",
                                             "interpret"))
def decode_attention(q, k_cache, v_cache, pos, position, *, window: int = 0,
                     block_c: int = 512, interpret: bool = True):
    """q [B,H,D]; caches [B,C,K,Dv]; pos [B,C] int32; position [B] ->
    [B,H,Dv]."""
    B, H, D = q.shape
    C, K = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = H // K
    block_c = min(block_c, max(C, 8))
    pc = (-C) % block_c
    kp = jnp.pad(k_cache, ((0, 0), (0, pc), (0, 0), (0, 0)))
    vp = jnp.pad(v_cache, ((0, 0), (0, pc), (0, 0), (0, 0)))
    posp = jnp.pad(pos, ((0, 0), (0, pc)), constant_values=-1)
    qh = q.reshape(B * K, G, D)
    kh = kp.transpose(0, 2, 1, 3).reshape(B * K, C + pc, D)
    vh = vp.transpose(0, 2, 1, 3).reshape(B * K, C + pc, Dv)
    # [B*K, 1, C+pc]: each block's last two dims are (1, block_c), the
    # full unit dim and a lane-aligned (or full) cache block
    posh = jnp.repeat(posp, K, axis=0)[:, None, :]
    curh = jnp.repeat(position.astype(jnp.int32), K)    # [B*K] -> SMEM
    nc = (C + pc) // block_c

    kernel = functools.partial(_decode_kernel, window=window,
                               scale=1.0 / math.sqrt(D))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                   # per-row query position
        grid=(B * K, nc),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda b, j, cur: (b, 0, 0)),
            pl.BlockSpec((1, block_c, D), lambda b, j, cur: (b, j, 0)),
            pl.BlockSpec((1, block_c, Dv), lambda b, j, cur: (b, j, 0)),
            pl.BlockSpec((1, 1, block_c), lambda b, j, cur: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, G, Dv), lambda b, j, cur: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, Dv), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * K, G, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(curh, qh, kh, vh, posh)
    return out.reshape(B, H, Dv)
