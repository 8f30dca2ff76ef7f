"""Flash attention forward — Pallas TPU kernel.

Grid ``(B*H, num_q_blocks, num_k_blocks)`` with the KV dimension innermost
and *arbitrary* (sequential), so the fp32 (acc, m, l) online-softmax state
lives in VMEM scratch across KV iterations. Blocks are MXU-aligned
(block_q x head_dim and block_k x head_dim, multiples of (8, 128) for fp32 /
(16, 128) for bf16). GQA is handled in the index maps: query head h reads
KV head h // group_size — no KV replication in HBM.

Validated in interpret mode against kernels.ref.attention_ref (see
tests/test_kernels.py); the XLA twin used inside the models is
repro.models.flash.flash_sdpa.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                causal: bool, window: int, sk: int, block_q: int,
                block_k: int, scale: float):
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # [bq, d]
    k = k_ref[0].astype(jnp.float32)                  # [bk, d]
    v = v_ref[0].astype(jnp.float32)                  # [bk, dv]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]

    i = pl.program_id(1)
    q_idx = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_idx = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_idx < sk
    if causal:
        mask &= k_idx <= q_idx
        if window:
            mask &= k_idx > q_idx - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_cur[:, None])
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_cur

    @pl.when(j == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = True):
    """q [B,Sq,H,D]; k/v [B,Sk,K,D] with H % K == 0 -> [B,Sq,H,Dv]."""
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // K
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    # layout: heads major so one program sees one (batch, head) pair
    qh = qp.transpose(0, 2, 1, 3).reshape(B * H, Sq + pq, D)
    kh = kp.transpose(0, 2, 1, 3).reshape(B * K, Sk + pk, D)
    vh = vp.transpose(0, 2, 1, 3).reshape(B * K, Sk + pk, Dv)
    nq = (Sq + pq) // block_q
    nk = (Sk + pk) // block_k

    grid = (B * H, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, window=window, sk=Sk,
        block_q=block_q, block_k=block_k, scale=1.0 / math.sqrt(D))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // G, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, i, j: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq + pq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)
    out = out.reshape(B, H, Sq + pq, Dv)[:, :, :Sq]
    return out.transpose(0, 2, 1, 3)
