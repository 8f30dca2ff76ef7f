"""Paged decode attention — block-table-indexed flash decode over the pool.

The Pallas kernel walks each sequence's block table as the innermost
sequential grid axis: program ``(b, k, j)`` attends query heads of KV group
``k`` of sequence ``b`` against page ``bt[b, j]`` of the pool, carrying the
online-softmax ``(acc, m, l)`` across pages in VMEM scratch. The block
table and current positions ride in as scalar prefetch so the page id is
known *before* the block's DMA is issued — the K/V BlockSpec index map
reads ``bt_ref`` directly, which is what makes the gather free: pages are
streamed HBM->VMEM exactly once each, no materialised ``[B, S]`` view.

Masking is positional: logical token ``j*ps + i`` is valid iff it is
``<= position[b]`` and the block is allocated (``bt >= 0``); unallocated
blocks alias page 0 and mask to -inf, so ragged block tables need no host
padding logic. The pure-JAX :func:`paged_attention_reference` (gather +
masked softmax) is the oracle for tests and the CPU fallback.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from repro.paged.paged_cache import gather_kv

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Pure-JAX reference (oracle / CPU path)
# ---------------------------------------------------------------------------
def paged_attention_reference(q, pool, block_tables, position):
    """q [B,H,D]; pool {"k","v": [P,ps,K,D]}; block_tables [B,nb] int32
    (-1 = unallocated); position [B] (last valid logical index) ->
    [B,H,Dv]. fp32 softmax, GQA grouping identical to layers.sdpa."""
    B, H, D = q.shape
    ps = pool["k"].shape[1]
    K = pool["k"].shape[2]
    G = H // K
    k, v = gather_kv(pool, block_tables)                 # [B, nb*ps, K, D]
    S = k.shape[1]
    idx = jnp.arange(S, dtype=jnp.int32)
    allocated = jnp.repeat(block_tables >= 0, ps, axis=1)    # [B, nb*ps]
    valid = allocated & (idx[None, :] <= position[:, None])
    qg = q.reshape(B, K, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg,
                   k.astype(jnp.float32)) / math.sqrt(D)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(B, H, -1).astype(q.dtype)


def paged_attention_reference_multi(q, pool, block_tables, positions):
    """Multi-query twin of :func:`paged_attention_reference` for the
    speculative-decode verify step. q [B,T,H,D]; positions [B,T] (per-query
    last valid logical index; -1 queries see nothing and produce garbage the
    caller masks) -> [B,T,H,Dv]. Token j of a draft run IS context for
    token j+1 because validity is per-query ``idx <= positions[:, j]``."""
    B, T, H, D = q.shape
    ps = pool["k"].shape[1]
    K = pool["k"].shape[2]
    G = H // K
    k, v = gather_kv(pool, block_tables)                 # [B, S, K, D]
    S = k.shape[1]
    idx = jnp.arange(S, dtype=jnp.int32)
    allocated = jnp.repeat(block_tables >= 0, ps, axis=1)        # [B, S]
    valid = allocated[:, None, :] \
        & (idx[None, None, :] <= positions[:, :, None])          # [B, T, S]
    qg = q.reshape(B, T, K, G, D).astype(jnp.float32)
    s = jnp.einsum("btkgd,bskd->btkgs", qg,
                   k.astype(jnp.float32)) / math.sqrt(D)
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("btkgs,bskd->btkgd", p, v.astype(jnp.float32))
    return out.reshape(B, T, H, -1).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------
def _paged_decode_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, page_size: int,
                         scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)                  # [ps, D]
    v = v_ref[0, 0].astype(jnp.float32)                  # [ps, Dv]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [G, ps]

    cur = pos_ref[b]
    idx = j * page_size + jax.lax.broadcasted_iota(jnp.int32, (page_size,), 0)
    valid = (idx <= cur) & (bt_ref[b, j] >= 0)
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_cur[:, None])
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_cur

    @pl.when(j == nb - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, block_tables, position, *,
                           interpret: bool = True):
    """q [B,H,D]; pools [P,ps,K,D]; block_tables [B,nb]; position [B] ->
    [B,H,Dv]. One flash pass per (sequence, kv head) over that sequence's
    pages."""
    B, H, D = q.shape
    P, ps, K, _ = k_pool.shape
    Dv = v_pool.shape[-1]
    G = H // K
    nb = block_tables.shape[1]

    bt = jnp.asarray(block_tables, jnp.int32)
    pos = jnp.asarray(position, jnp.int32)
    qh = q.reshape(B, K, G, D)
    kh = k_pool.transpose(0, 2, 1, 3)            # [P, K, ps, D]
    vh = v_pool.transpose(0, 2, 1, 3)

    def page_of(b, j, bt_ref):
        # -1 (unallocated) aliases page 0; the kernel masks it to -inf
        return jnp.maximum(bt_ref[b, j], 0)

    kernel = functools.partial(_paged_decode_kernel, page_size=ps,
                               scale=1.0 / math.sqrt(D))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # block table, positions
        grid=(B, K, nb),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, k, j, bt, pos:
                         (b, k, 0, 0)),
            pl.BlockSpec((1, 1, ps, D), lambda b, k, j, bt, pos:
                         (page_of(b, j, bt), k, 0, 0)),
            pl.BlockSpec((1, 1, ps, Dv), lambda b, k, j, bt, pos:
                         (page_of(b, j, bt), k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dv), lambda b, k, j, bt, pos:
                               (b, k, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, Dv), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(bt, pos, qh, kh, vh)
    return out.reshape(B, H, Dv)


# ---------------------------------------------------------------------------
# Layer-level decode (the paged twin of layers.attention_decode)
# ---------------------------------------------------------------------------
def paged_attention_decode(params, x, position, pool, block_tables, cfg, *,
                           use_kernel: bool = False, adapter=None):
    """One-token decode against a paged pool. x [B,1,D]; position [B]
    absolute (== logical index; paged sequences are densely 0-indexed).
    Appends this step's K/V to the pool, attends over the block table.
    ``adapter``: optional LoRA site dict (unmerged A·B on the projections).
    Returns (out [B,1,D], new_pool)."""
    from repro.models import layers as L
    from repro.models.lora import lora_delta
    from repro.paged.paged_cache import append_decode

    B = x.shape[0]
    q, k, v = L._project_qkv(params, x, cfg, adapter=adapter)
    sin, cos = L.rope_tables(position[:, None], cfg.resolved_head_dim(),
                             cfg.rope_theta)
    q = L.apply_rope(q, sin, cos)
    k = L.apply_rope(k, sin, cos)
    pool = append_decode(pool, k[:, 0], v[:, 0], block_tables, position)
    if use_kernel:
        from repro.kernels.ops import interpret_kernels
        out = paged_decode_attention(
            q[:, 0], pool["k"], pool["v"], block_tables, position,
            interpret=interpret_kernels())
    else:
        out = paged_attention_reference(q[:, 0], pool, block_tables, position)
    out = out.reshape(B, 1, -1)
    out = out @ params["wo"] + lora_delta(out, (adapter or {}).get("wo"))
    return out, pool


def paged_attention_decode_multi(params, x, positions, pool, block_tables,
                                 cfg, *, adapter=None):
    """T-token decode against a paged pool (speculative-decode verify).
    x [B,T,D]; positions [B,T] logical indices (consecutive per row; -1
    entries are dropped writes and all-masked queries). Appends all T K/V
    first, then attends with per-query position masks. Returns
    (out [B,T,D], new_pool)."""
    from repro.models import layers as L
    from repro.models.lora import lora_delta
    from repro.paged.paged_cache import append_decode_multi

    B, T = x.shape[:2]
    q, k, v = L._project_qkv(params, x, cfg, adapter=adapter)
    sin, cos = L.rope_tables(positions, cfg.resolved_head_dim(),
                             cfg.rope_theta)
    q = L.apply_rope(q, sin, cos)
    k = L.apply_rope(k, sin, cos)
    pool = append_decode_multi(pool, k, v, block_tables, positions)
    out = paged_attention_reference_multi(q, pool, block_tables, positions)
    out = out.reshape(B, T, -1)
    out = out @ params["wo"] + lora_delta(out, (adapter or {}).get("wo"))
    return out, pool
