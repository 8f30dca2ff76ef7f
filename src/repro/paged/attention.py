"""Paged decode attention: the Pallas kernel over the pool as stored, and
its pure-JAX oracle.

The TPU keeps a pool ``[P, ps, K, D]`` whose ``(K, D)`` would pad its
tiles (GPT-2's 25 x 64 pads 2.56-fold) with the pages as the minor dim, so
one page is one lane of every tile and no per-page DMA exists. The kernel
therefore reads the pool through its ``[ps, K, D, P]`` view, which is that
layout with no copy, one lane tile of 128 pages at a time: each KV head
scores all rows' query heads against every page of the tile on the MXU,
and a per-row count of the tokens it holds in each page
(:func:`page_counts`) masks the rest. Tiles that hold no page a live row
attends to are neither read nor computed. K and V stay in their dtype; the
scores, the online softmax and its accumulators are float32 in VMEM. The
step's tokens are written in place through the same view by
:func:`paged_append`: XLA's scatter would relay the pool out and back.

:func:`paged_attention_decode` takes the kernel where
:func:`decode_kernel_applies` (backend and shapes) and the gather path
:func:`paged_attention_reference` (gather + masked softmax) elsewhere, the
CPU included; that path is also the oracle of the tests.
:func:`paged_attention_reference_multi` serves the multi-query callers.
Masking is positional throughout: logical token ``j*ps + i`` is valid iff
it is ``<= position[b]`` and block ``j`` is allocated (``bt >= 0``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

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
# Pages one kernel block spans: one lane tile of the pool as the TPU keeps it
# (pages minor, see paged_decode_attention).
GROUP = 128
# VMEM the kernel's K and V blocks may take, double-buffered.
BLOCK_VMEM_BYTES = 16 * 2 ** 20


def _heads_per_block(ps: int, K: int, D: int, width: int,
                     itemsize: int) -> int:
    """The most KV heads one block of ``width`` pages holds: a divisor of
    K whose K and V blocks, double-buffered, fit ``BLOCK_VMEM_BYTES``; 0 if
    none does."""
    per_head = 2 * 2 * ps * D * width * itemsize
    return max((kb for kb in range(1, K + 1)
                if K % kb == 0 and kb * per_head <= BLOCK_VMEM_BYTES),
               default=0)


def decode_kernel_applies(pool_shape, dtype) -> bool:
    """Whether :func:`paged_attention_decode` takes the Pallas kernel for a
    pool of ``pool_shape`` ``[P, ps, K, D]`` and ``dtype``: the backend is
    a TPU, no multi-device mesh is active (the kernel is not partitioned
    over heads), and :func:`kernel_reads_in_place`. Everything else, the
    CPU included, takes :func:`paged_attention_reference`."""
    from repro.sharding import ctx as shctx
    mesh = shctx.current_mesh()
    return (jax.default_backend() == "tpu"
            and (mesh is None or mesh.size == 1)
            and kernel_reads_in_place(pool_shape, dtype))


def kernel_reads_in_place(pool_shape, dtype) -> bool:
    """The kernel's shape rule. The TPU keeps a pool whose head dim would
    pad the 128 lanes (``D % 128 != 0``; GPT-2's and OPT's 64) pages-minor,
    so the kernel's ``[ps, K, D, P]`` view of it is no copy, where the pages
    fill whole lane tiles (``P % 128 == 0``); a head dim of whole lane tiles
    keeps the pool row-major, and the view would copy it. The head dim also
    fills whole sublane tiles, and one KV head's block fits
    ``BLOCK_VMEM_BYTES``."""
    P, ps, K, D = pool_shape
    itemsize = jnp.dtype(dtype).itemsize
    return (D % 128 != 0 and D % (8 * max(1, 4 // itemsize)) == 0
            and P % GROUP == 0
            and _heads_per_block(ps, K, D, GROUP, itemsize) > 0)


def page_counts(block_tables, position, num_pages: int, page_size: int):
    """[B, P] int32: how many leading tokens of each pool page row b
    attends to (logical index ``<= position[b]`` on an allocated block),
    0 for a page the row does not hold or a pad row (position -1)."""
    B, nb = block_tables.shape
    blocks = jnp.arange(nb, dtype=jnp.int32)[None, :]
    cnt = jnp.clip(position[:, None] - blocks * page_size + 1, 0, page_size)
    page = jnp.where(block_tables >= 0, block_tables, num_pages)
    return jnp.zeros((B, num_pages), jnp.int32).at[
        jnp.arange(B)[:, None], page].set(cnt, mode="drop")


def _paged_decode_kernel(live_ref, src_ref, q_ref, cnt_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, scale: float):
    gi = pl.program_id(1)
    ps, kb = k_ref.shape[:2]

    @pl.when(gi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live_ref[gi] > 0)
    def _attend():
        cnt = cnt_ref[...]                                   # [R, W]
        # the block's ps tokens side by side on the lanes: [R, ps * W]
        valid = jnp.concatenate([cnt > t for t in range(ps)], axis=1)
        for h in range(kb):
            k = jnp.concatenate([k_ref[t, h] for t in range(ps)], axis=1)
            s = jax.lax.dot_general(
                q_ref[h], k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [R, ps*W]
            s = jnp.where(valid, s, NEG_INF)
            m = m_ref[h]
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            v = jnp.concatenate([v_ref[t, h] for t in range(ps)], axis=1)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [R, D]
            m_ref[h] = m_new
            l_ref[h] = alpha * l_ref[h] + p.sum(axis=1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + pv

    @pl.when(gi == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, block_tables, position, *,
                           interpret: bool = True):
    """q [B,H,D]; pools [P,ps,K,D]; block_tables [B,nb]; position [B] ->
    [B,H,D].

    The TPU keeps a pool whose (K, D) would pad its tiles with the pages
    as the minor dim: the transpose to ``[ps, K, D, P]`` is then its layout
    as stored, and the kernel reads that view in place, in its dtype. One
    block is a lane tile of 128 pages; all rows' query heads of a KV head
    meet all the block's tokens in one MXU product (``[B*G, D] @ [D, ps *
    128]``), masked by :func:`page_counts`, with a float32 online softmax
    carried across blocks. A block that holds no page a row attends to is
    neither read nor computed. A pool of fewer or other than whole tiles of
    pages (the CPU tests') is one block."""
    B, H, D = q.shape
    P, ps, K, _ = k_pool.shape
    G = H // K
    R = B * G
    width = GROUP if P % GROUP == 0 else P
    kb = _heads_per_block(ps, K, D, width, k_pool.dtype.itemsize) or 1
    n_groups = P // width
    # rows r = b * G + g of KV head k
    qk = q.reshape(B, K, G, D).transpose(1, 0, 2, 3).reshape(K, R, D)
    cnt = jnp.repeat(page_counts(jnp.asarray(block_tables, jnp.int32),
                                 jnp.asarray(position, jnp.int32), P, ps),
                     G, axis=0)                              # [R, P]
    live = (cnt.reshape(R, n_groups, width) > 0).any(axis=(0, 2))
    # a dead block re-points at the last live one before it: no new DMA
    idx = jnp.arange(n_groups, dtype=jnp.int32)
    src = jnp.maximum(jax.lax.cummax(jnp.where(live, idx, -1)), 0)
    kv_spec = pl.BlockSpec((ps, kb, D, width),
                           lambda h, g, live, src: (0, h, 0, src[g]))
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(K // kb, n_groups),
            in_specs=[
                pl.BlockSpec((kb, R, D), lambda h, g, live, src: (h, 0, 0)),
                pl.BlockSpec((R, width),
                             lambda h, g, live, src: (0, src[g])),
                kv_spec, kv_spec],
            out_specs=pl.BlockSpec((kb, R, D),
                                   lambda h, g, live, src: (h, 0, 0)),
            scratch_shapes=[pltpu.VMEM((kb, R, 1), jnp.float32),
                            pltpu.VMEM((kb, R, 1), jnp.float32),
                            pltpu.VMEM((kb, R, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((K, R, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=BLOCK_VMEM_BYTES + 8 * 2 ** 20),
        interpret=interpret,
    )(live.astype(jnp.int32), src, qk, cnt,
      k_pool.transpose(1, 2, 3, 0), v_pool.transpose(1, 2, 3, 0))
    return out.reshape(K, B, G, D).transpose(1, 0, 2, 3).reshape(B, H, D)


def _append_kernel(slab_ref, row_slab_ref, row_lane_ref, k_new_ref,
                   v_new_ref, k_in, v_in, k_out, v_out, k_buf, v_buf, sem, *,
                   tiles: int):
    del k_in, v_in                     # aliased to k_out, v_out
    B, width = row_lane_ref.shape[0], k_buf.shape[-1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (B, width), 1)

    def patch(sid):
        slot, tile = sid // tiles, sid % tiles
        start = pl.multiple_of(tile * width, width)
        windows = [(pool.at[slot, :, :, pl.ds(start, width)], buf, new_ref)
                   for pool, buf, new_ref in ((k_out, k_buf, k_new_ref),
                                              (v_out, v_buf, v_new_ref))]
        for i, (win, buf, _) in enumerate(windows):
            pltpu.make_async_copy(win, buf, sem.at[i]).start()
        # the rows that write into this slab, each at its page's lane
        hit = (row_slab_ref[...] == sid) & (row_lane_ref[...] == lanes)
        written = hit.any(axis=0, keepdims=True)             # [1, W]
        for i, (win, buf, new_ref) in enumerate(windows):
            pltpu.make_async_copy(win, buf, sem.at[i]).wait()
            K, D = buf.shape[:2]
            cols = jax.lax.dot_general(
                new_ref[...], hit.astype(new_ref.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [K*D, W]
            old = buf[...].reshape(K * D, width).astype(jnp.float32)
            buf[...] = jnp.where(written, cols, old).astype(
                buf.dtype).reshape(K, D, width)
            pltpu.make_async_copy(buf, win, sem.at[i]).start()
        for i, (win, buf, _) in enumerate(windows):
            pltpu.make_async_copy(buf, win, sem.at[i]).wait()

    def visit(j, _):
        # the rows' slabs come sorted, so each is patched once, at its first
        sid = slab_ref[j]
        prev = slab_ref[jnp.maximum(j - 1, 0)]
        pl.when((sid >= 0) & ((j == 0) | (sid != prev)))(
            functools.partial(patch, sid))

    jax.lax.fori_loop(0, B, visit, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_append(k_pool, v_pool, k_t, v_t, block_tables, position, *,
                 interpret: bool = True):
    """The kernel path's :func:`paged_cache.append_decode`: write one token
    per row at logical index ``position`` (rows with no page there, or at
    position -1, write nothing), in place, through the pool's ``[ps, K, D,
    P]`` view (:func:`paged_decode_attention`). XLA's scatter would relay
    out the whole pages-minor pool and back. Each (slot, 128-page tile)
    slab that rows write is read once into VMEM, the rows' tokens are put
    in their pages' lanes, and the slab is written back. Returns the
    pools."""
    P, ps, K, D = k_pool.shape
    B, nb = block_tables.shape
    width = GROUP if P % GROUP == 0 else P
    pos = jnp.maximum(position, 0).astype(jnp.int32)
    blk = jnp.minimum(pos // ps, nb - 1)
    page = jnp.take_along_axis(block_tables, blk[:, None], axis=1)[:, 0]
    ok = (page >= 0) & (position >= 0)
    row_slab = jnp.where(ok, (pos % ps) * (P // width) + page // width, -1)
    # new values [K*D, B]: row b's token in column b
    new = [x.astype(k_pool.dtype).transpose(1, 2, 0).reshape(K * D, B)
           for x in (k_t, v_t)]
    vmem = pltpu.MemorySpace.VMEM
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    k_view, v_view = (x.transpose(1, 2, 3, 0) for x in (k_pool, v_pool))
    k_view, v_view = pl.pallas_call(
        functools.partial(_append_kernel, tiles=P // width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=vmem)] * 4
            + [any_spec, any_spec],
            out_specs=[any_spec, any_spec],
            scratch_shapes=[pltpu.VMEM((K, D, width), k_pool.dtype),
                            pltpu.VMEM((K, D, width), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(k_view.shape, k_view.dtype),
                   jax.ShapeDtypeStruct(v_view.shape, v_view.dtype)],
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(jnp.sort(row_slab), row_slab[:, None], (page % width)[:, None], *new,
      k_view, v_view)
    return k_view.transpose(3, 0, 1, 2), v_view.transpose(3, 0, 1, 2)


# ---------------------------------------------------------------------------
# Layer-level decode (the paged twin of layers.attention_decode)
# ---------------------------------------------------------------------------
def paged_attention_decode(params, x, position, pool, block_tables, cfg, *,
                           use_kernel: Optional[bool] = None, adapter=None):
    """One-token decode against a paged pool. x [B,1,D]; position [B]
    absolute (== logical index; paged sequences are densely 0-indexed).
    Appends this step's K/V to the pool, attends over the block table.
    The attention is the Pallas kernel where :func:`decode_kernel_applies`
    to the pool, else :func:`paged_attention_reference`; ``use_kernel``
    forces either (tests). ``adapter``: optional LoRA site dict (unmerged
    A·B on the projections). Returns (out [B,1,D], new_pool)."""
    from repro.models import layers as L
    from repro.models.lora import lora_delta
    from repro.paged.paged_cache import append_decode

    B = x.shape[0]
    q, k, v = L._project_qkv(params, x, cfg, adapter=adapter)
    sin, cos = L.rope_tables(position[:, None], cfg.resolved_head_dim(),
                             cfg.rope_theta)
    q = L.apply_rope(q, sin, cos)
    k = L.apply_rope(k, sin, cos)
    if use_kernel is None:
        use_kernel = decode_kernel_applies(pool["k"].shape, pool["k"].dtype)
    if use_kernel:
        from repro.kernels.ops import interpret_kernels
        interpret = interpret_kernels()
        kp, vp = paged_append(pool["k"], pool["v"], k[:, 0], v[:, 0],
                              block_tables, position, interpret=interpret)
        pool = {"k": kp, "v": vp}
        out = paged_decode_attention(q[:, 0], kp, vp, block_tables,
                                     position, interpret=interpret)
    else:
        pool = append_decode(pool, k[:, 0], v[:, 0], block_tables, position)
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
