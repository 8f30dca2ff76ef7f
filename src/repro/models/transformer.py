"""Composable decoder (and encoder-decoder) LM over the layer-kind zoo.

A network is a list of *segments*; each segment is ``n_groups`` repetitions
of a layer *period* (e.g. Jamba's ``(M,M,M,M,A,M,M,M)``), scanned with
``jax.lax.scan`` over stacked parameters so the HLO stays small even for
80-layer models. ``first_k_dense`` (DeepSeek) becomes its own leading
segment. Supports forward (train), prefill (build caches) and one-token
decode, with full / sliding-window attention, MoE FFNs, MLA, Mamba2 and
cross-attention for enc-dec models.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, MAMBA, MLA, ModelConfig
from repro.models import layers as L
from repro.models import lora as LORA
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.sharding import ctx


@dataclass(frozen=True)
class Segment:
    kinds: Tuple[str, ...]
    moe_flags: Tuple[bool, ...]
    n_groups: int


def build_segments(cfg: ModelConfig) -> List[Segment]:
    per = len(cfg.period)
    segs: List[Segment] = []
    k = cfg.first_k_dense
    if k:
        assert k % per == 0, (cfg.name, k, per)
        segs.append(Segment(cfg.period, (False,) * per, k // per))
    rest = cfg.num_layers - k
    assert rest % per == 0, (cfg.name, rest, per)
    if rest:
        segs.append(Segment(cfg.period, cfg.moe_period, rest // per))
    return segs


def _stack_groups(groups: List[Any]):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *groups)


def _model_size() -> int:
    mesh = ctx.current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return mesh.shape["model"]


class Model:
    """cfg-driven LM. ``with_value=True`` adds a scalar value head (critic /
    reward models in the RLHF pipeline share this class)."""

    def __init__(self, cfg: ModelConfig, with_value: bool = False):
        self.cfg = cfg
        self.with_value = with_value
        self.segments = build_segments(cfg)
        self.is_encdec = cfg.input_mode == "encdec"

    # ------------------------------------------------------------------ init
    def _init_slot(self, key, kind: str, is_moe: bool, cross: bool, dtype):
        cfg = self.cfg
        ks = jax.random.split(key, 6)
        slot: Dict[str, Any] = {"norm1": L.init_norm(cfg.d_model, dtype)}
        if kind == ATTN:
            slot["mixer"] = L.init_attention(ks[0], cfg, dtype)
        elif kind == MLA:
            slot["mixer"] = L.init_mla(ks[0], cfg, dtype)
        elif kind == MAMBA:
            slot["mixer"] = M.init_mamba(ks[0], cfg, dtype)
        else:
            raise ValueError(kind)
        if cross:
            slot["norm_x"] = L.init_norm(cfg.d_model, dtype)
            slot["cross"] = L.init_attention(ks[1], cfg, dtype)
        if is_moe and cfg.moe is not None:
            slot["norm2"] = L.init_norm(cfg.d_model, dtype)
            slot["ffn"] = MOE.init_moe(ks[2], cfg, dtype)
        elif cfg.d_ff:
            slot["norm2"] = L.init_norm(cfg.d_model, dtype)
            slot["ffn"] = L.init_mlp(ks[2], cfg.d_model, cfg.d_ff,
                                     cfg.mlp_gated, cfg.num_layers, dtype)
        return slot

    def _init_group(self, key, seg: Segment, cross: bool, dtype):
        ks = jax.random.split(key, len(seg.kinds))
        return {f"slot{i}": self._init_slot(ks[i], kind, seg.moe_flags[i], cross, dtype)
                for i, kind in enumerate(seg.kinds)}

    def init(self, key) -> dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        n_seg = len(self.segments)
        ks = jax.random.split(key, n_seg + 6)
        params: Dict[str, Any] = {
            "embed": L._init(ks[0], (cfg.vocab_size, cfg.d_model), dtype=dtype),
            "final_norm": L.init_norm(cfg.d_model, dtype),
        }
        cross = self.is_encdec
        for si, seg in enumerate(self.segments):
            gks = jax.random.split(ks[1 + si], seg.n_groups)
            groups = [self._init_group(gks[g], seg, cross, dtype)
                      for g in range(seg.n_groups)]
            params[f"segment{si}"] = _stack_groups(groups)
        if not cfg.tie_embeddings:
            params["lm_head"] = L._init(ks[n_seg + 1], (cfg.d_model, cfg.vocab_size),
                                        dtype=dtype)
        if self.with_value:
            params["value_head"] = {
                "w": L._init(ks[n_seg + 2], (cfg.d_model, 1), dtype=jnp.float32),
                "b": jnp.zeros((1,), jnp.float32),
            }
        if cfg.encoder_layers:
            eseg = Segment((ATTN,), (False,), cfg.encoder_layers)
            gks = jax.random.split(ks[n_seg + 3], cfg.encoder_layers)
            groups = [self._init_group(gks[g], eseg, False, dtype)
                      for g in range(cfg.encoder_layers)]
            params["encoder"] = _stack_groups(groups)
            params["encoder_norm"] = L.init_norm(cfg.d_model, dtype)
        if cfg.mtp_depth:
            mseg = self.segments[-1]

            def mtp_module(pk, lk):
                return {
                    "proj": L._init(pk, (2 * cfg.d_model, cfg.d_model),
                                    dtype=dtype),
                    "norm_h": L.init_norm(cfg.d_model, dtype),
                    "norm_e": L.init_norm(cfg.d_model, dtype),
                    "layer": self._init_group(
                        lk, Segment(mseg.kinds[:1], mseg.moe_flags[:1], 1),
                        False, dtype),
                }

            params["mtp"] = mtp_module(ks[n_seg + 4], ks[n_seg + 5])
            if cfg.mtp_depth > 1:
                # depths 2..k stack on a leading axis ("mtp_extra") so the
                # depth-1 tree — and therefore every existing checkpoint —
                # is byte-identical; keys fork off the depth-1 stream
                extras = [
                    mtp_module(
                        jax.random.fold_in(ks[n_seg + 4], 1 + j),
                        jax.random.fold_in(ks[n_seg + 5], 1 + j))
                    for j in range(cfg.mtp_depth - 1)]
                params["mtp_extra"] = _stack_groups(extras)
        return params

    # -------------------------------------------------------- lora adapters
    def init_adapter(self, key, params, rank: int, *,
                     with_value: bool = False) -> dict:
        """Per-role LoRA adapter over ``params`` (see models/lora.py)."""
        return LORA.init_adapter(key, params, rank, with_value=with_value,
                                 d_model=self.cfg.d_model)

    def merge_adapter(self, params, adapter) -> dict:
        """Fold A·B into the base weights (rollout-speed generation). The
        returned tree aliases the base at non-adapted leaves — delete only
        ``lora.merged_leaves(merged, adapter["lora"])`` afterwards."""
        return LORA.merge_adapter(params, (adapter or {}).get("lora"))

    def unmerge_adapter(self, params, adapter) -> dict:
        """Subtract A·B back out of a merged tree (fp round-off applies)."""
        return LORA.unmerge_adapter(params, (adapter or {}).get("lora"))

    # ------------------------------------------------------------ embeddings
    def embed(self, params, tokens):
        return jnp.take(params["embed"], tokens, axis=0)

    def unembed(self, params, h):
        h = L.rms_norm(h, params["final_norm"], self.cfg.norm_eps)
        w = params["embed"].T if self.cfg.tie_embeddings else params["lm_head"]
        logits = h @ w
        if logits.ndim == 3:
            # vocab-parallel when V divides TP; else shard the seq dim
            if logits.shape[-1] % max(_model_size(), 1) == 0:
                logits = ctx.constrain(logits, "dp", None, "model")
            else:
                logits = ctx.constrain(logits, "dp", "model", None)
        return logits

    # -------------------------------------------------------------- full seq
    def _slot_fwd(self, slot, h, positions, kind, has_ffn, is_moe, *,
                  window, cross_kv=None, init_cache=None, adapter=None):
        """One layer. If ``init_cache`` is given (prefill), also fills and
        returns the slot's decode cache in the same pass. ``adapter`` is the
        slot's LoRA subtree (unmerged A·B applied at the matmul sites)."""
        cfg = self.cfg
        cache = {}
        ad = adapter or {}
        # (§Perf hillclimb C, refuted: per-slot Megatron-SP constraints were
        # tried here — GSPMD already picks its schedule and the extra
        # constraints cost +5..23% memory-term on jamba/llama; reverted.
        # The group-boundary seq-parallel constraint in _stack_fwd stays.)
        x = L.rms_norm(h, slot["norm1"], cfg.norm_eps)
        if kind == ATTN:
            y = L.attention_fwd(slot["mixer"], x, positions, cfg,
                                window=window, init_cache=init_cache,
                                adapter=ad.get("mixer"))
            if init_cache is not None:
                y, cache = y
            h = h + y
        elif kind == MLA:
            y = L.mla_fwd(slot["mixer"], x, positions, cfg,
                          window=window, init_cache=init_cache)
            if init_cache is not None:
                y, cache = y
            h = h + y
        elif kind == MAMBA:
            if init_cache is not None:
                y, cache = M.mamba_fwd(slot["mixer"], x, cfg, return_state=True)
            else:
                y = M.mamba_fwd(slot["mixer"], x, cfg)
            h = h + y
        if cross_kv is not None:
            xx = L.rms_norm(h, slot["norm_x"], cfg.norm_eps)
            h = h + L.cross_attention_fwd(slot["cross"], xx, cross_kv, cfg)
        aux = jnp.zeros((), jnp.float32)
        if has_ffn:
            x2 = L.rms_norm(h, slot["norm2"], cfg.norm_eps)
            if is_moe:
                y, aux = MOE.moe_fwd(slot["ffn"], x2, cfg)
            else:
                y = L.mlp_fwd(slot["ffn"], x2, cfg.mlp_gated,
                              adapter=ad.get("ffn"))
            h = h + y
        return h, aux, cache

    def _seg_has_ffn(self, seg: Segment, i: int) -> bool:
        return (seg.moe_flags[i] and self.cfg.moe is not None) or self.cfg.d_ff > 0

    def _stack_fwd(self, params, h, positions, *, window=0, cross_kv=None,
                   init_caches=None, adapter=None, layer_specs=None):
        """Run all segments. Returns (h, aux, filled_caches_per_segment).
        ``adapter`` is a LoRA tree mirroring the segment structure; its
        stacked factors ride the scan alongside the stacked weights.

        ``layer_specs`` (a per-segment list of sliced-layer sharding
        trees, ``sharding.TreePlan.layer_specs``) turns the scan body into
        the per-layer ZeRO-3/FSDP all-gather: each iteration constrains
        only its own sliced layer period to the DP-stripped compute
        layout, so the gathered weights live for ONE layer instead of the
        whole tree (DESIGN.md §3.7). Falls back to the ambient
        ``ctx.segment_param_specs()`` (the per-layer grad reduce-scatter
        hook) when None."""
        cfg = self.cfg
        lora = (adapter or {}).get("lora")
        aux_total = jnp.zeros((), jnp.float32)
        all_caches = []
        seg_specs = layer_specs if layer_specs is not None \
            else ctx.segment_param_specs()
        for si, seg in enumerate(self.segments):
            def group_fwd(carry, xs, seg=seg, si=si):
                hh, aux = carry
                # sequence parallelism at layer boundaries: the remat-saved
                # residual stream shards over ("dp", "model") — 16x smaller
                # checkpoint footprint; XLA all-gathers into the mixers.
                hh = ctx.constrain(hh, "dp", "model", None)
                gp, ckv, ic, ad = xs
                if seg_specs is not None:
                    gp = jax.tree.map(ctx.constrain_spec, gp, seg_specs[si])
                caches = {}
                for i, kind in enumerate(seg.kinds):
                    is_moe = seg.moe_flags[i] and cfg.moe is not None
                    hh, a, c = self._slot_fwd(
                        gp[f"slot{i}"], hh, positions, kind,
                        self._seg_has_ffn(seg, i), is_moe,
                        window=window,
                        cross_kv=None if ckv is None else ckv[i],
                        init_cache=None if ic is None else ic[f"slot{i}"],
                        adapter=None if ad is None else ad.get(f"slot{i}"))
                    caches[f"slot{i}"] = c
                    aux = aux + a
                if cfg.remat == "offload":
                    # name the carried residual so the offload-aware
                    # checkpoint policy can spill it to host (see
                    # repro.offload.policies)
                    from jax.ad_checkpoint import checkpoint_name
                    hh = checkpoint_name(hh, "residual")
                return (hh, aux), caches

            body = group_fwd
            if cfg.remat == "full":
                body = jax.checkpoint(group_fwd)
            elif cfg.remat in ("dots", "offload"):
                from repro.offload.policies import remat_policy_for
                body = jax.checkpoint(group_fwd,
                                      policy=remat_policy_for(cfg.remat))
            xs = (params[f"segment{si}"],
                  cross_kv[si] if cross_kv is not None else None,
                  init_caches[si] if init_caches is not None else None,
                  lora.get(f"segment{si}") if lora else None)
            (h, aux_total), caches = jax.lax.scan(
                body, (h, aux_total), xs)
            all_caches.append(caches)
        return h, aux_total, all_caches

    # ---------------------------------------------------------------- encode
    def encode(self, params, frame_embeds):
        """Bidirectional encoder over precomputed frame embeddings."""
        cfg = self.cfg
        h = frame_embeds
        Se = h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(Se), h.shape[:2])

        def group_fwd(hh, gp):
            hh = ctx.constrain(hh, "dp", "model", None)
            x = L.rms_norm(hh, gp["slot0"]["norm1"], cfg.norm_eps)
            q, k, v = L._project_qkv(gp["slot0"]["mixer"], x, cfg)
            sin, cos = L.rope_tables(positions, cfg.resolved_head_dim(),
                                     cfg.rope_theta)
            q, k = L.apply_rope(q, sin, cos), L.apply_rope(k, sin, cos)
            o = L.attn_core(q, k, v, causal=False).reshape(hh.shape[0], Se, -1)
            hh = hh + o @ gp["slot0"]["mixer"]["wo"]
            x2 = L.rms_norm(hh, gp["slot0"]["norm2"], cfg.norm_eps)
            hh = hh + L.mlp_fwd(gp["slot0"]["ffn"], x2, cfg.mlp_gated)
            return hh, None

        body = jax.checkpoint(group_fwd) if cfg.remat != "none" else group_fwd
        h, _ = jax.lax.scan(body, h, params["encoder"])
        return L.rms_norm(h, params["encoder_norm"], cfg.norm_eps)

    def _cross_kvs(self, params, enc_out):
        """Per-decoder-layer cross K/V, stacked per segment.
        Sharded (batch over dp, kv heads — or head_dim — over model)."""
        def con(x):  # [G, B, Se, K, hd]
            kh = "model" if x.shape[3] % _model_size() == 0 else None
            hd = None if kh else "model"
            return ctx.constrain(x, None, "dp", None, kh, hd)
        out = []
        for si, seg in enumerate(self.segments):
            def per_group(gp):
                return tuple(
                    L.encode_cross_kv(gp[f"slot{i}"]["cross"], enc_out, self.cfg)
                    for i in range(len(seg.kinds)))
            kvs = jax.vmap(per_group, in_axes=0)(params[f"segment{si}"])
            out.append(jax.tree.map(con, kvs))
        return out

    # --------------------------------------------------------------- forward
    def _prepare_inputs(self, params, batch):
        cfg = self.cfg
        cross_kv = None
        if cfg.input_mode == "tokens":
            h = self.embed(params, batch["tokens"])
        elif cfg.input_mode == "embeddings":
            tok = self.embed(params, batch["tokens"])
            h = jnp.concatenate([batch["prefix_embeds"].astype(tok.dtype), tok], 1)
        elif cfg.input_mode == "encdec":
            h = self.embed(params, batch["tokens"])
            enc_out = self.encode(params, batch["frame_embeds"].astype(h.dtype))
            cross_kv = self._cross_kvs(params, enc_out)
        else:
            raise ValueError(cfg.input_mode)
        S = h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), h.shape[:2])
        return h, positions, cross_kv

    def forward(self, params, batch, *, window: int = 0, adapter=None,
                layer_specs=None):
        """Full-sequence forward -> (logits [B,S,V], aux_loss, h_final).
        ``adapter`` (optional LoRA tree) is applied unmerged;
        ``layer_specs`` enables the per-layer ZeRO-3 gather in the scan
        body (see ``_stack_fwd``)."""
        h, positions, cross_kv = self._prepare_inputs(params, batch)
        # cross_kv from _cross_kvs is already per-segment stacked; pass as xs
        h, aux, _ = self._stack_fwd(params, h, positions, window=window,
                                    cross_kv=cross_kv, adapter=adapter,
                                    layer_specs=layer_specs)
        return self.unembed(params, h), aux, h

    def forward_value(self, params, batch, adapter=None, layer_specs=None):
        """[B,S] per-token scalar values (critic / reward). With an
        ``adapter`` carrying a value head, the head comes from the adapter —
        the hydra engine's critic/reward share a headless base trunk."""
        h, positions, cross_kv = self._prepare_inputs(params, batch)
        h, _, _ = self._stack_fwd(params, h, positions, cross_kv=cross_kv,
                                  adapter=adapter, layer_specs=layer_specs)
        h = L.rms_norm(h, params["final_norm"], self.cfg.norm_eps)
        vh = (adapter or {}).get("value_head") or params["value_head"]
        return (h.astype(jnp.float32) @ vh["w"] + vh["b"])[..., 0]

    def _mtp_modules(self, params) -> list:
        """Depth-ordered MTP modules: ``params["mtp"]`` is depth 1; extras
        (depths 2..k) are unstacked off ``params["mtp_extra"]``'s lead axis."""
        modules = [params["mtp"]]
        extra = params.get("mtp_extra")
        if extra is not None:
            n = jax.tree.leaves(extra)[0].shape[0]
            modules += [jax.tree.map(lambda x, j=j: x[j], extra)
                        for j in range(n)]
        return modules

    def _mtp_module_fwd(self, module, h_prev, e_next, positions, *, window=0):
        """One MTP module: combine h^{d-1} with emb(t_{i+d}) and run the
        module's transformer layer. Returns h^d (pre-final-norm)."""
        cfg = self.cfg
        h_in = jnp.concatenate([
            L.rms_norm(h_prev, module["norm_h"], cfg.norm_eps),
            L.rms_norm(e_next, module["norm_e"], cfg.norm_eps)], -1)
        hh = h_in @ module["proj"]
        seg = self.segments[-1]
        kind = seg.kinds[0]
        is_moe = seg.moe_flags[0] and cfg.moe is not None
        hh, _, _ = self._slot_fwd(module["layer"]["slot0"], hh, positions,
                                  kind, self._seg_has_ffn(seg, 0), is_moe,
                                  window=window)
        return hh

    def mtp_logits(self, params, h, tokens):
        """DeepSeek multi-token prediction: predict t_{i+2} from h_i and
        emb(t_{i+1}). Runs on the full (shifted, end-padded) sequence so the
        token grid keeps tiling the mesh (the MoE shard_map path applies);
        returns logits [B, S, V] where index i scores tokens[:, i+2]
        (the last two positions are padding — mask them in the loss)."""
        shifted = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        S = h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), h.shape[:2])
        hh = self._mtp_module_fwd(params["mtp"], h,
                                  self.embed(params, shifted), positions)
        return self.unembed(params, hh)

    def mtp_chain_logits(self, params, h, tokens, *, window: int = 0):
        """Depth-k chained MTP (arXiv:2412.19437): module d consumes
        h^{d-1} and emb(tokens shifted by d) and predicts t_{i+d+1}.
        Returns a list of logits [B, S, V], one per depth — entry d-1's
        index i scores tokens[:, i+d+1] (``steps.mtp_loss(offset=d+1)``).

        Depth 1 is bit-identical to :meth:`mtp_logits`. ``window=1`` trains
        the chain under the identity attention mask (each position sees
        only itself), which is exactly the function :meth:`mtp_draft`
        evaluates at decode time — use it to train draft-consistent heads."""
        S = h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), h.shape[:2])
        out = []
        h_prev = h
        for d, module in enumerate(self._mtp_modules(params), start=1):
            shifted = jnp.pad(tokens[:, d:], ((0, 0), (0, d)))
            h_prev = self._mtp_module_fwd(module, h_prev,
                                          self.embed(params, shifted),
                                          positions, window=window)
            out.append(self.unembed(params, h_prev))
        return out

    def mtp_draft(self, params, h_last, first_tok, k_draft: int):
        """Draft ``k_draft`` greedy tokens from the MTP chain in one shot.

        ``h_last`` [B, D] is the trunk hidden state at position i (the one
        whose logits produced ``first_tok`` = t_{i+1}); the chain then
        predicts t_{i+2}, t_{i+3}, ... Each module runs at a single
        position, where attention degenerates to v(x) — position- and
        RoPE-independent, equal to the ``window=1`` train-time chain — so
        drafts are a deterministic function of (h_last, first_tok). Depths
        beyond the trained ``mtp_depth`` reuse the deepest module. Draft
        quality only moves the accept rate; verification guarantees
        greedy-exact output regardless. Returns drafts [B, k_draft] int32."""
        modules = self._mtp_modules(params)
        h = h_last[:, None]                               # [B, 1, D]
        tok = first_tok
        positions = jnp.zeros(h.shape[:2], jnp.int32)
        drafts = []
        for d in range(k_draft):
            module = modules[min(d, len(modules) - 1)]
            e = self.embed(params, tok[:, None])
            h = self._mtp_module_fwd(module, h, e, positions)
            lg = self.unembed(params, h)[:, 0].astype(jnp.float32)
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
            drafts.append(tok)
        return jnp.stack(drafts, axis=1)

    # ------------------------------------------------------------- kv caches
    def init_cache(self, batch: int, capacity: int, dtype) -> list:
        """Per-segment stacked decode caches."""
        cfg = self.cfg
        caches = []
        for seg in self.segments:
            slot_caches = {}
            for i, kind in enumerate(seg.kinds):
                if kind == ATTN:
                    c = L.init_kv_cache(cfg, batch, capacity, dtype)
                elif kind == MLA:
                    c = L.init_mla_cache(cfg, batch, capacity, dtype)
                else:
                    c = M.init_mamba_cache(cfg, batch, dtype)
                slot_caches[f"slot{i}"] = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (seg.n_groups,) + x.shape), c)
            caches.append(slot_caches)
        return caches

    def prefill(self, params, batch, capacity: int, *, window: int = 0,
                adapter=None, lengths=None, return_h: bool = False):
        """Process a prompt, returning (last-position logits [B,V], caches).

        caches = {"segments": [...], "cross_kv": [...]|None}. Attention /
        MLA caches hold the last ``min(S, capacity)`` positions of a rolling
        buffer; Mamba slots hold (conv_state, ssm_state). Single pass.

        ``lengths`` [B] (optional) marks per-row valid-token counts under
        right-padding (the compile-bucket ladder pads prompts to a capture
        length): logits come from position ``lengths-1`` and padded cache
        entries are invalidated post-hoc (their "pos" set to -1) — exact
        because causal attention makes right-padding invisible to earlier
        positions. Token-input attention/MLA models only (Mamba states
        cannot be masked after the fact). ``return_h=True`` additionally
        returns the pre-final-norm trunk hidden at the logits position
        [B, D] — the state the MTP draft head chains from."""
        h, positions, cross_kv = self._prepare_inputs(params, batch)
        B = h.shape[0]
        init_caches = self.init_cache(B, capacity, h.dtype)
        h_out, aux, filled = self._stack_fwd(
            params, h, positions, window=window, cross_kv=cross_kv,
            init_caches=init_caches, adapter=adapter)
        if lengths is None:
            h_last = h_out[:, -1]
        else:
            assert self.cfg.input_mode == "tokens", \
                "bucketed (lengths-masked) prefill needs token inputs"
            assert all(k in (ATTN, MLA) for seg in self.segments
                       for k in seg.kinds), \
                "bucketed prefill cannot mask Mamba states post-hoc"
            idx = jnp.maximum(lengths - 1, 0).astype(jnp.int32)
            h_last = jnp.take_along_axis(
                h_out, jnp.broadcast_to(idx[:, None, None],
                                        (B, 1, h_out.shape[-1])), 1)[:, 0]
            lens = lengths[None, :, None]          # vs pos leaves [G, B, cap]
            filled = [
                {k: dict(c, pos=jnp.where(c["pos"] < lens, c["pos"], -1))
                 for k, c in seg.items()}
                for seg in filled]
        logits = self.unembed(params, h_last[:, None])[:, 0]
        caches = {"segments": filled, "cross_kv": cross_kv}
        if return_h:
            return logits, caches, h_last
        return logits, caches

    # ------------------------------------------------------- paged kv caches
    def supports_paged(self) -> bool:
        """The paged backend covers token-input, attention-only nets (the
        serving/RLHF configs). MLA/Mamba states are not paged (yet)."""
        return (self.cfg.input_mode == "tokens"
                and all(k == ATTN for seg in self.segments for k in seg.kinds))

    def init_paged_pools(self, num_pages: int, page_size: int, dtype) -> list:
        """Per-segment stacked paged KV pools ([n_groups, P, ps, kvh, hd]
        per attention slot). The block table is shared across layers; each
        layer owns its physical pool."""
        from repro import paged as PG
        assert self.supports_paged(), \
            f"paged cache needs attention-only token models, got {self.cfg.name}"
        pools = []
        for seg in self.segments:
            slot_pools = {}
            for i in range(len(seg.kinds)):
                c = PG.init_pool(self.cfg, num_pages, page_size, dtype)
                slot_pools[f"slot{i}"] = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (seg.n_groups,) + x.shape), c)
            pools.append(slot_pools)
        return pools

    def paged_prefill(self, params, batch, pools, block_tables, lengths, *,
                      adapter=None, return_h: bool = False):
        """Prefill into paged pools: dense single-pass prompt compute, then
        the per-layer K/V scattered to the sequences' pages (gather/scatter
        prefill). batch["tokens"] [B, S]; block_tables [B, nb] int32;
        lengths [B] valid-token counts — logits come from position
        ``lengths-1``, so bucket-padded prompts are exact. Returns
        (last-valid-position logits [B, V], pools[, h_last])."""
        from repro import paged as PG
        S = batch["tokens"].shape[1]
        logits, caches, h_last = self.prefill(params, batch, S,
                                              adapter=adapter,
                                              lengths=lengths, return_h=True)
        new_pools = []
        for si, seg in enumerate(self.segments):
            slot_pools = {}
            for i in range(len(seg.kinds)):
                filled = caches["segments"][si][f"slot{i}"]   # k/v [G,B,S,..]
                scatter = jax.vmap(PG.scatter_prefill,
                                   in_axes=(0, 0, 0, None, None))
                slot_pools[f"slot{i}"] = scatter(
                    pools[si][f"slot{i}"], filled["k"], filled["v"],
                    block_tables, lengths)
            new_pools.append(slot_pools)
        if return_h:
            return logits, new_pools, h_last
        return logits, new_pools

    def paged_prefill_suffix(self, params, batch, pools, block_tables,
                             start, lengths, *, adapter=None,
                             return_h: bool = False):
        """Prefill only the *suffix* of a prompt whose prefix KV already
        sits in the pool (cross-request prefix cache hit). batch["tokens"]
        [B, Sb] holds ``prompt[start:]`` right-padded to the suffix bucket;
        ``start`` [B] is the per-row count of cached prefix tokens and
        ``lengths`` [B] the full prompt length. Suffix K/V is appended to
        the pool first and attention gathers from the pool (the
        :meth:`paged_decode_multi` layout), so a query at position p sees
        cached prefix entries (idx < start) and earlier suffix entries
        through one and the same mask — a cold run (start = 0) and a warm
        run compute the identical per-position function. Returns
        (last-prompt-position logits [B, V], pools[, h_last])."""
        tokens = batch["tokens"]
        B, Sb = tokens.shape
        j = jnp.arange(Sb, dtype=jnp.int32)[None, :]
        pos = start[:, None].astype(jnp.int32) + j
        positions = jnp.where(pos < lengths[:, None], pos, -1)
        logits_all, h_all, new_pools = self.paged_decode_multi(
            params, pools, tokens, positions, block_tables, adapter=adapter)
        idx = jnp.clip(lengths - start - 1, 0, Sb - 1).astype(jnp.int32)
        logits = jnp.take_along_axis(
            logits_all, jnp.broadcast_to(idx[:, None, None],
                                         (B, 1, logits_all.shape[-1])),
            1)[:, 0]
        if return_h:
            h_last = jnp.take_along_axis(
                h_all, jnp.broadcast_to(idx[:, None, None],
                                        (B, 1, h_all.shape[-1])), 1)[:, 0]
            return logits, new_pools, h_last
        return logits, new_pools

    def paged_decode_step(self, params, pools, token, position, block_tables,
                          *, use_kernel: Optional[bool] = None,
                          adapter=None):
        """One-token decode over paged pools. token/position [B] (position
        is the logical index being written); block_tables [B, nb].
        ``use_kernel`` forces the attention path (None: chosen by backend
        and pool shape, ``paged.attention.decode_kernel_applies``).
        Returns (logits [B, V], pools)."""
        from repro.paged.attention import paged_attention_decode
        cfg = self.cfg
        lora = (adapter or {}).get("lora")
        h = self.embed(params, token[:, None])
        new_pools = []
        for si, seg in enumerate(self.segments):
            def group_dec(hh, xs, seg=seg):
                gp, pool, ad = xs
                new_pool = {}
                for i in range(len(seg.kinds)):
                    slot = gp[f"slot{i}"]
                    sad = (ad or {}).get(f"slot{i}") or {}
                    x = L.rms_norm(hh, slot["norm1"], cfg.norm_eps)
                    y, np_ = paged_attention_decode(
                        slot["mixer"], x, position, pool[f"slot{i}"],
                        block_tables, cfg, use_kernel=use_kernel,
                        adapter=sad.get("mixer"))
                    hh = hh + y
                    new_pool[f"slot{i}"] = np_
                    if self._seg_has_ffn(seg, i):
                        x2 = L.rms_norm(hh, slot["norm2"], cfg.norm_eps)
                        is_moe = seg.moe_flags[i] and cfg.moe is not None
                        if is_moe:
                            y2, _ = MOE.moe_fwd(slot["ffn"], x2, cfg)
                        else:
                            y2 = L.mlp_fwd(slot["ffn"], x2, cfg.mlp_gated,
                                           adapter=sad.get("ffn"))
                        hh = hh + y2
                return hh, new_pool

            xs = (params[f"segment{si}"], pools[si],
                  lora.get(f"segment{si}") if lora else None)
            h, seg_pool = jax.lax.scan(group_dec, h, xs)
            new_pools.append(seg_pool)
        logits = self.unembed(params, h)[:, 0]
        return logits, new_pools

    def decode_step(self, params, caches, token, position, *, window: int = 0,
                    adapter=None):
        """token [B] int32, position [B] int32 -> (logits [B,V], caches)."""
        cfg = self.cfg
        lora = (adapter or {}).get("lora")
        h = self.embed(params, token[:, None])
        cross_kv = caches.get("cross_kv")
        new_segments = []
        for si, seg in enumerate(self.segments):
            def group_dec(hh, xs, seg=seg):
                gp, cache, ckv, ad = xs
                new_cache = {}
                for i, kind in enumerate(seg.kinds):
                    slot = gp[f"slot{i}"]
                    sad = (ad or {}).get(f"slot{i}") or {}
                    x = L.rms_norm(hh, slot["norm1"], cfg.norm_eps)
                    if kind == ATTN:
                        y, nc = L.attention_decode(slot["mixer"], x, position,
                                                   cache[f"slot{i}"], cfg,
                                                   window=window,
                                                   adapter=sad.get("mixer"))
                    elif kind == MLA:
                        y, nc = L.mla_decode(slot["mixer"], x, position,
                                             cache[f"slot{i}"], cfg,
                                             window=window)
                    else:
                        y, nc = M.mamba_decode(slot["mixer"], x,
                                               cache[f"slot{i}"], cfg)
                    hh = hh + y
                    new_cache[f"slot{i}"] = nc
                    if ckv is not None:
                        xx = L.rms_norm(hh, slot["norm_x"], cfg.norm_eps)
                        hh = hh + L.cross_attention_fwd(slot["cross"], xx,
                                                        ckv[i], cfg)
                    if self._seg_has_ffn(seg, i):
                        x2 = L.rms_norm(hh, slot["norm2"], cfg.norm_eps)
                        is_moe = seg.moe_flags[i] and cfg.moe is not None
                        if is_moe:
                            y2, _ = MOE.moe_fwd(slot["ffn"], x2, cfg)
                        else:
                            y2 = L.mlp_fwd(slot["ffn"], x2, cfg.mlp_gated,
                                           adapter=sad.get("ffn"))
                        hh = hh + y2
                return hh, new_cache

            xs = (params[f"segment{si}"], caches["segments"][si],
                  cross_kv[si] if cross_kv is not None else None,
                  lora.get(f"segment{si}") if lora else None)
            h, seg_cache = jax.lax.scan(group_dec, h, xs)
            new_segments.append(seg_cache)
        logits = self.unembed(params, h)[:, 0]
        new_caches = dict(caches)
        new_caches["segments"] = new_segments
        return logits, new_caches

    # ------------------------------------------------- speculative decoding
    def supports_spec_decode(self) -> bool:
        """The draft/verify path covers token-input attention-only nets
        (rolling-pos dense caches and paged pools both self-heal rejected
        drafts by position masking; Mamba/MLA states cannot roll back)."""
        return (self.cfg.input_mode == "tokens" and self.cfg.mtp_depth > 0
                and all(k == ATTN for seg in self.segments
                        for k in seg.kinds))

    def decode_multi(self, params, caches, tokens, positions, *,
                     window: int = 0, adapter=None):
        """T-token verify forward over the dense rolling cache. tokens
        [B, T] int32, positions [B, T] absolute (consecutive per row; a -1
        row writes only dead entries). Returns (logits [B, T, V],
        h [B, T, D] pre-final-norm trunk states, caches) — logits[:, j]
        scores the token at position ``positions[:, j] + 1``. Rejected-draft
        cache entries need no rollback: their stored positions exceed any
        later query position, so the mask hides them until the rolling
        buffer overwrites them (attention-only models)."""
        cfg = self.cfg
        assert all(k == ATTN for seg in self.segments for k in seg.kinds), \
            "decode_multi needs attention-only models"
        lora = (adapter or {}).get("lora")
        h = self.embed(params, tokens)
        new_segments = []
        for si, seg in enumerate(self.segments):
            def group_dec(hh, xs, seg=seg):
                gp, cache, ad = xs
                new_cache = {}
                for i in range(len(seg.kinds)):
                    slot = gp[f"slot{i}"]
                    sad = (ad or {}).get(f"slot{i}") or {}
                    x = L.rms_norm(hh, slot["norm1"], cfg.norm_eps)
                    y, nc = L.attention_decode_multi(
                        slot["mixer"], x, positions, cache[f"slot{i}"], cfg,
                        window=window, adapter=sad.get("mixer"))
                    hh = hh + y
                    new_cache[f"slot{i}"] = nc
                    if self._seg_has_ffn(seg, i):
                        x2 = L.rms_norm(hh, slot["norm2"], cfg.norm_eps)
                        is_moe = seg.moe_flags[i] and cfg.moe is not None
                        if is_moe:
                            y2, _ = MOE.moe_fwd(slot["ffn"], x2, cfg)
                        else:
                            y2 = L.mlp_fwd(slot["ffn"], x2, cfg.mlp_gated,
                                           adapter=sad.get("ffn"))
                        hh = hh + y2
                return hh, new_cache

            xs = (params[f"segment{si}"], caches["segments"][si],
                  lora.get(f"segment{si}") if lora else None)
            h, seg_cache = jax.lax.scan(group_dec, h, xs)
            new_segments.append(seg_cache)
        logits = self.unembed(params, h)
        new_caches = dict(caches)
        new_caches["segments"] = new_segments
        return logits, h, new_caches

    def paged_decode_multi(self, params, pools, tokens, positions,
                           block_tables, *, adapter=None):
        """T-token verify forward over paged pools (the paged twin of
        :meth:`decode_multi`). tokens/positions [B, T]; position -1 entries
        are dropped writes (idle or finished rows). The page manager must
        have grown each live row by T logical tokens first
        (``PageManager.append_tokens``); after acceptance the caller
        truncates back (``PageManager.truncate``). Returns (logits
        [B, T, V], h [B, T, D], pools)."""
        from repro.paged.attention import paged_attention_decode_multi
        cfg = self.cfg
        lora = (adapter or {}).get("lora")
        h = self.embed(params, tokens)
        new_pools = []
        for si, seg in enumerate(self.segments):
            def group_dec(hh, xs, seg=seg):
                gp, pool, ad = xs
                new_pool = {}
                for i in range(len(seg.kinds)):
                    slot = gp[f"slot{i}"]
                    sad = (ad or {}).get(f"slot{i}") or {}
                    x = L.rms_norm(hh, slot["norm1"], cfg.norm_eps)
                    y, np_ = paged_attention_decode_multi(
                        slot["mixer"], x, positions, pool[f"slot{i}"],
                        block_tables, cfg, adapter=sad.get("mixer"))
                    hh = hh + y
                    new_pool[f"slot{i}"] = np_
                    if self._seg_has_ffn(seg, i):
                        x2 = L.rms_norm(hh, slot["norm2"], cfg.norm_eps)
                        is_moe = seg.moe_flags[i] and cfg.moe is not None
                        if is_moe:
                            y2, _ = MOE.moe_fwd(slot["ffn"], x2, cfg)
                        else:
                            y2 = L.mlp_fwd(slot["ffn"], x2, cfg.mlp_gated,
                                           adapter=sad.get("ffn"))
                        hh = hh + y2
                return hh, new_pool

            xs = (params[f"segment{si}"], pools[si],
                  lora.get(f"segment{si}") if lora else None)
            h, seg_pool = jax.lax.scan(group_dec, h, xs)
            new_pools.append(seg_pool)
        logits = self.unembed(params, h)
        return logits, h, new_pools
