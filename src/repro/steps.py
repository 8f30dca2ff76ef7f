"""Step functions — the units the launcher jits / lowers, and the phases of
the RLHF pipeline (DESIGN.md §5):

  * ``train_step``    — PPO actor update (clipped ratio vs old_logp, KL vs
                        ref_logp) + optional MTP CE + MoE aux loss.
  * ``critic_step``   — clipped value-function regression.
  * ``lm_step``       — plain CE (SFT / reward-model pretext, examples).
  * ``prefill_step``  — rollout prompt processing, builds decode caches.
  * ``decode_step``   — one rollout token (full or sliding-window).

``input_specs`` produces ShapeDtypeStruct stand-ins for every (arch x input
shape) pair — the dry-run lowers against these, no allocation.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import Model
from repro.optim import clip_by_global_norm, make_optimizer
from repro.sharding import ctx


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------
def _full_seq_logp(logits, targets):
    """Per-position log-prob of ``targets`` [B, T] under logits [B, T, V].
    Full-length (no slicing before the reduction) so the seq dim keeps its
    sharding; never materializes fp32 [B,T,V] — the fp32 exp fuses into the
    reduce. This keeps the training-phase memory roofline honest."""
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    mx = jax.lax.stop_gradient(logits.max(-1))
    lse = mx.astype(jnp.float32) + jnp.log(jnp.sum(
        jnp.exp(logits.astype(jnp.float32) - mx[..., None].astype(jnp.float32)),
        axis=-1))
    return tgt.astype(jnp.float32) - lse                   # [B, T]


def _action_logp(logits, tokens, prefix: int):
    """logits [B, P+S, V]; tokens [B, S]. Returns per-action log-probs
    aligned so out[:, t] scores tokens[:, t] (t >= 1); out[:, 0] = 0."""
    B, S = tokens.shape
    T = logits.shape[1]
    # full-length target map: position j scores tokens[:, j - prefix + 1]
    tgt_full = jnp.zeros((B, T), tokens.dtype)
    tgt_full = jax.lax.dynamic_update_slice(
        tgt_full, tokens[:, 1:], (0, prefix))
    logp_full = _full_seq_logp(logits, tgt_full)           # [B, T]
    act = jax.lax.dynamic_slice(logp_full, (0, prefix), (B, S - 1))
    return jnp.pad(act, ((0, 0), (1, 0)))                  # [B, S]


def ppo_actor_loss(logits, batch, *, prefix: int = 0, clip_eps: float = 0.2,
                   kl_coef: float = 0.1, entropy_coef: float = 0.0):
    tokens = batch["tokens"]
    mask = batch["loss_mask"].astype(jnp.float32)
    mask = mask.at[:, 0].set(0.0)
    denom = jnp.maximum(mask.sum(), 1.0)
    logp = _action_logp(logits, tokens, prefix)
    ratio = jnp.exp(logp - batch["old_logp"])
    adv = batch["advantages"]
    unclipped = ratio * adv
    clipped = jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * adv
    ppo = -jnp.sum(jnp.minimum(unclipped, clipped) * mask) / denom
    # k3 KL estimator vs the frozen reference policy
    log_r = batch["ref_logp"] - logp
    kl = jnp.sum((jnp.exp(log_r) - 1.0 - log_r) * mask) / denom
    loss = ppo + kl_coef * kl
    metrics = {"ppo_loss": ppo, "kl": kl,
               "clip_frac": jnp.sum((jnp.abs(ratio - 1) > clip_eps) * mask) / denom}
    return loss, metrics


def critic_loss(values, batch, *, clip_eps: float = 0.2):
    mask = batch["loss_mask"].astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    returns = batch["returns"]
    old_v = batch.get("old_values", returns)
    v_clip = old_v + jnp.clip(values - old_v, -clip_eps, clip_eps)
    l = jnp.maximum(jnp.square(values - returns), jnp.square(v_clip - returns))
    loss = 0.5 * jnp.sum(l * mask) / denom
    return loss, {"vf_loss": loss}


def mtp_loss(logits, tokens, mask, *, offset: int = 2):
    """MTP CE: logits[:, i] scores tokens[:, i+offset] (full-length logits,
    the last ``offset`` positions are padding). Depth-d logits of the
    chained head use ``offset = d + 1``; the default 2 is depth 1."""
    S = tokens.shape[1]
    tgt_full = jnp.pad(tokens[:, offset:], ((0, 0), (0, offset)))
    nll = -_full_seq_logp(logits, tgt_full)[:, :S - offset]
    m = mask[:, offset:].astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(m.sum(), 1.0)


def mtp_chain_loss(model, params, h, batch):
    """Mean CE over the depth-k MTP chain (depth 1 reproduces the old
    single-module loss bit-for-bit). ``params`` may be a base tree (hydra)
    — the chain always runs adapter-free, like the trunk aux loss."""
    lgs = model.mtp_chain_logits(params, h, batch["tokens"])
    losses = [mtp_loss(lg, batch["tokens"], batch["loss_mask"], offset=d + 1)
              for d, lg in enumerate(lgs, start=1)]
    total = losses[0]
    for extra in losses[1:]:
        total = total + extra
    return total / len(losses)


def lm_loss(logits, tokens, mask, *, prefix: int = 0):
    nll = -_action_logp(logits, tokens, prefix)[:, 1:]
    m = mask[:, 1:].astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(m.sum(), 1.0)


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------
def _prefix_len(cfg: ModelConfig) -> int:
    return cfg.num_prefix_embeddings if cfg.input_mode == "embeddings" else 0


def _accumulated_grads(loss_fn, params, batch, N: int, acc_dtype):
    """``value_and_grad(loss_fn)(params, batch)`` with N-way microbatch
    gradient accumulation under ``lax.scan`` (N == 1 is the plain call).
    ``loss_fn`` has signature ``(params, batch) -> (loss, metrics)``.
    Returns ``((loss, metrics), grads)`` averaged over microbatches."""
    if N == 1:
        return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
    mbs = jax.tree.map(
        lambda x: x.reshape((N, x.shape[0] // N) + x.shape[1:]), batch)

    def body(carry, mb):
        gacc, lacc, macc = carry
        (l, met), g = jax.value_and_grad(
            loss_fn, has_aux=True)(params, mb)
        gacc = jax.tree.map(
            lambda a, b: a + b.astype(acc_dtype), gacc, g)
        macc = jax.tree.map(lambda a, b: a + b, macc, met)
        return (gacc, lacc + l, macc), None

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dtype), params)
    m0 = jax.eval_shape(lambda p, mb: loss_fn(p, mb)[1], params,
                        jax.tree.map(lambda x: x[0], mbs))
    m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), m0)
    (grads, loss, metrics), _ = jax.lax.scan(
        body, (g0, jnp.zeros((), jnp.float32), m0), mbs)
    return ((loss / N, jax.tree.map(lambda m: m / N, metrics)),
            jax.tree.map(lambda g: g / N, grads))


def _trace_mesh(*shards):
    """Ambient mesh the sharded step programs trace under. TP needs the
    in-jit activation hints (``ctx.constrain`` "model" entries in the model
    forward) resolved against the real mesh, so any TP plan activates it;
    pure-DP plans return None — the historical mesh-free trace — so the
    ZeRO bit-identity contract (DESIGN.md §3) sees an unchanged program."""
    for shard in shards:
        if shard is not None and getattr(shard.strat, "ntp", 1) > 1:
            return shard.mesh
    return None


def _make_sharded_update(optimizer, shard, lr):
    """Update half of a ZeRO step: a jit whose operands (moments, grads,
    params) all arrive eagerly pre-placed on the SAME param-shaped update
    layout (``TreePlan.update_specs``) — uniform sharding keeps XLA's
    elementwise fusion identical to the unsharded program, which mixed
    layouts do not (per-operand reshards change FMA contraction by a ulp).
    The program's outputs STAY on the update layout (an in-graph gather
    back to replicated fuses into the elementwise math and perturbs it);
    ``_run_sharded_update`` re-places new params onto the persistent ZeRO
    layout eagerly afterwards — an exact-element all-gather below stage 3,
    a no-op at stage 3."""

    def apply_update(opt, step, grads, p_u):
        new_params, new_opt = optimizer.update(grads, opt, p_u, lr)
        new_params = shard.constrain_update(new_params)
        new_opt = shard.constrain_opt(new_opt)
        return new_params, new_opt, step + 1

    # donate: moments (rewritten), grads (consumed), and the update-layout
    # params (at ZeRO-3 the state buffers themselves — true in-place
    # update; below, the transient 1/ndp slice copy)
    return jax.jit(apply_update, donate_argnums=(0, 2, 3))


def _run_sharded_update(jit_update, shard, state, grads):
    grads = shard.place_grads(grads)
    p_u = shard.place_update_params(state["params"])
    new_params, new_opt, step = jit_update(state["opt"], state["step"],
                                           grads, p_u)
    return {"params": shard.place_params(new_params), "opt": new_opt,
            "step": step}


def make_train_step(model: Model, cfg: ModelConfig, *, lr: float = 3e-5,
                    kind: str = "ppo", kl_coef: float = 0.1,
                    max_grad_norm: float = 1.0, shard=None):
    """kind: ppo | critic | lm.

    ``shard`` (a ``sharding.TreePlan``) makes the step ZeRO-aware, split
    into two programs so the ZeRO layout can never perturb the arithmetic
    (DESIGN.md §3):

      1. a *grad* jit — params gathered to the DP-stripped compute specs
         at entry (the per-step all-gather of ZeRO-3; its transpose pins
         the parameter cotangent replicated, so no sharding pressure
         reaches the forward/backward matmuls), loss + clipped grads
         computed exactly as on one device;
      2. an eager ``device_put`` of the DP-identical grads (and, below
         stage 3, a transient slice of the params) onto the uniform
         update layout — bit-exact by construction;
      3. an *update* jit — elementwise optimizer math over uniformly
         sharded operands, outputs staying on that layout; new params are
         re-placed onto the persistent ZeRO shardings eagerly afterwards.

    Every stage therefore reproduces the unsharded step bit-for-bit while
    persistent params/opt live at ~1/ndp per device. (Adafactor reduces
    across elements inside its update; it declares a fully-replicated
    update layout via ``Adafactor.update_pspecs`` so those reductions run
    in single-device order — bit-equal too, at the cost of a transient
    replicated update.)"""
    optimizer = make_optimizer(cfg.optimizer)
    prefix = _prefix_len(cfg)
    # per-layer ZeRO-3 gather (gather_mode="layer"): the scan body
    # constrains one sliced layer period at a time (DESIGN.md §3.7)
    lspecs = getattr(shard, "layer_specs", None)

    def loss_fn(params, batch):
        if kind == "critic":
            values = model.forward_value(params, batch, layer_specs=lspecs)
            S = batch["tokens"].shape[1]
            values = values[:, prefix:prefix + S]
            return critic_loss(values, batch)
        logits, aux, h = model.forward(params, batch, layer_specs=lspecs)
        if kind == "lm":
            loss = lm_loss(logits, batch["tokens"], batch["loss_mask"],
                           prefix=prefix)
            metrics = {"lm_loss": loss}
        else:
            loss, metrics = ppo_actor_loss(logits, batch, prefix=prefix,
                                           kl_coef=kl_coef)
        if cfg.mtp_depth and kind != "critic":
            mtp = mtp_chain_loss(model, params, h, batch)
            loss = loss + 0.1 * mtp
            metrics["mtp_loss"] = mtp
        return loss + aux, metrics

    N = max(1, cfg.microbatches)
    # grad-accumulation dtype: bf16 for the memory-lean >=100B configs
    acc_dtype = jnp.float32 if cfg.optimizer == "adamw" else jnp.bfloat16

    def grads_and_metrics(state, batch):
        params = state["params"] if shard is None \
            else shard.gather(state["params"])
        (loss, metrics), grads = _accumulated_grads(
            loss_fn, params, batch, N, acc_dtype)
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        return grads, dict(metrics, loss=loss, grad_norm=gnorm)

    if shard is None:
        def train_step(state, batch):
            grads, metrics = grads_and_metrics(state, batch)
            new_params, new_opt = optimizer.update(grads, state["opt"],
                                                   state["params"], lr)
            return {"params": new_params, "opt": new_opt,
                    "step": state["step"] + 1}, metrics

        train_step.optimizer = optimizer
        return train_step

    jit_grads = jax.jit(grads_and_metrics)
    jit_update = _make_sharded_update(optimizer, shard, lr)
    mesh = _trace_mesh(shard)

    def train_step(state, batch):
        with ctx.use_mesh(mesh):
            grads, metrics = jit_grads(state, batch)
            new_state = _run_sharded_update(jit_update, shard, state, grads)
        return new_state, metrics

    train_step.optimizer = optimizer
    train_step.prejitted = True     # callers must NOT wrap in jax.jit
    train_step.jit_grads = jit_grads    # exposed so benchmarks can read the
    # compiled program's transient-peak stats (memory_analysis)
    return train_step


def init_train_state(model: Model, cfg: ModelConfig, key, optimizer,
                     plan=None):
    """Fresh train state; with a ``sharding.TreePlan`` it is built on the
    plan's layout (``TreePlan.init_state``) instead of on one device."""
    if plan is not None:
        return plan.init_state(model.init, key, optimizer)
    params = model.init(key)
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def make_lora_train_step(model: Model, cfg: ModelConfig, *, lr: float = 3e-5,
                         kind: str = "ppo", kl_coef: float = 0.1,
                         max_grad_norm: float = 1.0, shard=None,
                         base_shard=None):
    """LoRA-aware twin of :func:`make_train_step` for the hydra engine.

    The step signature is ``(state, base_params, batch)``: gradients and the
    optimizer state cover ONLY the adapter leaves in ``state["params"]`` —
    the frozen trunk rides along as a non-donated, non-differentiated input,
    so its bytes are shared across every role's step. Microbatch gradient
    accumulation and the MTP auxiliary loss match :func:`make_train_step`
    (the MTP head stays frozen in the trunk; its loss still trains the
    adapter through the hidden states). kind: ppo | critic | lm.

    ``shard`` (the adapter's ``sharding.TreePlan``) and ``base_shard``
    (the frozen trunk's) make the step ZeRO-aware with the same
    gather-compute / slice-update contract as :func:`make_train_step`: the
    ZeRO-3 trunk is gathered for the forward, adapter grads are clipped
    replicated then sliced onto the adapter optimizer layout.
    """
    optimizer = make_optimizer(cfg.optimizer)
    prefix = _prefix_len(cfg)
    # per-layer ZeRO-3 gather of the frozen trunk inside the scan body
    # (the adapter itself always gathers whole — it is paper-small)
    blspecs = getattr(base_shard, "layer_specs", None)

    def loss_fn(adapter, base_params, batch):
        if kind == "critic":
            values = model.forward_value(base_params, batch, adapter=adapter,
                                         layer_specs=blspecs)
            S = batch["tokens"].shape[1]
            values = values[:, prefix:prefix + S]
            return critic_loss(values, batch)
        logits, aux, h = model.forward(base_params, batch, adapter=adapter,
                                       layer_specs=blspecs)
        if kind == "lm":
            loss = lm_loss(logits, batch["tokens"], batch["loss_mask"],
                           prefix=prefix)
            metrics = {"lm_loss": loss}
        else:
            loss, metrics = ppo_actor_loss(logits, batch, prefix=prefix,
                                           kl_coef=kl_coef)
        if cfg.mtp_depth and kind != "critic":
            mtp = mtp_chain_loss(model, base_params, h, batch)
            loss = loss + 0.1 * mtp
            metrics["mtp_loss"] = mtp
        return loss + aux, metrics

    N = max(1, cfg.microbatches)
    acc_dtype = jnp.float32 if cfg.optimizer == "adamw" else jnp.bfloat16

    def grads_and_metrics(state, base_params, batch):
        if base_shard is not None:
            base_params = base_shard.gather(base_params)
        adapter = state["params"] if shard is None \
            else shard.gather(state["params"])
        (loss, metrics), grads = _accumulated_grads(
            lambda ad, mb: loss_fn(ad, base_params, mb),
            adapter, batch, N, acc_dtype)
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        return grads, dict(metrics, loss=loss, grad_norm=gnorm)

    if shard is None and base_shard is None:
        def train_step(state, base_params, batch):
            grads, metrics = grads_and_metrics(state, base_params, batch)
            new_params, new_opt = optimizer.update(grads, state["opt"],
                                                   state["params"], lr)
            return {"params": new_params, "opt": new_opt,
                    "step": state["step"] + 1}, metrics

        train_step.optimizer = optimizer
        return train_step

    assert shard is not None, "base_shard without an adapter plan"
    jit_grads = jax.jit(grads_and_metrics)
    jit_update = _make_sharded_update(optimizer, shard, lr)
    mesh = _trace_mesh(base_shard, shard)

    def train_step(state, base_params, batch):
        with ctx.use_mesh(mesh):
            grads, metrics = jit_grads(state, base_params, batch)
            new_state = _run_sharded_update(jit_update, shard, state, grads)
        return new_state, metrics

    train_step.optimizer = optimizer
    train_step.prejitted = True     # callers must NOT wrap in jax.jit
    train_step.jit_grads = jit_grads
    return train_step


def init_lora_train_state(adapter, optimizer):
    """Train state whose params (and hence optimizer moments) are only the
    adapter tree — the trainable_fraction-scaled footprint of the paper's
    LoRA rows, realized."""
    return {"params": adapter, "opt": optimizer.init(adapter),
            "step": jnp.zeros((), jnp.int32)}


def make_prefill_step(model: Model, cfg: ModelConfig, *, capacity: int,
                      window: int = 0):
    def prefill_step(params, batch):
        return model.prefill(params, batch, capacity, window=window)
    return prefill_step


def make_decode_step(model: Model, cfg: ModelConfig, *, window: int = 0):
    def decode_step(params, caches, token, position):
        return model.decode_step(params, caches, token, position,
                                 window=window)
    return decode_step


# ---------------------------------------------------------------------------
# ShapeDtypeStruct stand-ins for the dry-run (no allocation)
# ---------------------------------------------------------------------------
def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def decode_window(cfg: ModelConfig, shape: ShapeConfig) -> int:
    """Sub-quadratic path: long_500k uses a sliding window for attention
    layers (SSM layers are O(1) anyway). 0 = full attention."""
    if shape.kind == "long_decode":
        return cfg.long_context_window
    return cfg.sliding_window


def cache_capacity(cfg: ModelConfig, shape: ShapeConfig) -> int:
    w = decode_window(cfg, shape)
    return min(shape.seq_len, w) if w else shape.seq_len


def input_specs(cfg: ModelConfig, shape: ShapeConfig,
                dtype: str = "bfloat16") -> Dict[str, Any]:
    """Batch ShapeDtypeStructs for (arch, shape). For decode kinds this is
    the (token, position) pair; caches are built separately (they are
    threaded state, not per-step host input)."""
    B, S = shape.global_batch, shape.seq_len
    P = _prefix_len(cfg)
    S_tok = S - P if cfg.input_mode == "embeddings" else S
    f32 = jnp.float32
    out: Dict[str, Any] = {}
    if shape.kind in ("train", "prefill"):
        out["tokens"] = sds((B, S_tok), jnp.int32)
        if cfg.input_mode == "embeddings":
            out["prefix_embeds"] = sds((B, P, cfg.d_model), dtype)
        if cfg.input_mode == "encdec":
            out["frame_embeds"] = sds((B, cfg.num_prefix_embeddings,
                                       cfg.d_model), dtype)
        if shape.kind == "train":
            for k in ("loss_mask", "advantages", "old_logp", "ref_logp",
                      "returns"):
                out[k] = sds((B, S_tok), f32)
    else:  # decode kinds
        out["token"] = sds((B,), jnp.int32)
        out["position"] = sds((B,), jnp.int32)
    return out


def cache_specs(model: Model, cfg: ModelConfig, shape: ShapeConfig,
                dtype: str = "bfloat16"):
    """ShapeDtypeStructs of the decode caches for (arch, shape)."""
    cap = cache_capacity(cfg, shape)
    B = shape.global_batch
    segs = jax.eval_shape(
        lambda: model.init_cache(B, cap, jnp.dtype(dtype)))
    caches = {"segments": segs, "cross_kv": None}
    if cfg.input_mode == "encdec":
        Se = cfg.num_prefix_embeddings
        kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim()
        out = []
        for seg in model.segments:
            out.append(tuple(
                (sds((seg.n_groups, B, Se, kvh, hd), dtype),
                 sds((seg.n_groups, B, Se, kvh, hd), dtype))
                for _ in range(len(seg.kinds))))
        caches["cross_kv"] = out
    return caches
