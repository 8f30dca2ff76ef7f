"""Bring-up smoke run of the RLHF trainer and the paged server on TPU.

    python chip_smoke.py                # one chip: phases A, B and C
    python chip_smoke.py --four-chips   # four chips: the ZeRO-3 vs dp x tp phase

One chip runs OPT-1.3b, the paper's actor, at its published widths (24
layers, d_model 2048, 32 heads, vocab 50272) with random weights drawn from
``--seed``:

  A. training — 3 PPO iterations of ``RLHFTrainer`` on the hydra engine
     (one frozen trunk, rank-128 LoRA adapters per role, learned reward
     head), batch 4, prompt 128, generation 128;
  B. serving — the paged ``ContinuousBatcher`` answers 8 greedy requests
     (prompts of 64 to 256 tokens, 32 new tokens each) from the trained
     actor's merged weights;
  C. correctness — one served prompt's last-position logits against a
     float32 ``Model.forward`` of the same weights cast up, and every token
     served for it against the float32 logits at its position.

``--four-chips`` runs only the paper's DeepSpeed-Chat pair on the separate
engine (OPT-1.3b actor at its published widths cut to 16 of its 24 layers,
OPT-350m critic and reward model) in float32 with greedy rollout: 2 PPO
iterations at ndp=4 ZeRO-3 and 2 at ndp=2 x ntp=2 from one seed. Their
losses must agree within DESIGN.md §9's allclose bar, and device 0 may not
peak above 1.5x the median of the other devices.

Each phase reports its compile time as set-up. The numbers printed are
bring-up readings, not benchmark results. The last line of standard output
is the JSON result; the script exits non-zero without it when JAX finds no
TPU or any check fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# phase A shape: its compiled actor step is sized against one v5e's HBM by
# tests/test_tpu_compile.py
TRAIN_BATCH, PROMPT_LEN, GEN_LEN, LORA_RANK, ITERATIONS = 4, 128, 128, 128, 3
# phase B traffic
N_REQUESTS, MIN_PROMPT, MAX_PROMPT, NEW_TOKENS = 8, 64, 256, 32
PAGE_SIZE, PREFILL_BUCKETS = 16, (64, 128, 256)
# phase C: bf16 keeps an 8-bit significand and the served logits pass
# through 24 layers of bf16 rounding; a correct run measures well under 1%
# of the largest logit (a 4-layer cut on CPU: 0.8%), a wrong kernel or
# compiler setting is off by the logits' own size
LOGIT_RTOL = 0.05
# four chips: DESIGN.md §9.5, the float32 dp x tp bar of tests/test_tp.py
FOUR_CHIP_RTOL, FOUR_CHIP_ATOL, PEAK_RATIO = 1e-4, 1e-6, 1.5
FOUR_CHIP_BATCH, FOUR_CHIP_PROMPT, FOUR_CHIP_GEN = 4, 64, 64
FOUR_CHIP_ITERATIONS = 2
# ZeRO-3 keeps each step's float32 gradients whole on every device (the
# bit-identity contract of DESIGN.md §3), so at ndp=4 the full 24-layer
# actor step needs about 17.5 GiB per device (compiled for a described
# v5e: 6.5 GiB temporaries + 4.9 GiB gradients + the resident state);
# 16 layers leave about 3 GiB of the 15.75 GiB free
FOUR_CHIP_ACTOR_LAYERS = 16


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileMeter:
    """Compile time per phase, from JAX's own monitoring events: tracing,
    lowering and backend compilation (a persistent-cache hit is counted as
    a load, in the backend time)."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self._DURATIONS:
            self.seconds += duration
        if event == self._DURATIONS[-1]:
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.seconds, self.programs, self.cache_hits

    def report(self, phase: str, since) -> None:
        s, p, h = self.mark()
        log(f"[set-up] {phase}: compile {s - since[0]:.3f} s over "
            f"{p - since[1]} programs ({h - since[2]} loaded from the "
            "persistent cache)")


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def release(*trees, keep=()) -> None:
    """Delete every device buffer in ``trees`` except those in ``keep``."""
    import jax
    kept = {id(x) for x in jax.tree.leaves(keep)}
    for leaf in jax.tree.leaves(trees):
        if id(leaf) not in kept and hasattr(leaf, "delete") \
                and not leaf.is_deleted():
            leaf.delete()


# ------------------------------------------------------------------ phases
def train_phase(cfg, seed: int, meter: CompileMeter):
    """Phase A. Returns the trainer after its PPO iterations."""
    import jax
    import numpy as np

    from repro.rlhf import RLHFConfig, RLHFTrainer

    dev = jax.devices()[0]
    mark = meter.mark()
    t0 = time.perf_counter()
    rl = RLHFConfig(prompt_len=PROMPT_LEN, gen_len=GEN_LEN, engine="hydra",
                    lora_rank=LORA_RANK)
    trainer = RLHFTrainer(cfg, cfg, rl, jax.random.PRNGKey(seed))
    jax.block_until_ready((trainer.base_params, trainer.actor_state))
    log(f"[A] hydra trainer for {cfg.name} (rank {LORA_RANK}) built in "
        f"{time.perf_counter() - t0:.3f} s")
    key = jax.random.PRNGKey(seed + 1)
    for it in range(ITERATIONS):
        kp, kr = jax.random.split(jax.random.fold_in(key, it))
        prompts = jax.random.randint(kp, (TRAIN_BATCH, PROMPT_LEN), 0,
                                     cfg.vocab_size)
        c0 = meter.mark()
        t0 = time.perf_counter()
        m = trainer.train_step(prompts, kr)
        jax.block_until_ready((trainer.actor_state, trainer.critic_state))
        wall = time.perf_counter() - t0
        compile_s = meter.mark()[0] - c0[0]
        log(f"[A] iteration {it + 1}: wall {wall:.3f} s (compile "
            f"{compile_s:.3f} s of it) "
            + " ".join(f"{k} {m[k]:.6g}" for k in
                       ("ppo_loss", "kl", "vf_loss", "mean_reward"))
            + f" peak_bytes_in_use {peak_bytes(dev)}")
        check(all(np.isfinite(v) for v in m.values()),
              f"iteration {it + 1} has a non-finite metric: {m}")
    meter.report("A training", mark)
    return trainer


def serve_phase(model, cfg, params, seed: int, meter: CompileMeter):
    """Phase B. Returns (batcher, finished requests)."""
    import jax
    import numpy as np

    from repro.serving import ContinuousBatcher

    dev = jax.devices()[0]
    mark = meter.mark()
    cb = ContinuousBatcher(model, cfg, params, slots=N_REQUESTS,
                           capacity=MAX_PROMPT + NEW_TOKENS,
                           temperature=0.0, top_k=0, seed=seed,
                           cache_backend="paged", page_size=PAGE_SIZE,
                           capture_buckets=PREFILL_BUCKETS, warmup=False)
    cb.warmup(max_prompt_len=MAX_PROMPT)
    meter.report("B serving warm-up", mark)
    rng = np.random.RandomState(seed)
    lens = np.linspace(MIN_PROMPT, MAX_PROMPT, N_REQUESTS).astype(int)
    reqs = [cb.submit(rng.randint(0, cfg.vocab_size, size=n), NEW_TOKENS)
            for n in lens]
    t0 = time.perf_counter()
    cb.run_until_drained()
    wall = time.perf_counter() - t0
    for r in reqs:
        log(f"[B] request {r.rid}: prompt {len(r.prompt)} tokens, "
            f"{len(r.out_tokens)} new")
        check(r.done and len(r.out_tokens) == NEW_TOKENS,
              f"request {r.rid} returned {len(r.out_tokens)} of "
              f"{NEW_TOKENS} tokens")
    log(f"[B] {N_REQUESTS} requests served in {wall:.3f} s over "
        f"{cb.steps} steps; compile cache {cb.compile_cache.stats()}; "
        f"peak_bytes_in_use {peak_bytes(dev)}")
    return cb, reqs


def logits_phase(model, params, cb, req, meter: CompileMeter):
    """Phase C: served numerics against a float32 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    mark = meter.mark()
    prompt = np.asarray(req.prompt, np.int32)
    L = len(prompt)
    # the server's prefill computation for this prompt: the same bucket
    # padding, the same paged program, the same bf16 weights
    Sb = cb.prefill_ladder.fit(L)
    padded = np.zeros((1, Sb), np.int32)
    padded[0, :L] = prompt
    pools = model.init_paged_pools(cb.max_blocks, cb.page_size,
                                   jax.tree.leaves(params)[0].dtype)
    bt = jnp.arange(cb.max_blocks, dtype=jnp.int32)[None]
    served, _ = jax.jit(model.paged_prefill)(
        params, {"tokens": jnp.asarray(padded)}, pools, bt,
        jnp.asarray([L], jnp.int32))
    served = np.asarray(served[0], np.float32)
    release(pools)
    # float32 reference over the prompt and every served token but the
    # last, in full float32 matmul precision
    seq = np.concatenate([prompt, np.asarray(req.out_tokens[:-1],
                                             np.int32)])[None]
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0][0])(
            p32, jnp.asarray(seq))
    ref = np.asarray(ref, np.float32)
    release(p32)
    meter.report("C correctness", mark)

    last = ref[L - 1]
    tol = LOGIT_RTOL * float(np.abs(last).max())
    err = np.abs(served - last)
    log(f"[C] prompt of {L} tokens: max |served - float32| "
        f"{float(err.max()):.6g}, mean {float(err.mean()):.6g}, "
        f"tolerance {tol:.6g} (= {LOGIT_RTOL} x max|float32 logit| "
        f"{float(np.abs(last).max()):.6g})")
    check(float(err.max()) <= tol,
          f"served logits differ from float32 by {float(err.max()):.6g} "
          f"> {tol:.6g}")
    # a greedy token is the argmax of logits within tol of the float32
    # ones, so its float32 logit is within 2 tol of the float32 maximum
    worst, agree = np.inf, 0
    for t, tok in enumerate(req.out_tokens):
        row = ref[L - 1 + t]
        margin = 2 * LOGIT_RTOL * float(np.abs(row).max()) \
            - (float(row.max()) - float(row[tok]))
        worst = min(worst, margin)
        agree += int(tok == int(row.argmax()))
    log(f"[C] {len(req.out_tokens)} served tokens: {agree} equal the "
        f"float32 argmax; smallest slack to the 2 x tolerance bound "
        f"{worst:.6g}; peak_bytes_in_use {peak_bytes(dev)}")
    check(worst >= 0, "a served token is not a float32 greedy choice "
          "within tolerance")


def four_chip_phase(actor_cfg, critic_cfg, seed: int, meter: CompileMeter):
    """ndp=4 ZeRO-3 against ndp=2 x ntp=2 ZeRO-3, separate engine."""
    import jax
    import numpy as np

    from repro.rlhf import RLHFConfig, RLHFTrainer
    from repro.sharding import ShardedContext
    from repro.sharding.rules import validate_tp

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    validate_tp(actor_cfg, 2)
    validate_tp(critic_cfg, 2)
    rl = RLHFConfig(prompt_len=FOUR_CHIP_PROMPT, gen_len=FOUR_CHIP_GEN,
                    engine="separate", temperature=0.0, top_k=0)
    key = jax.random.PRNGKey(seed + 1)
    prompts = jax.random.randint(key, (FOUR_CHIP_BATCH, FOUR_CHIP_PROMPT),
                                 0, actor_cfg.vocab_size)
    runs = {}
    for label, ndp, ntp in (("ndp=4 zero3", 4, 1),
                            ("ndp=2 x ntp=2 zero3", 2, 2)):
        mark = meter.mark()
        sc = ShardedContext.create(ndp, zero_stage=3, model=ntp)
        t0 = time.perf_counter()
        tr = RLHFTrainer(actor_cfg, critic_cfg, rl, jax.random.PRNGKey(seed),
                         shard=sc)
        log(f"[4] {label}: trainer built in {time.perf_counter() - t0:.3f} s,"
            f" per-device state {tr.per_device_state_bytes()} bytes")
        ms = []
        for it in range(FOUR_CHIP_ITERATIONS):
            t0 = time.perf_counter()
            m = tr.train_step(prompts, jax.random.fold_in(key, 100 + it))
            jax.block_until_ready((tr.actor_state, tr.critic_state))
            log(f"[4] {label} iteration {it + 1}: wall "
                f"{time.perf_counter() - t0:.3f} s "
                + " ".join(f"{k} {m[k]:.9g}" for k in
                           ("ppo_loss", "kl", "vf_loss", "mean_reward")))
            check(all(np.isfinite(v) for v in m.values()),
                  f"{label} iteration {it + 1} has a non-finite metric")
            ms.append(m)
        meter.report(f"4 {label}", mark)
        release(tr.actor_state, tr.critic_state, tr.ref_params,
                tr.reward_params)
        del tr
        gc.collect()
        runs[label] = ms
    (la, ma), (lb, mb) = runs.items()
    for it, (a, b) in enumerate(zip(ma, mb)):
        for k in ("loss", "ppo_loss", "vf_loss", "kl"):
            d = abs(a[k] - b[k])
            log(f"[4] iteration {it + 1} {k}: |{la} - {lb}| = {d:.3g} "
                f"(bar {FOUR_CHIP_ATOL} + {FOUR_CHIP_RTOL} x "
                f"{abs(a[k]):.6g})")
            check(d <= FOUR_CHIP_ATOL + FOUR_CHIP_RTOL * abs(a[k]),
                  f"iteration {it + 1} {k}: {a[k]!r} vs {b[k]!r}")
    peaks = [peak_bytes(d) for d in devs[:4]]
    median_others = float(np.median(peaks[1:]))
    log("[4] peak_bytes_in_use per device: "
        + " ".join(f"{d.id}:{p}" for d, p in zip(devs, peaks))
        + f"; device 0 / median of the others = "
        f"{peaks[0] / median_others:.4f} (bound {PEAK_RATIO})")
    check(peaks[0] <= PEAK_RATIO * median_others,
          "device 0 peaks above the bound: it holds more than its share")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip ZeRO-3 vs dp x tp phase")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports platform "
              f"{devs[0].platform!r}); this script has no CPU fallback",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    dev = devs[0]
    log(f"platform {dev.platform} device_kind {dev.device_kind} "
        f"count {len(devs)}; compilation cache {cache}")
    meter = CompileMeter()

    import dataclasses

    from repro.configs import get_config
    t_all = time.perf_counter()
    try:
        if args.four_chips:
            jax.config.update("jax_default_matmul_precision", "highest")
            four_chip_phase(
                dataclasses.replace(get_config("opt_1_3b"),
                                    num_layers=FOUR_CHIP_ACTOR_LAYERS,
                                    param_dtype="float32"),
                dataclasses.replace(get_config("opt_350m"),
                                    param_dtype="float32"),
                args.seed, meter)
        else:
            cfg = get_config("opt_1_3b")
            trainer = train_phase(cfg, args.seed, meter)
            model = trainer.actor
            merged = model.merge_adapter(trainer.base_params,
                                         trainer.actor_state["params"])
            release(trainer.base_params, trainer.actor_state,
                    trainer.critic_state, trainer.engine.adapters,
                    keep=merged)
            del trainer
            gc.collect()
            cb, reqs = serve_phase(model, cfg, merged, args.seed, meter)
            logits_phase(model, merged, cb, reqs[-1], meter)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    log(f"total wall {time.perf_counter() - t_all:.3f} s; compile "
        f"{meter.seconds:.3f} s over {meter.programs} programs "
        f"({meter.cache_hits} loaded from the persistent cache)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
