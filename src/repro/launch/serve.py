"""Serving launcher: batched generation with the fixed-capacity donated KV
cache (prefill + decode loop), reporting per-phase live-memory — the
inference side of the paper's study as a runnable service loop.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_3b --smoke \
      --batch 8 --prompt-len 32 --gen 64 --requests 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import ByteTokenizer, PromptDataset, \
    synthetic_instruction_prompts
from repro.launch.compile_cache import use_compile_cache
from repro.models import Model
from repro.obs import MetricsRegistry
from repro.rlhf import Rollout, live_device_bytes


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=64)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a metrics-registry JSONL snapshot here")
    args = ap.parse_args()
    use_compile_cache()
    reg = MetricsRegistry()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"[serve] {cfg.name}: {n/1e6:.2f}M params, "
          f"live {live_device_bytes()/2**20:.1f} MiB")
    reg.gauge("serve_params_m", "model size in M params").set(n / 1e6)
    reg.gauge("serve_live_device_bytes",
              "live HBM bytes (peak via gauge peak)").set(live_device_bytes())

    rollout = Rollout(model, cfg, capacity=args.prompt_len + args.gen,
                      temperature=args.temperature, top_k=50)
    prompts = PromptDataset(
        synthetic_instruction_prompts(args.batch * args.requests,
                                      seed=args.seed), args.prompt_len)
    it = prompts.batches(args.batch, seed=args.seed)
    tok = ByteTokenizer()
    key = jax.random.PRNGKey(args.seed + 1)
    for r in range(args.requests):
        key, k = jax.random.split(key)
        batch = jnp.asarray(next(it)) % cfg.vocab_size
        t0 = time.time()
        res = rollout.generate(params, {"tokens": batch}, args.gen, k)
        dt = time.time() - t0
        tput = args.batch * args.gen / dt
        print(f"[serve] request {r}: {dt*1e3:7.1f} ms "
              f"({tput:7.1f} tok/s) live {live_device_bytes()/2**20:8.1f} MiB")
        reg.counter("serve_requests_total", "generate calls served").inc()
        reg.counter("serve_tokens_total", "tokens generated").inc(
            args.batch * args.gen)
        reg.histogram("serve_request_latency_s",
                      "wall time per generate call").observe(dt)
        reg.gauge("serve_tokens_per_s", "throughput of last request").set(tput)
        reg.gauge("serve_live_device_bytes",
                  "live HBM bytes (peak via gauge peak)").set(
            live_device_bytes())
        if cfg.vocab_size >= 259 and r == 0:
            print("  sample:", tok.decode(
                np.asarray(res.tokens[0])[args.prompt_len:])[:60])
    if args.metrics_out:
        reg.write_jsonl(args.metrics_out)
        print(f"[serve] metrics -> {args.metrics_out}")


if __name__ == "__main__":
    main()
