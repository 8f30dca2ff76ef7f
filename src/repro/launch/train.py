"""Training launcher: LM pretraining/SFT or RLHF PPO for any registered
architecture on the host devices (CPU smoke / single TPU host) — the
multi-device production configuration is exercised via dryrun.py.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3_2_3b --smoke \
      --mode lm --steps 50 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch opt_1_3b --smoke \
      --mode rlhf --steps 20 --batch 8
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save
from repro.configs import get_config
from repro.data import PromptDataset, SyntheticTextDataset, \
    synthetic_instruction_prompts
from repro.launch.compile_cache import use_compile_cache
from repro.models import Model
from repro.rlhf import RLHFConfig, RLHFTrainer
from repro.rlhf.reward import make_target_token_reward
from repro.steps import init_train_state, make_train_step


def train_lm(cfg, args):
    model = Model(cfg)
    step_fn = make_train_step(model, cfg, kind="lm", lr=args.lr)
    state = init_train_state(model, cfg, jax.random.PRNGKey(args.seed),
                             step_fn.optimizer)
    n_params = sum(int(np.prod(p.shape)) for p in
                   jax.tree.leaves(state["params"]))
    print(f"[train] {cfg.name}: {n_params/1e6:.2f}M params")
    data = SyntheticTextDataset(cfg.vocab_size, args.seq, seed=args.seed)
    jit_step = jax.jit(step_fn, donate_argnums=(0,))
    it = data.batches(args.batch)
    t0 = time.time()
    for step in range(args.steps):
        toks = jnp.asarray(next(it))
        batch = {"tokens": toks, "loss_mask": jnp.ones_like(toks, jnp.float32)}
        state, metrics = jit_step(state, batch)
        if step % max(1, args.steps // 10) == 0 or step == args.steps - 1:
            print(f"  step {step:4d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"({time.time()-t0:.1f}s)")
    if args.ckpt_dir:
        path = save(args.ckpt_dir, args.steps, state["params"])
        print(f"[train] saved {path}")
    return state


def train_rlhf(cfg, args):
    rl = RLHFConfig(prompt_len=args.seq // 2, gen_len=args.seq // 2,
                    lr=args.lr, critic_lr=args.lr * 3,
                    kl_coef=0.05, memory_policy=args.memory_policy)
    trainer = RLHFTrainer(cfg, cfg, rl, jax.random.PRNGKey(args.seed),
                          reward_fn=make_target_token_reward(7))
    prompts = PromptDataset(synthetic_instruction_prompts(256),
                            rl.prompt_len)
    it = prompts.batches(args.batch, seed=args.seed)
    key = jax.random.PRNGKey(args.seed + 1)
    t0 = time.time()
    for step in range(args.steps):
        key, k = jax.random.split(key)
        batch = jnp.asarray(next(it)) % cfg.vocab_size
        m = trainer.train_step(batch, k)
        if step % max(1, args.steps // 10) == 0 or step == args.steps - 1:
            print(f"  step {step:4d} reward {m['mean_reward']:+.4f} "
                  f"kl {m['kl']:.4f} vf {m['vf_loss']:.4f} "
                  f"({time.time()-t0:.1f}s)")
    print(f"[train] phase-memory records: {len(trainer.memory.records)} "
          f"(policy={args.memory_policy})")
    return trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mode", choices=("lm", "rlhf"), default="lm")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced CPU-sized variant")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--memory-policy", default="after_inference",
                    choices=("none", "after_inference", "after_training",
                             "after_all"))
    args = ap.parse_args()
    use_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.mode == "lm":
        train_lm(cfg, args)
    else:
        train_rlhf(cfg, args)


if __name__ == "__main__":
    main()
