"""Benchmark harness — one benchmark per paper table/figure, plus kernel
microbenchmarks and the roofline summary. Prints ``name,us_per_call,derived``
CSV rows (and the detailed tables beneath).

  figure1    — per-phase memory timeline of one PPO iteration (all-enabled)
  table1     — strategies x {none, empty_cache} for OPT and GPT-2 (24 GB)
  table2     — A100-80GB grid: OPT-1.3b / OPT-6.7b / Llama-2-7b, +-ZeRO-3
  placement  — empty_cache placement ablation (paper §3.3)
  generation — naive (HF-style growing cache) vs framework static cache
  paged      — dense [B, capacity] vs paged KV cache on ragged requests
  decode     — fast decode path: compile-bucket ladder + MTP speculation
  obs        — runtime telemetry: phase spans, sim-vs-measured, overhead,
               per-owner HBM attribution + flight-recorder dump (PR 8)
  zero       — mesh-sharded ZeRO RLHF smoke on 8 forced host devices
  tp         — TP x ZeRO composition smoke: dp x tp allclose + byte cuts
  kernels    — wall-time microbenches of the XLA flash twin vs dense sdpa
  roofline   — summary of roofline_baseline.json if present

Run: PYTHONPATH=src python -m benchmarks.run [--only table1 ...]

Every run writes one ``BENCH_<name>.json`` per benchmark into ``--out-dir``
(default ``benchmarks/results/``; CI uploads them as artifacts). Metrics a
benchmark registers via ``_gate`` are regression-gated: with
``--check-baseline``, any gated metric that regresses >10% against the
committed ``benchmarks/baselines/BENCH_<name>.json`` fails the run —
the perf trajectory is recorded, not just asserted once. Each run also
appends the gated metrics as one git-sha-stamped line to
``benchmarks/history/HISTORY_<name>.jsonl`` (``--history-dir``) — the
cross-run trend ``launch/report.py --trend`` renders.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

GB = 1 << 30

# per-benchmark results registry: name -> {"metrics": {...}, "gated": {...}}
RESULTS: dict = {}
_CURRENT = [None]                   # benchmark currently executing
# with --emit-trace: name -> Chrome-trace dict, written as TRACE_<name>.json
TRACES: dict = {}
_EMIT_TRACE = [False]
# extra JSON artifacts a bench wants preserved next to its BENCH_ file
# (attribution tables, flight-recorder dumps): filename -> obj
ARTIFACTS: dict = {}


def _result(name=None):
    cur = name or _CURRENT[0] or "misc"
    return RESULTS.setdefault(cur, {"name": cur, "metrics": {}, "gated": {}})


def _trace(chrome: dict) -> None:
    """Attach a Chrome-trace dict to the current benchmark (overrides the
    harness's own wall-clock span trace for benches that record a richer
    one, e.g. bench_obs's full per-phase run trace)."""
    if _EMIT_TRACE[0] and _CURRENT[0]:
        TRACES[_CURRENT[0]] = chrome


def _artifact(filename: str, obj) -> None:
    """Register an extra JSON artifact (flight dump, attribution tables)
    for ``write_results`` to persist into ``--out-dir``."""
    ARTIFACTS[filename] = obj


def _csv(name, us, derived=""):
    print(f"CSV,{name},{us:.1f},{derived}")
    _result()["metrics"][name] = {"us_per_call": round(us, 1),
                                  "derived": derived}


def _gate(key, value, better="higher"):
    """Register a regression-gated metric for the current benchmark.
    ``better="higher"`` fails when the value drops >10% below baseline;
    ``"lower"`` fails when it rises >10% above."""
    assert better in ("higher", "lower"), better
    _result()["gated"][key] = {"value": float(value), "better": better}


def write_results(out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    for name, rec in RESULTS.items():
        path = os.path.join(out_dir, f"BENCH_{name}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, sort_keys=True)
        print(f"[bench] wrote {path}")
    for name, chrome in TRACES.items():
        path = os.path.join(out_dir, f"TRACE_{name}.json")
        with open(path, "w") as f:
            json.dump(chrome, f)
        print(f"[bench] wrote {path}")
    for fname, obj in ARTIFACTS.items():
        path = os.path.join(out_dir, fname)
        with open(path, "w") as f:
            json.dump(obj, f, indent=1, default=str)
        print(f"[bench] wrote {path}")


def _git_sha() -> str:
    try:
        import subprocess
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def append_history(history_dir: str) -> None:
    """Append one timestamped, git-sha-stamped JSONL line per completed
    benchmark to ``HISTORY_<name>.jsonl`` — the cross-run trajectory that
    ``launch/report.py --trend`` renders. Append-only by design: the
    BENCH_ files are one run's snapshot; the history is the trend."""
    os.makedirs(history_dir, exist_ok=True)
    t = time.time()
    iso = time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(t))
    sha = _git_sha()
    for name, rec in RESULTS.items():
        if not rec["gated"]:
            continue            # nothing trend-worthy was registered
        line = {"t": t, "iso": iso, "sha": sha, "bench": name,
                "gated": {k: v["value"] for k, v in rec["gated"].items()}}
        path = os.path.join(history_dir, f"HISTORY_{name}.jsonl")
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(f"[bench] history += {path} ({sha})")


def check_baseline(baseline_dir: str, tol: float = 0.10) -> int:
    """Compare this run's gated metrics against the committed baselines.
    Returns the number of regressions (>tol relative, in the bad
    direction — improvements never fail)."""
    failures = 0
    for name, rec in RESULTS.items():
        path = os.path.join(baseline_dir, f"BENCH_{name}.json")
        if not os.path.exists(path):
            if rec["gated"]:
                print(f"[bench] {name}: no baseline committed "
                      f"({path}) — skipped")
            continue
        base = json.load(open(path)).get("gated", {})
        for key, cur in rec["gated"].items():
            if key not in base:
                print(f"[bench] {name}.{key}: not in baseline — skipped")
                continue
            bv, cv = base[key]["value"], cur["value"]
            if cur["better"] == "higher":
                ok = cv >= bv - abs(bv) * tol
            else:
                ok = cv <= bv + abs(bv) * tol
            status = "ok" if ok else "REGRESSION"
            print(f"[bench] {name}.{key}: {cv:.2f} vs baseline {bv:.2f} "
                  f"({cur['better']} is better) {status}")
            failures += 0 if ok else 1
    return failures


def _study(actor_name, critic_name, gen_lens, naive=True):
    from repro.configs import get_config
    from repro.core import build_rlhf_phases, lora_trainable_fraction
    actor = get_config(actor_name)
    critic = get_config(critic_name)
    # exact trainable fraction from the real adapter tree; the lora_rank
    # axis of the strategy grid threads through here
    tf = lambda rank=128: lora_trainable_fraction(actor, rank)
    cache = {}

    def plans(grad_ckpt):
        if grad_ckpt not in cache:
            out, persist = [], None
            for gl in gen_lens:
                ph, persist = build_rlhf_phases(
                    actor, critic, gen_len=gl, naive_generation=naive,
                    grad_ckpt=grad_ckpt)
                out.append(ph)
            cache[grad_ckpt] = (out, persist)
        return cache[grad_ckpt]
    return plans, tf


GEN_LENS = [180, 256, 199, 243]


def bench_figure1():
    """Figure 1: reserved/allocated timeline across the phases of a PPO
    iteration (all strategies enabled)."""
    from repro.core import PAPER_STRATEGIES, run_iteration
    t0 = time.time()
    plans, tf = _study("opt_1_3b", "opt_350m", GEN_LENS)
    strat = [s for s in PAPER_STRATEGIES if s.name == "All Enabled"][0]
    pl, persist = plans(True)
    r = run_iteration(pl, persist, strat, "none", ndp=4,
                      trainable_fraction=tf(strat.lora_rank), timeline=True)
    print("\n== Figure 1: phase memory timeline (All Enabled, OPT) ==")
    print(f"{'phase':18s} {'reserved_end':>12s} {'alloc_end':>10s} "
          f"{'frag_end':>9s}")
    for rec in r.phase_records[:8]:
        print(f"{rec.name:18s} {rec.reserved_end/GB:11.2f}G "
              f"{rec.allocated_end/GB:9.2f}G {rec.frag_end/GB:8.2f}G")
    ov = 100 * r.frag_at_peak / max(r.peak_reserved - r.frag_at_peak, 1)
    print(f"peak reserved {r.peak_reserved/GB:.2f}G  "
          f"frag@peak {r.frag_at_peak/GB:.2f}G  "
          f"(overhead {ov:.0f}% — paper: 46%)")
    _gate("frag_overhead_pct", ov, "lower")
    _csv("figure1_timeline", (time.time() - t0) * 1e6,
         f"frag_overhead_pct={ov:.0f}")


def _grid(title, actor, critic, capacity,
          policies=("none", "after_inference")):
    from repro.core import PAPER_STRATEGIES, run_iteration
    plans, tf = _study(actor, critic, GEN_LENS)
    print(f"\n== {title} ==")
    print(f"{'strategy':28s} {'policy':16s} {'reserved':>8s} {'frag':>6s} "
          f"{'alloc':>6s} {'time':>7s}")
    rows = []
    for strat in PAPER_STRATEGIES:
        pl, persist = plans(strat.grad_ckpt)
        for policy in policies:
            try:
                r = run_iteration(pl, persist, strat, policy, ndp=4,
                                  trainable_fraction=tf(strat.lora_rank),
                                  capacity=capacity)
                print(f"{strat.name:28s} {policy:16s} "
                      f"{r.peak_reserved/GB:7.2f}G {r.frag_at_peak/GB:5.2f}G "
                      f"{r.peak_allocated/GB:5.2f}G {r.time_s:6.2f}s")
                rows.append((strat.name, policy, r))
            except MemoryError:
                print(f"{strat.name:28s} {policy:16s} OOM")
    red, dt = [], []
    by = {(s, p): r for s, p, r in rows}
    for s in {s for s, _, _ in rows}:
        if (s, "none") in by and (s, "after_inference") in by:
            a, b = by[(s, "none")], by[(s, "after_inference")]
            red.append(1 - b.peak_reserved / a.peak_reserved)
            dt.append(b.time_s / a.time_s - 1)
    if red:
        print(f"-> empty_cache: avg consumption -{100*sum(red)/len(red):.0f}% "
              f"(paper -25%), time +{100*sum(dt)/len(dt):.1f}% (paper +2%)")
    return rows


def bench_table1():
    t0 = time.time()
    rows1 = _grid("Table 1a: DeepSpeed-Chat-style, OPT-1.3b/350m, 24 GB",
                  "opt_1_3b", "opt_350m", 24 * GB)
    rows2 = _grid("Table 1b: ColossalChat-style, GPT2-xl/medium, 24 GB",
                  "gpt2_xl", "gpt2_medium", 24 * GB)
    _csv("table1", (time.time() - t0) * 1e6, f"rows={len(rows1)+len(rows2)}")


def bench_table2():
    """Appendix C, Table 2: A100-80GB node, bigger models, +-ZeRO-3."""
    from repro.core import PAPER_STRATEGIES, run_iteration
    t0 = time.time()
    print("\n== Table 2: A100-80GB grid ==")
    strat_by = {s.name: s for s in PAPER_STRATEGIES}
    print(f"{'model':12s} {'strategy':8s} {'policy':16s} {'reserved':>8s} "
          f"{'frag':>6s} {'alloc':>6s}")
    for actor, critic in [("opt_1_3b", "opt_350m"),
                          ("opt_6_7b", "opt_350m"),
                          ("llama2_7b", "opt_350m")]:
        plans, tf = _study(actor, critic, GEN_LENS[:3])
        for sname in ("None", "ZeRO-3"):
            strat = strat_by[sname]
            pl, persist = plans(False)
            for policy in ("none", "after_inference"):
                try:
                    r = run_iteration(pl, persist, strat, policy,
                                      ndp=4,
                                      trainable_fraction=tf(strat.lora_rank),
                                      capacity=80 * GB)
                    print(f"{actor:12s} {sname:8s} {policy:16s} "
                          f"{r.peak_reserved/GB:7.2f}G "
                          f"{r.frag_at_peak/GB:5.2f}G "
                          f"{r.peak_allocated/GB:5.2f}G")
                except MemoryError:
                    print(f"{actor:12s} {sname:8s} {policy:16s} OOM")
    _csv("table2", (time.time() - t0) * 1e6)


def bench_placement():
    """§3.3: where to call empty_cache."""
    from repro.core import PAPER_STRATEGIES, run_iteration
    t0 = time.time()
    plans, tf = _study("opt_1_3b", "opt_350m", GEN_LENS)
    pl, persist = plans(False)
    print("\n== empty_cache placement ablation (None strategy) ==")
    res = {}
    for policy in ("none", "after_inference", "after_training", "after_all"):
        r = run_iteration(pl, persist, PAPER_STRATEGIES[0], policy, ndp=4,
                          trainable_fraction=tf(PAPER_STRATEGIES[0].lora_rank))
        res[policy] = r
        print(f"{policy:16s} reserved {r.peak_reserved/GB:6.2f}G "
              f"frag {r.frag_at_peak/GB:5.2f}G time {r.time_s:6.2f}s")
    d = res
    print(f"-> after_inference ~ after_all "
          f"({d['after_inference'].peak_reserved/GB:.2f} vs "
          f"{d['after_all'].peak_reserved/GB:.2f}); both << none "
          f"({d['none'].peak_reserved/GB:.2f}) — paper insight §3.3")
    _csv("placement", (time.time() - t0) * 1e6)


def bench_generation():
    """App. B: HF-style growing-cache generation vs our static donated
    cache (the framework's beyond-paper default)."""
    from repro.configs import get_config
    from repro.core import (PAPER_STRATEGIES, build_rlhf_phases,
                            lora_trainable_fraction, run_iteration)
    t0 = time.time()
    actor, critic = get_config("opt_1_3b"), get_config("opt_350m")
    tf = lora_trainable_fraction(actor, 128)
    print("\n== generation memory: naive growing cache vs static cache ==")
    for naive, label in ((True, "naive (HF dynamic cache)"),
                         (False, "framework (static donated)")):
        ph, persist = build_rlhf_phases(actor, critic, gen_len=256,
                                        naive_generation=naive)
        r = run_iteration([ph], persist, PAPER_STRATEGIES[0], "none", ndp=4,
                          trainable_fraction=tf, capacity=None)
        recs = {p.name: p for p in r.phase_records}
        growth = (recs["rollout_decode"].reserved_end
                  - recs["rollout_prefill"].reserved_end)
        print(f"{label:28s} decode reserved growth {growth/GB:6.2f}G "
              f"(cudaMallocs {r.n_cuda_malloc})")
    _csv("generation", (time.time() - t0) * 1e6)


def bench_kernels():
    """Microbench: XLA flash twin vs dense attention (wall time, CPU)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ref import attention_ref
    from repro.models.flash import flash_sdpa
    t0 = time.time()
    print("\n== kernel microbench (CPU wall time; Pallas kernels are")
    print("   TPU-targeted, validated in interpret mode in tests/) ==")
    B, S, H, K, D = 1, 2048, 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
    f_dense = jax.jit(lambda q, k, v: attention_ref(q, k, v))
    f_flash = jax.jit(lambda q, k, v: flash_sdpa(q, k, v, True, 0, 512))
    for name, fn in (("attention_dense", f_dense),
                     ("attention_flash_xla", f_flash)):
        fn(q, k, v).block_until_ready()
        t1 = time.time()
        n = 3
        for _ in range(n):
            fn(q, k, v).block_until_ready()
        us = (time.time() - t1) / n * 1e6
        _csv(name, us, f"S={S}")
    _csv("kernels", (time.time() - t0) * 1e6)


def bench_paged():
    """Beyond-paper: dense [B, capacity] vs paged KV cache under ragged
    request lengths — reserved KV bytes and us/token of the serving loop."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import Model
    from repro.serving import ContinuousBatcher
    t0 = time.time()
    cfg = dataclasses.replace(
        get_config("llama3_2_3b").smoke(), num_layers=2, d_model=128,
        d_ff=256, vocab_size=64, num_heads=4, num_kv_heads=2, head_dim=32)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    slots, capacity = 4, 128
    # ragged workload: short completions against a worst-case capacity
    gens = rng.randint(8, 48, size=10)
    print("\n== paged vs dense KV cache (ragged serving workload) ==")
    rows = {}
    for backend in ("dense", "paged"):
        cb = ContinuousBatcher(model, cfg, params, slots=slots,
                               capacity=capacity, temperature=0.0, seed=0,
                               cache_backend=backend, page_size=16)
        for g in gens:
            cb.submit(rng.randint(0, 64, size=8), int(g))
        t1 = time.time()
        done = cb.run_until_drained()
        dt = time.time() - t1
        toks = sum(len(r.out_tokens) for r in done)
        if backend == "paged":
            reserved = cb.pm.stats.peak_pages_in_use * cb.pm.page_bytes
        else:
            reserved = cb.kv_reserved_bytes()
        rows[backend] = (reserved, dt / toks * 1e6, toks)
        print(f"{backend:6s} reserved_kv {reserved/2**20:7.2f} MiB  "
              f"{dt/toks*1e6:8.1f} us/tok  ({toks} tokens)")
    dense_r, paged_r = rows["dense"][0], rows["paged"][0]
    assert paged_r < dense_r, "paged must reserve less than dense"
    print(f"-> paged reserves {100*(1-paged_r/dense_r):.0f}% less KV than "
          f"the dense [B, capacity] layout")
    _gate("kv_reduction_pct", 100 * (1 - paged_r / dense_r), "higher")
    _gate("paged_reserved_bytes", paged_r, "lower")
    _csv("paged", (time.time() - t0) * 1e6,
         f"dense_bytes={dense_r};paged_bytes={paged_r}")


def bench_decode():
    """Beyond-paper: the DESIGN.md "Fast decode path" — greedy decode
    tokens/s with MTP self-speculative decoding off vs on (bit-identity
    asserted), plus the compile-bucket ladder's cache hit rate on ragged
    serving traffic and paged-KV bytes per generated token.

    The draft heads only help if they predict the trunk, so the bench
    first trains the tiny model on a deterministic cyclic-token task
    (t_{i+1} = (t_i + 1) mod V) with the chained MTP loss at window=1 —
    the identity attention mask is exactly the function ``mtp_draft``
    evaluates at decode time."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import Model
    from repro.optim import make_optimizer
    from repro.rlhf import Rollout
    from repro.serving import ContinuousBatcher
    from repro.steps import lm_loss, mtp_loss

    t0 = time.time()
    V, SPEC_K = 64, 3
    cfg = dataclasses.replace(
        get_config("llama3_2_3b").smoke(), num_layers=2, d_model=128,
        d_ff=256, vocab_size=V, num_heads=4, num_kv_heads=2, head_dim=32,
        mtp_depth=SPEC_K)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer("adamw")
    opt_state = opt.init(params)
    S, TB = 32, 8

    def loss_fn(p, tokens):
        logits, _aux, h = model.forward(p, {"tokens": tokens})
        mask = jnp.ones_like(tokens)
        loss = lm_loss(logits, tokens, mask)
        for d, lg in enumerate(model.mtp_chain_logits(p, h, tokens,
                                                      window=1), start=1):
            loss = loss + mtp_loss(lg, tokens, mask, offset=d + 1) / SPEC_K
        return loss

    @jax.jit
    def train_step(p, st, tokens):
        loss, g = jax.value_and_grad(loss_fn)(p, tokens)
        p, st = opt.update(g, st, p, 3e-3)
        return p, st, loss

    print("\n== decode fast path: bucket ladder + MTP self-speculation ==")
    rng = np.random.RandomState(0)
    for _ in range(300):
        start = rng.randint(0, V, size=(TB, 1))
        params, opt_state, loss = train_step(
            params, opt_state, jnp.asarray((start + np.arange(S)[None]) % V))
    print(f"mini-train: cyclic-token task, 300 steps, "
          f"final loss {float(loss):.4f}")

    # -- greedy rollout tokens/s, speculation off vs on --------------------
    B, P, G = 4, 8, 64
    prompts = jnp.asarray(
        (rng.randint(0, V, size=(B, 1)) + np.arange(P)[None]) % V)
    key = jax.random.PRNGKey(1)
    runs = {}
    for name, kw in (("vanilla", {}),
                     ("spec", {"spec_decode": True, "spec_k": SPEC_K})):
        ro = Rollout(model, cfg, capacity=P + G, temperature=0.0, top_k=0,
                     **kw)
        res = ro.generate(params, {"tokens": prompts}, G, key)   # compile
        best = float("inf")
        for _ in range(7):      # best-of: robust to CI-runner load spikes
            t1 = time.time()
            res = ro.generate(params, {"tokens": prompts}, G, key)
            jax.block_until_ready(res.tokens)
            best = min(best, time.time() - t1)
        tps = B * G / best
        runs[name] = (ro, res, tps)
        print(f"{name:8s} {tps:8.0f} tok/s greedy (B={B}, gen={G})")
    (_, rv, tps_v), (ro_s, rs, tps_s) = runs["vanilla"], runs["spec"]
    assert bool(jnp.array_equal(rv.tokens, rs.tokens)), \
        "speculative greedy tokens diverged from vanilla"
    assert float(jnp.max(jnp.abs(rv.logp - rs.logp))) < 1e-5
    st = ro_s.spec_stats
    accept = st["accepted"] / max(st["drafted"], 1)
    speedup = tps_s / tps_v
    # deterministic companion to the (timing-noisy) speedup: forwards per
    # emitted token — vanilla is G, spec is the verify-step count
    dispatch_red = G / st["steps"]
    print(f"-> spec speedup {speedup:.2f}x wall ({dispatch_red:.2f}x fewer "
          f"decode dispatches), draft accept rate {100*accept:.0f}% "
          f"({st['steps']} verify steps; bit-identical)")
    assert speedup >= 1.2, f"spec decode speedup {speedup:.2f}x < 1.2x"
    assert accept >= 0.90, f"trained draft accept rate {accept:.2f} < 0.90"

    # -- bucketed batcher on ragged traffic: hit rate + bytes/token --------
    cb = ContinuousBatcher(model, cfg, params, slots=4, capacity=64,
                           temperature=0.0, seed=0, cache_backend="paged",
                           page_size=16, capture_buckets=(8, 16, 32),
                           spec_decode=True, spec_k=SPEC_K)
    for _ in range(12):
        plen = int(rng.randint(4, 28))
        cb.submit((int(rng.randint(0, V)) + np.arange(plen)) % V,
                  int(rng.randint(8, 32)))
    done = cb.run_until_drained()
    toks = sum(len(r.out_tokens) for r in done)
    hit = cb.compile_cache.hit_rate
    kv_bpt = cb.pm.stats.peak_pages_in_use * cb.pm.page_bytes / toks
    print(f"ragged traffic: {len(done)} requests, {toks} tokens, "
          f"compile cache {cb.compile_cache.stats()}")
    print(f"-> hit rate {100*hit:.1f}% (acceptance: >=95%), "
          f"paged KV {kv_bpt:.0f} bytes/token")
    assert hit >= 0.95, f"compile-cache hit rate {hit:.2f} < 0.95"
    assert cb.compile_cache.recompiles == 0, "post-warmup recompile"

    _gate("spec_speedup", speedup, "higher")
    _gate("dispatch_reduction", dispatch_red, "higher")
    _gate("draft_accept_rate", accept, "higher")
    _gate("compile_cache_hit_rate", hit, "higher")
    _gate("kv_bytes_per_token", kv_bpt, "lower")
    _result()["metrics"]["tokens_per_s"] = {
        "vanilla": round(tps_v, 1), "spec": round(tps_s, 1)}
    _csv("decode", (time.time() - t0) * 1e6,
         f"speedup={speedup:.2f};accept={accept:.2f};hit_rate={hit:.2f};"
         f"kv_bytes_per_token={kv_bpt:.0f}")


def bench_serving():
    """Beyond-paper: trace-driven multi-tenant serving A/B — the same
    shared-system-prompt traffic (3 tenants, weighted 4:2:1, deterministic
    arrivals) through the continuous batcher with the prefix cache off and
    on. Asserts greedy outputs are bit-identical between the legs, a
    token-level prefix-hit-rate >= 0.9, and >= 40% lower peak reserved KV
    on the cached leg; gates hit rate, KV reduction, tokens/s and p99
    admission latency against the committed baseline."""
    import dataclasses

    import jax

    from benchmarks.serving_traffic import run_trace, synthetic_trace
    from repro.configs import get_config
    from repro.models import Model
    from repro.obs import RunTelemetry
    from repro.serving import ContinuousBatcher

    t0 = time.time()
    cfg = dataclasses.replace(
        get_config("llama3_2_3b").smoke(), num_layers=2, d_model=128,
        d_ff=256, vocab_size=64, num_heads=4, num_kv_heads=2, head_dim=32)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    trace = synthetic_trace(cfg.vocab_size)
    print("\n== multi-tenant serving traffic (prefix cache A/B) ==")
    legs = {}
    tel = None
    # the trace replay is deterministic, so repeats only re-measure wall
    # time — best-of-3 keeps the wall-clock gates out of CI-runner noise
    # (same trick as bench_decode's best-of-7 decode timing)
    for prefix_cache in (False, True):
        repeats = 3 if prefix_cache else 1
        best_tps, best_p99 = 0.0, float("inf")
        for rep in range(repeats):
            # telemetry only on the measured (cached) leg: registry gauges,
            # attribution owner tables and the Chrome trace come from it
            tel = (RunTelemetry.create(sim_delta=False)
                   if prefix_cache else None)
            cb = ContinuousBatcher(
                model, cfg, params, slots=4, capacity=96, temperature=0.0,
                seed=0, cache_backend="paged", page_size=16, num_pages=48,
                capture_buckets=(4, 16, 80), prefix_cache=prefix_cache,
                telemetry=tel,
                tenant_weights={"tenant0": 4.0, "tenant1": 2.0,
                                "tenant2": 1.0})
            res = run_trace(cb, trace)
            best_tps = max(best_tps, res.tokens_per_s)
            best_p99 = min(best_p99, res.p99_admission_latency_s())
        legs[prefix_cache] = (cb, res, best_tps, best_p99)
        reserved = cb.pm.stats.peak_pages_in_use * cb.pm.page_bytes
        print(f"prefix_cache={str(prefix_cache):5s}: {res.n_tokens} tokens "
              f"{best_tps:8.0f} tok/s  hit {cb.prefix_hit_rate():.3f}"
              f"  peak_reserved {reserved} B  "
              f"p99_admit {best_p99*1e3:.1f} ms")

    (cb_off, res_off, _, _) = legs[False]
    (cb_on, res_on, tps_on, p99_on) = legs[True]
    # greedy decoding must not notice the cache: same rid order, same tokens
    for a, b in zip(res_off.requests, res_on.requests):
        assert a.out_tokens == b.out_tokens, \
            f"prefix cache changed rid {a.rid}: {a.out_tokens} vs " \
            f"{b.out_tokens}"
    hit = cb_on.prefix_hit_rate()
    assert hit >= 0.9, f"prefix hit rate {hit:.3f} < 0.9"
    r_off = cb_off.pm.stats.peak_pages_in_use * cb_off.pm.page_bytes
    r_on = cb_on.pm.stats.peak_pages_in_use * cb_on.pm.page_bytes
    kv_red = 100 * (1 - r_on / r_off)
    assert kv_red >= 40, f"reserved-KV reduction {kv_red:.0f}% < 40%"
    # the registry gauge the scheduler emits must agree with the API
    g = tel.registry.get("serving_prefix_hit_rate")
    assert g is not None and abs(g.value() - hit) < 1e-9
    print(f"-> hit rate {hit:.3f}, reserved KV -{kv_red:.0f}% "
          f"({r_off} -> {r_on} B), outputs bit-identical")

    _gate("prefix_hit_rate", hit, "higher")
    _gate("kv_reduction_pct", kv_red, "higher")
    _gate("tokens_per_s", tps_on, "higher")
    _gate("p99_admission_latency_s", p99_on, "lower")
    _result()["metrics"]["reserved_kv_bytes"] = {
        "prefix_cache_off": int(r_off), "prefix_cache_on": int(r_on)}
    _result()["metrics"]["prefix_cache"] = {
        "hits": cb_on.pm.stats.n_prefix_hits,
        "queries": cb_on.pm.stats.n_prefix_queries,
        "evictions": cb_on.pm.stats.n_prefix_evictions}
    _result()["metrics"]["per_tenant_p50_admission_steps"] = {
        t: sorted(ls)[len(ls) // 2] for t, ls in (
            (t, [res_on.latency_steps[r.rid] for r in res_on.requests
                 if r.tenant == t and r.rid in res_on.latency_steps])
            for t in ("tenant0", "tenant1", "tenant2")) if ls}
    _trace(tel.tracer.chrome_trace())
    _artifact("ATTRIB_serving.json",
              {"owners": tel.attribution.snapshot().table(),
               "metrics": tel.registry.snapshot()})
    _csv("serving", (time.time() - t0) * 1e6,
         f"hit_rate={hit:.3f};kv_reduction_pct={kv_red:.0f};"
         f"p99_admit_s={p99_on:.4f}")


def bench_hydra():
    """Beyond-paper: the shared-base hydra engine (one frozen trunk +
    per-role LoRA adapters, rank 128) vs the four-model separate path —
    REAL live device bytes from PhaseMemoryManager, plus the greedy
    merged-rollout == unmerged-argmax identity check."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.rlhf import RLHFConfig, RLHFTrainer, live_device_bytes
    from repro.rlhf.reward import make_target_token_reward

    t0 = time.time()
    cfg = dataclasses.replace(
        get_config("llama3_2_3b").smoke(), num_layers=2, d_model=1024,
        d_ff=2048, vocab_size=64, num_heads=8, num_kv_heads=4, head_dim=128)
    print("\n== hydra engine vs four-model pipeline (live device bytes) ==")
    init_bytes, tr = {}, None
    for engine in ("separate", "hydra"):
        rl = RLHFConfig(prompt_len=8, gen_len=16, lr=1e-3, critic_lr=1e-3,
                        kl_coef=0.0, top_k=0, engine=engine, lora_rank=128)
        before = live_device_bytes()
        tr = RLHFTrainer(cfg, cfg, rl, jax.random.PRNGKey(0),
                         reward_fn=make_target_token_reward(7))
        init_bytes[engine] = live_device_bytes() - before
        print(f"{engine:9s} live after init {init_bytes[engine]/2**20:8.2f} "
              f"MiB")
        if engine == "separate":
            # only measured for the A/B — free the four full models before
            # the hydra trainer allocates. The trainer's engine-bound
            # closures capture self (a reference cycle), so refcounting
            # alone frees nothing: collect explicitly, or the drop lands
            # nondeterministically inside the hydra measurement window.
            del tr
            import gc
            gc.collect()
    acc = tr.engine.memory_accounting()
    for layout in ("separate", "hydra"):
        tot = {k: sum(r[k] for r in acc[layout].values())
               for k in ("params", "opt", "grad")}
        print(f"  accounting[{layout:9s}] params "
              f"{tot['params']/2**20:8.2f} MiB  opt "
              f"{tot['opt']/2**20:8.2f} MiB  grad "
              f"{tot['grad']/2**20:8.2f} MiB")
    red = 1 - init_bytes["hydra"] / init_bytes["separate"]
    print(f"-> hydra holds {100*red:.0f}% less live memory after init "
          f"(acceptance: >=40%)")
    assert red >= 0.40, f"hydra must cut live bytes >=40%, got {100*red:.0f}%"

    # greedy identity: 2 PPO steps to move the adapters off zero-delta, then
    # a greedy merged rollout must equal the unmerged forward's argmax path
    from repro.rlhf import Rollout
    P = tr.rl.prompt_len
    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(key, (4, P), 0, cfg.vocab_size)
    for s in range(2):
        tr.train_step(prompts, jax.random.fold_in(key, s))
    greedy_ro = Rollout(tr.actor, cfg, capacity=P + tr.rl.gen_len,
                        temperature=0.0, top_k=0)
    ro = greedy_ro.generate(tr.base_params, {"tokens": prompts},
                            tr.rl.gen_len, key,
                            adapter=tr.actor_state["params"])
    logits, _, _ = tr.actor.forward(tr.base_params, {"tokens": ro.tokens},
                                    adapter=tr.actor_state["params"])
    greedy = jnp.argmax(logits[:, P - 1:-1], -1)   # position P-1+t scores t
    gen = ro.tokens[:, P:]
    match = bool(jnp.array_equal(greedy, gen))
    print(f"-> merged-rollout greedy tokens == unmerged argmax: {match}")
    assert match, "merged rollout diverged from unmerged argmax path"
    _gate("reduction_pct", 100 * red, "higher")
    _csv("hydra", (time.time() - t0) * 1e6,
         f"separate_bytes={init_bytes['separate']};"
         f"hydra_bytes={init_bytes['hydra']};reduction_pct={100*red:.0f}")


def bench_offload():
    """Beyond-paper: the phase-aware host-offload subsystem
    (repro.offload). Part 1 replays the paper-scale hydra engine
    (OPT-1.3b trunk + OPT-350m critic slot, rank 128, grad-ckpt — the
    paper's all-enabled remat regime) through the allocator simulator
    across the offload grid and asserts the >=25% peak-live-HBM floor for
    offload="all". Part 2 runs the real trainer A/B at CPU scale:
    bit-identical greedy rollout tokens and exactly equal 2-step PPO
    losses between offload="all" and "none", plus the check that the
    simulator's per-phase live-bytes curve brackets the measured one."""
    import dataclasses
    import gc

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import (MemoryStrategy, OFFLOAD_LEVELS,
                            build_rlhf_phases, run_iteration)
    from repro.rlhf import (RLHFConfig, RLHFTrainer, Rollout,
                            live_device_bytes)
    from repro.rlhf.reward import make_target_token_reward

    t0 = time.time()
    # ---- part 1: paper scale through the simulator -----------------------
    actor, critic = get_config("opt_1_3b"), get_config("opt_350m")
    ph, persist = build_rlhf_phases(actor, critic, gen_len=256,
                                    engine="hydra", lora_rank=128,
                                    grad_ckpt=True)
    print("\n== offload grid: paper scale, hydra engine (simulator) ==")
    print(f"{'offload':10s} {'peak_live':>9s} {'peak_host':>9s} "
          f"{'swapped':>8s} {'time':>7s}")
    peaks = {}
    for level in OFFLOAD_LEVELS:
        r = run_iteration(ph, persist,
                          MemoryStrategy("None", grad_ckpt=True,
                                         offload=level),
                          "none", ndp=4, trainable_fraction=1.0,
                          capacity=None)
        peaks[level] = r.peak_allocated
        print(f"{level:10s} {r.peak_allocated/GB:8.2f}G "
              f"{r.peak_host_bytes/GB:8.2f}G {r.swapped_bytes/GB:7.2f}G "
              f"{r.time_s:6.2f}s")
    red = 1 - peaks["all"] / peaks["none"]
    print(f"-> offload=all cuts peak live HBM {100*red:.0f}% "
          f"(acceptance: >=25%)")
    assert red >= 0.25, f"offload=all must cut >=25%, got {100*red:.0f}%"

    # ---- part 2: runtime A/B (tiny hydra config) -------------------------
    # bf16 params to match the dtype build_rlhf_phases forces, so part 3's
    # bracket compares like against like
    cfg = dataclasses.replace(
        get_config("llama3_2_3b").smoke(), num_layers=2, d_model=1024,
        d_ff=2048, vocab_size=64, num_heads=8, num_kv_heads=4, head_dim=128,
        param_dtype="bfloat16")
    P, G, B = 8, 16, 4
    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(key, (B, P), 0, cfg.vocab_size)
    print("\n== offload runtime A/B (live device bytes per phase) ==")
    metrics, tokens, peak_live = {}, {}, {}
    trainers = {}
    for level in ("none", "all"):
        gc.collect()
        base_live = live_device_bytes()
        rl = RLHFConfig(prompt_len=P, gen_len=G, lr=1e-3, critic_lr=1e-3,
                        kl_coef=0.0, top_k=0, engine="hydra", lora_rank=128,
                        offload=level)
        tr = RLHFTrainer(cfg, cfg, rl, jax.random.PRNGKey(0),
                         reward_fn=make_target_token_reward(7))
        ms = [tr.train_step(prompts, jax.random.fold_in(key, s))
              for s in range(2)]
        metrics[level] = ms
        recs = tr.memory.records[-8:]         # final iteration
        peak_live[level] = max(r["live_bytes"] for r in recs) - base_live
        for r in recs:
            print(f"  [{level:4s}] {r['phase']:16s} live "
                  f"{(r['live_bytes']-base_live)/2**20:8.2f} MiB  host "
                  f"{r['host_bytes']/2**20:8.2f} MiB")
        # greedy rollout from the trained state (merged path)
        ro = Rollout(tr.actor, cfg, capacity=P + G, temperature=0.0,
                     top_k=0).generate(
            tr.base_params, {"tokens": prompts}, G, key,
            adapter=tr.actor_state["params"])
        tokens[level] = ro.tokens
        if level == "none":
            # greedy identity vs the unmerged argmax path
            logits, _, _ = tr.actor.forward(
                tr.base_params, {"tokens": ro.tokens},
                adapter=tr.actor_state["params"])
            greedy = jnp.argmax(logits[:, P - 1:-1], -1)
            assert bool(jnp.array_equal(greedy, ro.tokens[:, P:])), \
                "merged greedy rollout diverged from unmerged argmax"
            del tr, logits
        else:
            trainers[level] = tr
        del ms, recs, ro
    run_red = 1 - peak_live["all"] / peak_live["none"]
    print(f"-> runtime peak live bytes: -{100*run_red:.0f}% "
          f"(offload=all vs none)")
    assert bool(jnp.array_equal(tokens["none"], tokens["all"])), \
        "greedy rollout tokens differ between offload levels"
    for a, b in zip(metrics["none"], metrics["all"]):
        for k in ("loss", "vf_loss", "ppo_loss"):
            assert a[k] == b[k], (k, a[k], b[k])
    print("-> greedy rollout tokens bit-identical; 2-step PPO losses equal")

    # ---- part 3: simulator curve brackets the measured one ---------------
    tr = trainers["all"]
    sph, spersist = build_rlhf_phases(
        cfg, cfg, batch=B, prompt_len=P, gen_len=G, engine="hydra",
        lora_rank=128, grad_ckpt=(cfg.remat == "full"), min_bytes=2048)
    sr = run_iteration(sph, spersist,
                       MemoryStrategy("None", offload="all"), "none",
                       ndp=1, trainable_fraction=1.0, capacity=None)
    sim = {rec.name: rec for rec in sr.phase_records}
    name_map = {"rollout": "rollout_decode"}
    print("\n== simulator brackets runtime (per-phase live bytes) ==")
    gc.collect()
    slack = 4 << 20     # python-side scalars/rng keys the sim doesn't see
    for r in tr.memory.records[-8:]:
        srec = sim[name_map.get(r["phase"], r["phase"])]
        measured = r["live_bytes"]
        # bracket: [post-eviction floor, within-phase allocation peak] —
        # boundary records sit near the floor, the mid-rollout sample
        # (merged weights live) under the peak
        lo, hi = srec.allocated_end, srec.alloc_peak
        ok = lo * 0.8 - slack <= measured <= hi * 1.2 + slack
        print(f"  {r['phase']:16s} sim [{lo/2**20:8.2f}, {hi/2**20:8.2f}] "
              f"MiB  measured {measured/2**20:8.2f} MiB  "
              f"{'ok' if ok else 'OUT'}")
        assert ok, (r["phase"], lo, measured, hi)
    print("-> simulator's predicted live-HBM curve brackets the runtime")
    _gate("sim_reduction_pct", 100 * red, "higher")
    _gate("runtime_reduction_pct", 100 * run_red, "higher")
    _csv("offload", (time.time() - t0) * 1e6,
         f"sim_reduction_pct={100*red:.0f};"
         f"runtime_reduction_pct={100*run_red:.0f}")


def bench_obs():
    """Unified runtime telemetry acceptance: a 2-step PPO run (hydra
    engine, offload=all, zero_stage=3) must produce a Perfetto-loadable
    Chrome trace with >= one span per canonical runtime phase carrying the
    measured peak bytes AND the traced simulator's prediction, a JSONL that
    ``launch/report.py`` renders with zero recomputation, and a telemetry
    tax <= 2% of wall time (tracer self-accounting). PR 8 extends the
    acceptance to the attribution observatory: every phase span's owner
    table must sum (with the unattributed residue) EXACTLY to the
    measured live bytes, the residue must stay <= 10% of live at every
    boundary, and a forced low watermark must produce a valid
    flight-recorder dump naming the top owners."""
    import dataclasses
    import tempfile

    import jax

    from repro.configs import get_config
    from repro.core.phases import RUNTIME_RLHF_PHASE_SEQUENCE
    from repro.launch.report import render
    from repro.obs import FlightRecorder, RunTelemetry
    from repro.rlhf import RLHFConfig, RLHFTrainer
    from repro.rlhf.reward import make_target_token_reward
    from repro.sharding import ShardedContext

    t0 = time.time()
    print("\n== runtime telemetry (hydra, offload=all, zero_stage=3) ==")
    cfg = dataclasses.replace(
        get_config("llama3_2_3b").smoke(), num_layers=2, d_model=128,
        d_ff=256, vocab_size=64, num_heads=4, num_kv_heads=2, head_dim=32,
        param_dtype="bfloat16")
    rl = RLHFConfig(prompt_len=8, gen_len=16, lr=1e-3, critic_lr=1e-3,
                    kl_coef=0.0, top_k=0, engine="hydra", lora_rank=16,
                    offload="all")
    shard = ShardedContext.create(1, zero_stage=3)
    # forced watermark: on CPU the recorder calibrates capacity from its
    # first check (step-1 mid-rollout peak, merged weights live), so 0.9
    # deterministically breaches at step 2's rollout sample — the
    # memory-rich point — after a full iteration of phase history
    fl = FlightRecorder(watermark=0.9, ring=128)
    tel = RunTelemetry.create(engine="hydra", offload="all", zero_stage=3,
                              flight=fl)
    tr = RLHFTrainer(cfg, cfg, rl, jax.random.PRNGKey(0),
                     reward_fn=make_target_token_reward(7), shard=shard,
                     telemetry=tel)
    key = jax.random.PRNGKey(1)
    for s in range(2):
        prompts = jax.random.randint(jax.random.fold_in(key, s),
                                     (4, rl.prompt_len), 0, cfg.vocab_size)
        tr.train_step(prompts, jax.random.fold_in(key, 100 + s))
    wall = time.time() - t0

    # one span per canonical phase, measured AND simulated peaks attached
    by_phase = {}
    for sp in tel.tracer.spans:
        if sp.cat == "phase":
            by_phase.setdefault(sp.name, []).append(sp)
    for ph in RUNTIME_RLHF_PHASE_SEQUENCE:
        name = "rollout" if ph == "rollout" else ph
        assert by_phase.get(name), f"no phase span for {ph}"
        args = by_phase[name][-1].args
        assert "measured_peak_bytes" in args, (name, args)
        assert "sim_peak_bytes" in args, \
            f"{name}: simulator prediction missing from phase span"
    n_phase = sum(len(v) for v in by_phase.values())
    print(f"phase spans: {n_phase} over {len(by_phase)} phases "
          f"(2 iterations x {len(RUNTIME_RLHF_PHASE_SEQUENCE)})")
    assert n_phase == 2 * len(RUNTIME_RLHF_PHASE_SEQUENCE)
    n_off = sum(1 for sp in tel.tracer.spans if sp.cat == "offload")
    assert n_off > 0, "offload=all run emitted no offload spans"

    # -- attribution observatory acceptance --------------------------------
    # exactness: at every boundary, sum(owner table) + residue must equal
    # the measured live bytes EXACTLY (the snapshot walk IS the
    # measurement — one jax.live_arrays() pass classifies and totals)
    phase_spans = [sp for sp in tel.tracer.spans if sp.cat == "phase"]
    worst_resid = 0.0
    attrib_tables = {}
    for sp in phase_spans:
        a = sp.args
        assert "attrib" in a, f"{sp.name}: no owner table on phase span"
        total = sum(a["attrib"].values()) + a["attrib_unattributed"]
        assert total == a["measured_bytes"], \
            (sp.name, total, a["measured_bytes"])
        resid = a["attrib_unattributed"] / max(a["measured_bytes"], 1)
        worst_resid = max(worst_resid, resid)
        attrib_tables[sp.name] = {"owners": a["attrib"],
                                  "unattributed": a["attrib_unattributed"],
                                  "measured_bytes": a["measured_bytes"],
                                  "sim_delta": a.get("attrib_sim_delta")}
    n_sim_owner = sum(1 for sp in phase_spans
                      if "attrib_sim_delta" in sp.args)
    # the mid-phase samples sit at the phase PEAKS (hydra rollout decode:
    # merged weights + ZeRO gather copies live) — exactness and the <=10%
    # residue bound must hold there too, not just at boundary troughs
    n_samples = 0
    for ev in tel.tracer.instants:
        a = ev["args"]
        if ev["cat"] != "phase" or "attrib" not in a:
            continue
        n_samples += 1
        total = sum(a["attrib"].values()) + a["attrib_unattributed"]
        assert total == a["measured_bytes"], (ev["name"], total)
        resid = a["attrib_unattributed"] / max(a["measured_bytes"], 1)
        worst_resid = max(worst_resid, resid)
    assert n_samples > 0, "no mid-phase attribution samples recorded"
    print(f"attribution: {len(phase_spans)} spans + {n_samples} peak "
          f"samples exact (sum owners + residue == measured), worst "
          f"residue {100*worst_resid:.2f}% of live, {n_sim_owner} spans "
          f"carry per-owner sim deltas")
    assert worst_resid <= 0.10, \
        f"unattributed residue {100*worst_resid:.1f}% > 10% of live"
    assert n_sim_owner > 0, "no span joined the sim's per-owner ledger"

    # forced watermark must have produced a valid forensic dump
    assert fl.dumps, "forced watermark=0.25 produced no flight dump"
    dump = fl.dumps[0]
    assert dump["schema"] == "flight-recorder/v1" and \
        dump["trigger"] == "watermark", dump["trigger"]
    top3 = dump["owners_ranked"][:3]
    assert len(top3) >= 3 and all(dump["owners"][o] > 0 for o in top3), top3
    assert dump["top_buffers"] and dump["phase_history"], \
        "dump missing top_buffers/phase_history forensics"
    print(f"flight dump: trigger={dump['trigger']} top owners {top3}")
    _artifact("FLIGHT_obs.json", dump)
    _artifact("ATTRIB_obs.json", attrib_tables)

    # per-jitted-program compiled-memory accounting joined the registry
    n_compiled = sum(
        1 for m in tel.registry.snapshot()
        if m["name"].startswith("compiled_") and m["name"].endswith("_bytes"))
    print(f"compiled-memory gauges: {n_compiled}")
    assert n_compiled > 0, "no compiled_*_bytes program accounting recorded"

    # Chrome-trace schema: loadable JSON, required keys per event type
    chrome = tel.tracer.chrome_trace()
    chrome = json.loads(json.dumps(chrome))        # round-trip
    assert isinstance(chrome["traceEvents"], list) and chrome["traceEvents"]
    for ev in chrome["traceEvents"]:
        assert ev["ph"] in ("M", "X", "i", "C"), ev
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and isinstance(ev["ts"], (int, float))
    _trace(chrome)

    # report renders the JSONL without recomputation
    with tempfile.NamedTemporaryFile("w", suffix=".jsonl",
                                     delete=False) as f:
        jsonl_path = f.name
    tel.write_jsonl(jsonl_path)
    report = render(jsonl_path)
    for ph in RUNTIME_RLHF_PHASE_SEQUENCE:
        assert ("rollout" if ph == "rollout" else ph) in report
    print(report.split("\n\n")[1])                 # the per-phase table
    os.unlink(jsonl_path)

    ov_pct = 100 * tel.tracer.overhead_fraction(wall)
    print(f"-> telemetry self-time {tel.tracer.self_time_s*1e3:.2f} ms "
          f"of {wall:.2f} s wall = {ov_pct:.3f}% (acceptance: <=2%)")
    assert ov_pct <= 2.0, f"telemetry overhead {ov_pct:.2f}% > 2%"
    # the 2% gate now covers the attribution walk too: snapshot() charges
    # its walk time to tracer.self_time_s
    _gate("telemetry_overhead_pct", ov_pct, "lower")
    _gate("phase_spans_per_iteration", n_phase / 2, "higher")
    _gate("attrib_unattributed_pct", 100 * worst_resid, "lower")
    _csv("obs", (time.time() - t0) * 1e6,
         f"phase_spans={n_phase};offload_spans={n_off};"
         f"overhead_pct={ov_pct:.3f};"
         f"attrib_unattributed_pct={100*worst_resid:.2f}")


def bench_grpo():
    """Beyond-paper: GRPO (2 models) vs PPO (4 models) peak memory."""
    from repro.configs import get_config
    from repro.core import (PAPER_STRATEGIES, build_rlhf_phases,
                            lora_trainable_fraction, run_iteration)
    from repro.core.phases import build_grpo_phases
    t0 = time.time()
    actor, critic = get_config("opt_1_3b"), get_config("opt_350m")
    tf = lora_trainable_fraction(actor, 128)
    strat = PAPER_STRATEGIES[0]
    print("\n== GRPO vs PPO memory (same token budget) ==")
    for name, builder in (
            ("PPO", lambda gl: build_rlhf_phases(
                actor, critic, gen_len=gl, naive_generation=True)),
            ("GRPO", lambda gl: build_grpo_phases(
                actor, batch=2, group_size=1, gen_len=gl,
                naive_generation=True))):
        plans = []
        for gl in (180, 256, 199, 243):
            ph, persist = builder(gl)
            plans.append(ph)
        for policy in ("none", "after_inference"):
            r = run_iteration(plans, persist, strat, policy, ndp=4,
                              trainable_fraction=tf)
            print(f"{name:5s} {policy:16s} reserved {r.peak_reserved/GB:6.2f}G"
                  f" frag {r.frag_at_peak/GB:5.2f}G"
                  f" alloc {r.peak_allocated/GB:6.2f}G")
    _csv("grpo_vs_ppo", (time.time() - t0) * 1e6)


def bench_zero():
    """Beyond-paper: the mesh-sharded ZeRO RLHF engines, validated on 8
    forced host devices (subprocess — the flag must be set before jax
    initializes). Asserts 2-step PPO bit-identity between ndp=1 and ndp=8
    on BOTH engines, dense+paged rollout identity under the mesh, the
    ZeRO-3 per-device param+opt cut (<=30% of replicated for the separate
    engine), and that the simulator's traced ndp=8 curve brackets the
    measured one. See benchmarks/zero_smoke.py."""
    import subprocess
    t0 = time.time()
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-m", "benchmarks.zero_smoke"],
                       env=env, cwd=root, capture_output=True, text=True,
                       timeout=3000)
    print("\n== mesh-sharded ZeRO RLHF smoke (8 forced host devices) ==")
    out = r.stdout or ""
    print("\n".join(l for l in out.splitlines()
                    if not l.startswith("ZERO_METRICS")))
    assert r.returncode == 0, f"zero_smoke failed:\n{out}\n{r.stderr[-3000:]}"
    metrics = json.loads(
        [l for l in out.splitlines()
         if l.startswith("ZERO_METRICS ")][-1][len("ZERO_METRICS "):])
    assert metrics["separate_biteq"] and metrics["hydra_biteq"]
    assert metrics["sim_bracket_ok"]
    assert metrics["separate_state_bytes_zero3"] <= \
        0.30 * metrics["separate_state_bytes_ndp1"]
    # per-layer FSDP gathers: the compiled-program transient peak must
    # drop from the whole stacked tree to ~one layer period, and the
    # traced simulator term must bracket the measured delta
    assert metrics["layer_transient_ok"]
    assert metrics["transient_sim_bracket_ok"]
    assert metrics["telemetry_overhead_pct"] <= 2.0
    _gate("telemetry_overhead_pct", metrics["telemetry_overhead_pct"],
          "lower")
    _gate("separate_zero3_cut_pct", metrics["separate_zero3_cut_pct"],
          "higher")
    _gate("hydra_zero3_cut_pct", metrics["hydra_zero3_cut_pct"], "higher")
    _gate("gather_transient_cut_pct", metrics["gather_transient_cut_pct"],
          "higher")
    _csv("zero", (time.time() - t0) * 1e6,
         f"separate_cut_pct={metrics['separate_zero3_cut_pct']};"
         f"hydra_cut_pct={metrics['hydra_zero3_cut_pct']};"
         f"gather_transient_cut_pct={metrics['gather_transient_cut_pct']}")


def bench_tp():
    """Beyond-paper: tensor parallelism as a runtime axis composed with
    ZeRO, validated on 8 forced host devices (subprocess — the flag must
    be set before jax initializes). Asserts 2-step PPO loss ALLCLOSE
    (reduction-order drift only — TP splits contractions, so the pure-DP
    bit-identity bar does not apply; DESIGN.md §9) between ndp=1,ntp=1 and
    ndp=2,ntp=2 on BOTH engines, dense+paged rollout identity from the
    TP-sharded state (paged KV pool kv-head-sharded), the pure-TP
    per-device param+opt cut (>=40% at ntp=2, ZeRO off), and that the
    simulator's traced dp x tp curve brackets the measured one. See
    benchmarks/tp_smoke.py."""
    import subprocess
    t0 = time.time()
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-m", "benchmarks.tp_smoke"],
                       env=env, cwd=root, capture_output=True, text=True,
                       timeout=3000)
    print("\n== TP x ZeRO sharded RLHF smoke (8 forced host devices) ==")
    out = r.stdout or ""
    print("\n".join(l for l in out.splitlines()
                    if not l.startswith("TP_METRICS")))
    assert r.returncode == 0, f"tp_smoke failed:\n{out}\n{r.stderr[-3000:]}"
    metrics = json.loads(
        [l for l in out.splitlines()
         if l.startswith("TP_METRICS ")][-1][len("TP_METRICS "):])
    assert metrics["separate_tp_allclose"] and metrics["hydra_tp_allclose"]
    assert metrics["separate_rollout_identical"]
    assert metrics["hydra_rollout_identical"]
    assert metrics["sim_bracket_ok"]
    assert metrics["separate_tp_cut_pct"] >= 40.0
    _gate("separate_tp_cut_pct", metrics["separate_tp_cut_pct"], "higher")
    _gate("separate_tp_zero3_cut_pct",
          metrics["separate_tp_zero3_cut_pct"], "higher")
    _gate("hydra_tp_zero3_cut_pct",
          metrics["hydra_tp_zero3_cut_pct"], "higher")
    _csv("tp", (time.time() - t0) * 1e6,
         f"separate_tp_cut_pct={metrics['separate_tp_cut_pct']};"
         f"separate_tp_zero3_cut_pct={metrics['separate_tp_zero3_cut_pct']};"
         f"hydra_tp_zero3_cut_pct={metrics['hydra_tp_zero3_cut_pct']}")


def bench_roofline():
    root = os.path.join(os.path.dirname(__file__), "..")
    path = os.path.join(root, "roofline_final.json")
    if not os.path.exists(path):
        path = os.path.join(root, "roofline_baseline.json")
    if not os.path.exists(path):
        print("\n(roofline_baseline.json not present — run "
              "python -m repro.launch.roofline)")
        return
    recs = json.load(open(path))
    print("\n== Roofline baselines (single-pod 16x16; see EXPERIMENTS.md) ==")
    print(f"{'arch':25s} {'shape':12s} {'compute':>8s} {'memory':>8s} "
          f"{'coll':>8s} {'dominant':>10s} {'useful':>7s}")
    for r in recs:
        if "error" in r:
            print(f"{r['arch']:25s} {r['shape']:12s} ERROR")
            continue
        print(f"{r['arch']:25s} {r['shape']:12s} {r['compute_s']:7.3f}s "
              f"{r['memory_s']:7.3f}s {r['collective_s']:7.3f}s "
              f"{r['dominant']:>10s} {r['useful_ratio']:6.3f}")


BENCHES = {
    "figure1": bench_figure1,
    "table1": bench_table1,
    "table2": bench_table2,
    "placement": bench_placement,
    "generation": bench_generation,
    "paged": bench_paged,
    "decode": bench_decode,
    "serving": bench_serving,
    "hydra": bench_hydra,
    "offload": bench_offload,
    "obs": bench_obs,
    "zero": bench_zero,
    "tp": bench_tp,
    "kernels": bench_kernels,
    "grpo": bench_grpo,
    "roofline": bench_roofline,
}

_DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "results")
_DEFAULT_BASELINES = os.path.join(os.path.dirname(__file__), "baselines")
_DEFAULT_HISTORY = os.path.join(os.path.dirname(__file__), "history")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", choices=list(BENCHES), default=None)
    ap.add_argument("--out-dir", default=_DEFAULT_OUT,
                    help="where BENCH_<name>.json result files are written")
    ap.add_argument("--check-baseline", action="store_true",
                    help="fail when a gated metric regresses >10%% vs the "
                         "committed benchmarks/baselines/BENCH_*.json")
    ap.add_argument("--baseline-dir", default=_DEFAULT_BASELINES)
    ap.add_argument("--emit-trace", action="store_true",
                    help="write a Chrome-trace TRACE_<name>.json sibling "
                         "next to every BENCH_<name>.json")
    ap.add_argument("--history-dir", default=_DEFAULT_HISTORY,
                    help="append one git-sha-stamped JSONL line per bench "
                         "to HISTORY_<name>.jsonl here (render with "
                         "launch/report.py --trend); '' disables")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    _EMIT_TRACE[0] = args.emit_trace
    print("name,us_per_call,derived")
    try:
        for name, fn in BENCHES.items():
            if args.only and name not in args.only:
                continue
            _CURRENT[0] = name
            try:
                if args.emit_trace:
                    from repro.obs import SpanTracer
                    bench_tr = SpanTracer()
                    with bench_tr.span(name, "bench"):
                        fn()
                    # a bench that recorded its own richer trace wins
                    TRACES.setdefault(name, bench_tr.chrome_trace())
                else:
                    fn()
            finally:
                _CURRENT[0] = None
    finally:
        # a failing bench must not lose the results of the ones that
        # completed — that is exactly when the artifacts matter
        write_results(args.out_dir)
        if args.history_dir:
            append_history(args.history_dir)
    if args.check_baseline:
        failures = check_baseline(args.baseline_dir)
        if failures:
            print(f"[bench] {failures} gated metric(s) regressed >10%")
            sys.exit(1)
        print("[bench] baseline gate passed")


if __name__ == "__main__":
    main()
