"""Serving driver: the configuration's paged ``ContinuousBatcher`` under the
mix's open-loop traffic.

Set-up draws the weights, builds the batcher, compiles (or loads) its
prefill buckets and decode program, and runs one short request per prefill
bucket through to the end, so that every program and eager helper the
window uses has run. Then it offers the mix, untimed, for the mix's
``warm_s`` seconds, so that the window opens on the slots and pages a
steady stream holds and not on an empty batcher: a request here lives for
tens of seconds. The window goes on with the same arrivals: it submits
each request when it is due and calls ``step`` while there is work; one
step admits what fits, runs one decode for every live slot and emits one
token per live slot. Times are the harness's clock at the end of the step
that emitted a token.

The end-to-end metrics are taken over the requests due inside the window
(the time to first token; one not served when the window closes counts at
its age) and over every gap between two tokens whose later token the
window emitted, requests of the pre-warm included.

When the window closes the program's state is freed, and a sample drawn
from the seed of the requests that finished inside the window, the longest
among them, is checked against the plain float32 reference. The number
compared, ``served_mean_gap``, is the mean over the sample's served tokens
of how far each token's reference logit lies below the reference's best at
its position (0 where the served token is the reference's greedy choice).
The widest such gap is printed beside it; it is not compared, because it
swings with the one nearest tie of each sample and does not separate the
program from the float8 control (PERF.md). With ``control="fp8"`` the
control takes the program's place in the comparison: at every position of
the same prompts and served tokens, the gap of the token that the
reference computed in float8 puts first.
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from bench import flops, reference, traffic, weights
from bench.common import Run, p95, peak_bytes
from bench.trace import WINDOW


def program_config(config: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=config["name"], family="dense", **config["model"])


def build(config: dict, seed: int):
    """(program config, weight shapes, batcher), warmed up."""
    import jax
    from repro.models import Model
    from repro.serving import ContinuousBatcher

    mcfg = program_config(config)
    model = Model(mcfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make_params(shapes, seed, mcfg.num_layers)
    s = config["serve"]
    cb = ContinuousBatcher(
        model, mcfg, params, slots=s["slots"], capacity=s["capacity"],
        temperature=s["temperature"], top_k=s["top_k"],
        seed=seed % 2**31, cache_backend="paged", page_size=s["page_size"],
        num_pages=s["num_pages"], capture_buckets=tuple(s["prefill_buckets"]),
        warmup=False, prefix_cache=s["prefix_cache"])
    cb.warmup(max_prompt_len=max(s["prefill_buckets"]))
    for r in traffic.warmup_requests(s["prefill_buckets"], mcfg.vocab_size):
        cb.submit(r.prompt, r.max_new_tokens)
    cb.run_until_drained()
    jax.block_until_ready(cb.pools)
    return mcfg, shapes, cb


def _quiet(_name):
    return contextlib.nullcontext()


class Offer:
    """One run's open-loop offer: the batcher, the requests and what became
    of each, on one clock whose origin is the moment the offer began.
    ``serve_until`` is called once for the pre-warm and once, counting, for
    the window."""

    def __init__(self, cb, reqs, dims: dict, clock, annotate=_quiet):
        n = len(reqs)
        self.cb, self.reqs, self.dims = cb, reqs, dims
        self.clock, self.annotate = clock, annotate
        self.handles = [None] * n
        self.seen = np.zeros(n, np.int64)
        self.admitted = np.full(n, np.nan)
        self.first = np.full(n, np.nan)
        self.done_at = np.full(n, np.nan)
        self.tok_times = [[] for _ in range(n)]
        self.by_rid = {}
        self.i = 0
        self.refused = 0
        self.late_s = 0.0
        # per window step: (time, queued, live slots, pages in use)
        self.occupancy = []
        self.c = {"steps": 0, "step_wall_s": 0.0, "model_flops": 0,
                  "decode_steps": 0, "decode_bytes": 0, "decode_flops": 0,
                  "prefill_tokens": 0, "decode_tokens": 0}
        self.t0 = clock()

    def now(self) -> float:
        return self.clock() - self.t0

    def _submit_due(self, now: float) -> None:
        reqs = self.reqs
        while self.i < len(reqs) and reqs[self.i].due_s <= now:
            r = reqs[self.i]
            self.late_s = max(self.late_s, now - r.due_s)
            with self.annotate("submit"):
                try:
                    h = self.cb.submit(r.prompt, r.max_new_tokens)
                except ValueError:
                    self.refused += 1
                    self.i += 1
                    continue
            self.handles[self.i] = h
            self.by_rid[h.rid] = self.i
            self.i += 1

    def serve_until(self, end: float, count: bool = False) -> None:
        """Submit what falls due and step while there is work, until the
        offer's clock reads ``end``; with ``count``, the steps go into the
        window's counters."""
        cb, c = self.cb, self.c
        while True:
            now = self.now()
            if now >= end:
                return
            self._submit_due(now)
            if not (cb.n_queued or any(r is not None for r in cb.active)):
                nxt = self.reqs[self.i].due_s if self.i < len(self.reqs) \
                    else end
                wait = min(nxt, end) - self.now()
                if wait > 0:
                    with self.annotate("idle"):
                        time.sleep(wait)
                continue
            ts = self.clock()
            with self.annotate("step"):
                done = cb.step()
            te = self.clock()
            t = te - self.t0
            contexts = []
            for r in [r for r in cb.active if r is not None] + done:
                k = self.by_rid[r.rid]
                new = len(r.out_tokens) - self.seen[k]
                if self.seen[k] == 0 and new:
                    self.first[k] = t
                    self.admitted[k] = ts - self.t0
                    if count:
                        c["prefill_tokens"] += len(r.prompt)
                        c["model_flops"] += flops.prefill_flops(
                            self.dims, len(r.prompt))
                    new -= 1
                if new:
                    contexts.append(len(r.prompt) + len(r.out_tokens) - 1)
                self.tok_times[k].extend([t] * int(len(r.out_tokens)
                                                   - self.seen[k]))
                self.seen[k] = len(r.out_tokens)
            for r in done:
                self.done_at[self.by_rid[r.rid]] = t
            if not count:
                continue
            c["steps"] += 1
            c["step_wall_s"] += te - ts
            self.occupancy.append(
                (t, cb.n_queued, sum(r is not None for r in cb.active),
                 cb.pm.num_pages - cb.pm.num_free_pages))
            if contexts:
                c["decode_steps"] += 1
                c["decode_tokens"] += len(contexts)
                f = flops.decode_flops(self.dims, contexts)
                c["decode_flops"] += f
                c["model_flops"] += f
                c["decode_bytes"] += flops.decode_bytes(self.dims, contexts)

    def reduce(self, warm_s: float, w0: float, w1: float):
        """The end-to-end values and counters of the window ``[w0, w1)``;
        the window's requests are those due from ``warm_s`` on."""
        due = np.array([r.due_s for r in self.reqs])
        mine = np.flatnonzero(due >= warm_s)
        mine = mine[mine < self.i]
        d = due[mine]
        first, adm = self.first[mine], self.admitted[mine]
        ttft = np.where(np.isnan(first), w1 - d, first - d)
        waits = np.where(np.isnan(adm), w1 - d, adm - d)
        gaps = [b - a for t in self.tok_times
                for a, b in zip(t, t[1:]) if w0 <= b <= w1]
        occ = np.array(self.occupancy, float).reshape(-1, 4)
        pool = self.cb.pm.num_pages
        e2e = {"ttft_p95_ms": p95(ttft) * 1e3 if len(ttft) else float("nan"),
               "itl_p95_ms": p95(gaps) * 1e3 if gaps else float("nan")}
        c = dict(self.c)
        c.update(
            window_s=w1 - w0, offered=len(mine), refused=self.refused,
            late_s=self.late_s, queue_wait_s=list(waits),
            finished=int(np.sum((self.done_at >= w0) & (self.done_at <= w1))),
            ttft_p50_ms=float(np.median(ttft)) * 1e3 if len(ttft)
            else float("nan"),
            itl_p50_ms=float(np.median(gaps)) * 1e3 if gaps
            else float("nan"),
            backlog_start=int(occ[0, 1]) if len(occ) else 0,
            backlog_end=self.cb.n_queued,
            live_mean=float(occ[:, 2].mean()) if len(occ) else 0.0,
            pool_share_mean=float(occ[:, 3].mean() / pool) if len(occ)
            else 0.0,
            pool_share_max=float(occ[:, 3].max() / pool) if len(occ)
            else 0.0,
            preempted=sum(h.n_preempted for h in self.handles
                          if h is not None),
            occupancy=[(round(t - w0, 3), int(q), int(lv), int(p))
                       for t, q, lv, p in occ])
        return e2e, c

    def sample(self, k: int, seed: int, w0: float, w1: float):
        """``k`` requests that finished inside the window, drawn from the
        seed, the longest among them: (prompt, served tokens) each."""
        done = [j for j, h in enumerate(self.handles) if h is not None
                and h.done and w0 <= self.done_at[j] <= w1]
        if not done:
            return []
        h = self.handles
        longest = max(done, key=lambda j: len(h[j].prompt)
                      + len(h[j].out_tokens))
        rest = [j for j in done if j != longest]
        rng = traffic.rng_for(seed, 7)
        pick = [longest] + list(rng.choice(rest, size=min(k - 1, len(rest)),
                                           replace=False))
        return [(h[j].prompt, list(h[j].out_tokens)) for j in pick]


def _free(cb):
    import jax
    for leaf in jax.tree.leaves((cb.pools, cb.params)):
        leaf.delete()
    cb.pools = cb.params = None


def served_gaps(config: dict, shapes, seed: int, sample, control=None):
    """For each (prompt, served tokens) in ``sample``: how far below the
    float32 reference's best logit each served token lies; and, with
    ``control``, the gaps of the tokens that reduced-precision reference
    puts first at the same positions (else None)."""
    import jax
    import jax.numpy as jnp
    params = weights.make_params(shapes, seed, config["model"]["num_layers"])
    ref = reference.Reference(params, config["model"])
    ctl = reference.Reference(params, config["model"], control) \
        if control else None
    pad = config["serve"]["capacity"]
    prog, ctrl = [], []
    for prompt, served in sample:
        P, N = len(prompt), len(served)
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        pos = jnp.arange(P - 1, P - 1 + N)
        lg = ref.logits(seq, pad)
        prog.append(np.asarray(reference.greedy_gaps(
            lg, jnp.asarray(served, jnp.int32), pos)))
        if ctl is not None:
            toks = reference.first_choice(ctl.logits(seq, pad), pos)
            ctrl.append(np.asarray(reference.greedy_gaps(lg, toks, pos)))
    jax.tree.map(lambda x: x.delete(), params)
    return prog, (ctrl if ctl is not None else None)


def gap_stats(gaps) -> dict:
    """The mean gap (the number a run compares), the widest, the 99th
    percentile and the share of tokens that are not the reference's
    greedy choice; an empty sample reads an infinite gap."""
    g = np.concatenate(gaps) if gaps else np.full(1, np.inf)
    return {"mean": float(g.mean()), "max": float(g.max()),
            "p99": float(np.quantile(g, 0.99)),
            "not_greedy": float((g > 0).mean())}


def run(cell, seed: int, seconds: float, trace: bool, ctx,
        control=None) -> Run:
    config, mix = cell.config, cell.mix
    mcfg, shapes, cb = build(config, seed)
    warm = mix["warm_s"]
    window = min(seconds, mix.get("trace_seconds", seconds)) if trace \
        else seconds
    reqs = traffic.open_loop(mix, warm + window, seed, mcfg.vocab_size)
    offer = Offer(cb, reqs, config["model"], ctx.clock, ctx.annotate)
    offer.serve_until(warm)
    ctx.setup_done()
    with ctx.window(trace):
        w0 = offer.now()
        with ctx.annotate(WINDOW):
            offer.serve_until(w0 + window, count=True)
        w1 = offer.now()
    peak = peak_bytes(ctx.devices)
    e2e, c = offer.reduce(warm, w0, w1)
    sample = offer.sample(mix["check_requests"], seed, w0, w1)
    _free(cb)
    del cb, offer
    gc.collect()
    prog, ctrl = served_gaps(config, shapes, seed, sample, control)
    c.update(checked_requests=len(sample),
             checked_tokens=sum(len(s) for _, s in sample),
             program_gap=gap_stats(prog))
    compared = c["program_gap"]
    if ctrl is not None:
        compared = c["control_gap"] = gap_stats(ctrl)
    c["served_max_gap"] = compared["max"]
    return Run(e2e=e2e,
               checks={"served_mean_gap": (compared["mean"],
                                           cell.limits["served_mean_gap"])},
               attempted=c["offered"], failed=c["refused"], peak_bytes=peak,
               counters=c)
