"""Continuous-batching serving scheduler with selectable KV-cache backends.

Two cache layouts behind one admit/decode/retire loop:

  * ``dense`` — the seed's fixed pool of B slots over a donated
    ``[B, capacity]`` rolling cache. Zero allocator churn, but every slot
    reserves ``capacity`` tokens of KV no matter how short its request.
  * ``paged`` — a vLLM-style global page pool (``repro.paged``): slots
    hold block tables instead of cache rows, pages are claimed as
    sequences grow and freed the step they retire, and admission is gated
    on free pages rather than free slots alone. When the pool runs dry
    mid-decode the youngest request is preempted (pages freed, request
    re-queued with its generated prefix for recompute) — the memory shape
    the paper's §3 inference-phase traces call for: reserved KV tracks
    *live tokens*, not worst-case capacity.

One jitted decode step serves all active slots either way; idle slots
decode into garbage that is masked out.

Two decode-path speed features ride on top (DESIGN.md "Fast decode path"):

  * ``capture_buckets`` — a compile-bucket ladder (``serving.buckets``):
    prompts pad to the smallest capture length >= P (masked exactly via
    per-row ``lengths``), paged decode batches pad to a live-slot bucket
    (idle rows carry ``position = -1`` and write nothing), and an explicit
    warmup pass at construction compiles every bucket before traffic
    arrives. The compile cache tracks hits/misses/recompiles per
    ``(kind, backend, bucket)`` key and feeds ``serving_*`` metrics.
  * ``spec_decode`` — MTP self-speculative greedy decoding: draft
    ``spec_k`` tokens per slot from the model's MTP chain, verify all
    drafts in ONE batched forward, accept the greedy-consistent prefix.
    Output is bit-identical to vanilla greedy decoding by construction
    (every emitted token is the verify forward's own argmax); drafts only
    move the accept rate. Greedy-only (``temperature == 0, top_k == 0``).

Multi-tenant serving features (paged backend):

  * ``prefix_cache`` — cross-request prefix sharing: committed prompt
    pages are content-hash indexed in the ``PageManager`` and a new
    request whose prompt shares the prefix reuses them with a refcount
    bump, prefilling only the *suffix* (bucketed on suffix length). With
    the cache on, **every** prefill — cold included — runs through
    ``Model.paged_prefill_suffix``, so a hash hit is bit-identical to a
    cold prefill by construction. ``update_params`` bumps the pool's
    weight version and invalidates every cached prefix, so RLHF weight
    updates never serve stale KV.
  * per-tenant fairness — requests carry a ``tenant`` label; admission
    runs weighted round-robin over per-tenant FIFO queues using virtual
    time (``vtime += cost / weight``) with an anti-starvation aging term,
    so a heavy tenant cannot starve a light one and every queued request
    is admitted in bounded time. Preemption picks the victim holding the
    most *exclusively owned* pages (shared prefix pages survive their
    victim and keep serving siblings).

Every submit and step writes ``serve.*`` spans with their counts as
stats (DESIGN.md §4) into the profiler's trace, telemetry bundle or not;
a bundle also keeps them. The stats are host values, read from state the
step keeps anyway, so the spans add no device sync.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import OrderedDict, deque
from typing import Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import Model
from repro.obs.tracer import ProfilerSpans
from repro.rlhf.rollout import place_kv_tp, sample_token, spec_verify_step
from repro.sharding import ctx as shctx
from repro.serving.buckets import BucketLadder, CompileCache


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # [P] int32
    max_new_tokens: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    n_preempted: int = 0
    t_submit: float = 0.0        # perf_counter at submit(); admission latency
    tenant: str = "default"
    step_submit: int = 0         # batcher step at submit(); aging clock
    n_cached_tokens: int = 0     # prompt tokens served from the prefix cache
    # on t_submit's clock, at the first admission (a re-admission after
    # preemption keeps them): admitted, and its first token on the host
    t_admitted: Optional[float] = None
    t_first_token: Optional[float] = None


class ContinuousBatcher:
    def __init__(self, model: Model, cfg: ModelConfig, params, *,
                 slots: int = 4, capacity: int = 128,
                 temperature: float = 1.0, top_k: int = 0,
                 eos_id: Optional[int] = None, seed: int = 0,
                 cache_backend: str = "dense", page_size: int = 16,
                 num_pages: Optional[int] = None, telemetry=None,
                 capture_buckets: Optional[Sequence[int]] = None,
                 spec_decode: bool = False, spec_k: int = 2,
                 warmup: bool = True, prefix_cache: bool = False,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 aging: float = 1.0, mesh=None):
        assert cache_backend in ("dense", "paged"), cache_backend
        # TP mesh (DESIGN.md §9): serving params arrive model-sharded from
        # the trainer's compute layout, the KV pool/cache commits sharded
        # over the kv-head axis, and every jitted program (prefill, decode,
        # spec verify) traces under ``ctx.use_mesh`` so its "model"
        # constraint hints resolve. None = the historical single-device /
        # pure-DP layout, byte-for-byte.
        self.mesh = mesh
        assert not (prefix_cache and cache_backend != "paged"), \
            "prefix caching needs the paged backend"
        self.telemetry = telemetry          # obs.RunTelemetry | None
        # the serve.* spans go into the profiler's trace on every step; a
        # telemetry bundle also keeps them (DESIGN.md §4)
        self.spans = telemetry.tracer if telemetry is not None \
            else ProfilerSpans()
        # memory observatory: owner registration for the attribution
        # engine, the run's flight recorder, and per-jit-program
        # compiled-memory stats joined to CompileCache keys
        self.flight = getattr(telemetry, "flight", None)
        self.attributor = None
        self.compiled_memory: dict = {}
        if telemetry is not None:
            from repro.obs import MemoryAttributor
            at = telemetry.attribution
            if at is None:
                at = telemetry.attribution = MemoryAttributor()
            at.register("serving_params", lambda: self.params)
            at.register("kv_cache", lambda: getattr(self, "caches", None))
            at.register("kv_pool", lambda: getattr(self, "pools", None))
            at.register("spec_state", lambda: getattr(self, "h_last", None))
            self.attributor = at
        self.model, self.cfg, self.params = model, cfg, params
        self.B, self.capacity = slots, capacity
        self.temperature, self.top_k, self.eos_id = temperature, top_k, eos_id
        self.backend = cache_backend
        self.prefix_cache = prefix_cache
        # per-tenant FIFO queues under weighted round-robin admission;
        # single-tenant traffic degenerates to the old global FIFO
        self.queues: "OrderedDict[str, Deque[Request]]" = OrderedDict()
        self.tenant_weights: Dict[str, float] = dict(tenant_weights or {})
        self.aging = aging
        self._vtime: Dict[str, float] = {}
        self._prefix_tokens_hit = 0
        self._prefix_tokens_total = 0
        self.active: List[Optional[Request]] = [None] * slots
        self.pos = np.zeros(slots, np.int64)        # next absolute position
        self.last_tok = np.zeros(slots, np.int64)
        self.key = jax.random.PRNGKey(seed)
        self.steps = 0
        self._next_rid = 0
        cache_dtype = jax.tree.leaves(params)[0].dtype

        # compile-bucket ladder + compile-cache accounting ------------------
        self.compile_cache = CompileCache()
        self.prefill_ladder = (BucketLadder(capture_buckets)
                               if capture_buckets else None)
        self.slot_ladder = None
        if capture_buckets and cache_backend == "paged":
            # live-slot buckets: ladder rungs clipped to the slot count
            # (dense rows cannot be subset — its decode stays full-B)
            self.slot_ladder = BucketLadder(
                [min(b, slots) for b in capture_buckets] + [slots])

        # speculative decoding ----------------------------------------------
        self.spec_decode = spec_decode
        self.spec_k = spec_k
        if spec_decode:
            assert model.supports_spec_decode(), \
                "spec decode needs a token-input attention-only model " \
                "with mtp_depth > 0"
            assert temperature <= 0.0 and top_k == 0, \
                "spec decode is greedy-only (temperature=0, top_k=0)"
            self.h_last = jnp.zeros((slots, cfg.d_model), cache_dtype)

        self._decode_kernel = False         # the paged decode kernel runs
        if cache_backend == "dense":
            self.caches = model.init_cache(slots, capacity, cache_dtype)
            self.caches = {"segments": place_kv_tp(self.caches, mesh),
                           "cross_kv": None}

            def decode(params, caches, tok, pos, key, live):
                logits, caches = model.decode_step(params, caches, tok, pos)
                t, _ = sample_token(key, logits, temperature=temperature,
                                    top_k=top_k)
                t = jnp.where(live, t, 0).astype(jnp.int32)
                return t, caches

            self._decode = jax.jit(decode, donate_argnums=(1,))
            # the lengths-masked prefill needs token inputs and attention
            # kinds; plain traffic on exotic models keeps the legacy path
            self._rich_prefill = self.prefill_ladder is not None or \
                spec_decode
            if self._rich_prefill:
                self._prefill = jax.jit(
                    lambda params, batch, lens: model.prefill(
                        params, batch, capacity, lengths=lens, return_h=True))
            else:
                self._prefill = jax.jit(
                    lambda params, batch: model.prefill(params, batch,
                                                        capacity))

            if spec_decode:
                def spec_step(params, caches, h_last, tok, pos, live):
                    return spec_verify_step(
                        model, spec_k,
                        lambda seq, positions: model.decode_multi(
                            params, caches, seq, positions),
                        params, h_last, tok, pos, live)

                self._spec = jax.jit(spec_step, donate_argnums=(1,))
        else:
            from repro.paged import PageManager, pool_token_bytes
            self.page_size = page_size
            self.max_blocks = -(-capacity // page_size)
            if num_pages is None:
                # default pool: what the dense layout would reserve
                num_pages = slots * self.max_blocks
            assert num_pages >= self.max_blocks, \
                "pool smaller than one max-length sequence"
            layer_token_bytes = pool_token_bytes(cfg, cache_dtype)
            self.pm = PageManager(
                num_pages, page_size,
                bytes_per_token=layer_token_bytes * cfg.num_layers)
            self.pools = place_kv_tp(
                model.init_paged_pools(num_pages, page_size, cache_dtype),
                mesh)
            from repro.paged.attention import decode_kernel_applies
            with shctx.use_mesh(mesh):
                # the attention path the decode program traces with
                self._decode_kernel = decode_kernel_applies(
                    jax.tree.leaves(self.pools)[0].shape[1:], cache_dtype)

            def decode(params, pools, tok, pos, bt, key, live):
                logits, pools = model.paged_decode_step(params, pools, tok,
                                                        pos, bt)
                t, _ = sample_token(key, logits, temperature=temperature,
                                    top_k=top_k)
                t = jnp.where(live, t, 0).astype(jnp.int32)
                return t, pools

            self._decode = jax.jit(decode, donate_argnums=(1,))
            self._prefill = jax.jit(
                lambda params, batch, pools, bt, lens: model.paged_prefill(
                    params, batch, pools, bt, lens, return_h=True),
                donate_argnums=(2,))
            if prefix_cache:
                # with the cache on, ALL prefills (cold included) run the
                # suffix program — hash hits are bit-identical to cold
                # prefills because they are the same computation
                self._prefill_suffix = jax.jit(
                    lambda params, batch, pools, bt, start, lens:
                        model.paged_prefill_suffix(
                            params, batch, pools, bt, start, lens,
                            return_h=True),
                    donate_argnums=(2,))

            if spec_decode:
                def spec_step(params, pools, h_last, tok, pos, bt, live):
                    return spec_verify_step(
                        model, spec_k,
                        lambda seq, positions: model.paged_decode_multi(
                            params, pools, seq, positions, bt),
                        params, h_last, tok, pos, live)

                self._spec = jax.jit(spec_step, donate_argnums=(1,))

        if warmup and self.prefill_ladder is not None:
            self.warmup()

    # -- warmup capture ------------------------------------------------------
    def warmup(self, max_prompt_len: Optional[int] = None) -> None:
        """Compile every ladder bucket before traffic arrives. Runs real
        calls on the live caches with only dead writes (``lengths = 0``,
        ``position = -1``), so it must precede admission — which it does:
        construction is the one moment both backends are guaranteed empty.
        After this, any post-warmup compile-cache miss is a recompile.
        Traces run under the TP mesh (if any), so every bucket's program
        bakes in the same model-sharded layout ``step`` serves with."""
        with shctx.use_mesh(self.mesh):
            self._warmup_inner(max_prompt_len)

    def _warmup_inner(self, max_prompt_len: Optional[int]) -> None:
        cc = self.compile_cache
        if self.prefill_ladder is not None:
            for Sb in self.prefill_ladder.up_to(
                    max_prompt_len or self.capacity):
                batch = {"tokens": jnp.zeros((1, Sb), jnp.int32)}
                lens = jnp.zeros((1,), jnp.int32)
                if self.backend == "dense":
                    self._prefill(self.params, batch, lens)
                    cc.warm(("prefill", self.backend, Sb))
                    self._note_compiled(("prefill", self.backend, Sb),
                                        self._prefill, self.params, batch,
                                        lens)
                elif self.prefix_cache:
                    bt = jnp.full((1, self.max_blocks), -1, jnp.int32)
                    start = jnp.zeros((1,), jnp.int32)
                    _, self.pools, _ = self._prefill_suffix(
                        self.params, batch, self.pools, bt, start, lens)
                    cc.warm(("prefill", self.backend, Sb))
                    self._note_compiled(("prefill", self.backend, Sb),
                                        self._prefill_suffix, self.params,
                                        batch, self.pools, bt, start, lens)
                else:
                    bt = jnp.full((1, self.max_blocks), -1, jnp.int32)
                    _, self.pools, _ = self._prefill(
                        self.params, batch, self.pools, bt, lens)
                    cc.warm(("prefill", self.backend, Sb))
                    self._note_compiled(("prefill", self.backend, Sb),
                                        self._prefill, self.params, batch,
                                        self.pools, bt, lens)
        for nb in (self.slot_ladder.up_to(self.B)
                   if self.slot_ladder is not None else (self.B,)):
            tok = jnp.zeros((nb,), jnp.int32)
            pos = jnp.full((nb,), -1, jnp.int32)
            live = jnp.zeros((nb,), bool)
            self.key, k = jax.random.split(self.key)
            if self.backend == "dense":
                if nb != self.B:
                    continue                    # dense decode is full-B only
                if self.spec_decode:
                    *_, self.caches = self._spec(
                        self.params, self.caches, self.h_last, tok, pos,
                        live)
                    cc.warm(self._decode_key(nb))
                    self._note_compiled(self._decode_key(nb), self._spec,
                                        self.params, self.caches,
                                        self.h_last, tok, pos, live)
                else:
                    _, self.caches = self._decode(
                        self.params, self.caches, tok, pos, k, live)
                    cc.warm(self._decode_key(nb))
                    self._note_compiled(self._decode_key(nb), self._decode,
                                        self.params, self.caches, tok, pos,
                                        k, live)
            else:
                bt = jnp.full((nb, self.max_blocks), -1, jnp.int32)
                if self.spec_decode:
                    h = jnp.zeros((nb, self.cfg.d_model),
                                  self.h_last.dtype)
                    *_, self.pools = self._spec(
                        self.params, self.pools, h, tok, pos, bt, live)
                    cc.warm(self._decode_key(nb))
                    self._note_compiled(self._decode_key(nb), self._spec,
                                        self.params, self.pools, h, tok,
                                        pos, bt, live)
                else:
                    _, self.pools = self._decode(
                        self.params, self.pools, tok, pos, bt, k, live)
                    cc.warm(self._decode_key(nb))
                    self._note_compiled(self._decode_key(nb), self._decode,
                                        self.params, self.pools, tok, pos,
                                        bt, k, live)
        cc.finish_warmup()

    def _decode_key(self, nb: int):
        kind = "spec" if self.spec_decode else "decode"
        extents = (nb, self.spec_k + 1) if self.spec_decode else (nb,)
        return (kind, self.backend) + extents

    def _note_compiled(self, key, fn, *args) -> None:
        """Join this CompileCache key with its program's compiled-memory
        stats (XLA ``memory_analysis``): temp/arg/output bytes land in the
        registry under ``program=<key>`` and in ``self.compiled_memory``
        — so every bucket rung, and any post-warmup recompile, carries
        its memory cost. Lowering only traces; no execution."""
        if self.telemetry is None or key in self.compiled_memory:
            return
        from repro.obs import record_compiled_memory
        stats = record_compiled_memory(
            self.telemetry.registry, ":".join(str(k) for k in key),
            fn, *args)
        if stats is not None:
            self.compiled_memory[key] = stats

    def _compiling(self, key, fn=None, *args):
        """Count ``key`` in the compile cache; returns the context to run
        its call in. A miss after warm-up is a recompile, and the context
        is its ``serve.recompile`` span, so the trace shows the step and
        the call that compiled. With telemetry a miss is also an instant,
        and ``fn(*args)`` has its compiled memory noted."""
        if self.compile_cache.lookup(key):
            return contextlib.nullcontext()
        name = ":".join(str(k) for k in key)
        if self.telemetry is not None:
            self.telemetry.tracer.instant(
                f"compile:{name}", "serving",
                recompile=self.compile_cache.warmed)
            if fn is not None:
                self._note_compiled(key, fn, *args)
        if not self.compile_cache.warmed:
            return contextlib.nullcontext()
        return self.spans.span("serve.recompile", "serving", key=name)

    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               tenant: str = "default") -> Request:
        prompt = np.asarray(prompt, np.int32)
        if self.backend == "paged" and \
                len(prompt) + max_new_tokens > self.capacity:
            # reject up front — an unservable request must not reach _admit
            raise ValueError(
                f"request needs {len(prompt) + max_new_tokens} tokens, "
                f"capacity is {self.capacity}")
        rid = self._next_rid
        with self.spans.span("serve.submit", "serving", rid=rid,
                             prompt=len(prompt)):
            req = Request(rid, prompt, max_new_tokens,
                          t_submit=time.perf_counter(), tenant=tenant,
                          step_submit=self.steps)
            self._next_rid += 1
            q = self.queues.get(tenant)
            if q is None:
                q = self.queues[tenant] = deque()
            if not q:
                # a tenant going from idle to backlogged re-enters at the
                # current service frontier: it must not bank idle time and
                # then monopolise admission catching up
                floor = min((self._vtime.get(t, 0.0)
                             for t, tq in self.queues.items()
                             if tq and t != tenant),
                            default=self._vtime.get(tenant, 0.0))
                self._vtime[tenant] = max(self._vtime.get(tenant, 0.0),
                                          floor)
            q.append(req)
        if self.telemetry is not None:
            self.telemetry.registry.counter(
                "serving_requests_total", "requests submitted").inc()
        return req

    @property
    def queue(self) -> List[Request]:
        """Flat view of all queued requests (oldest first), across tenants."""
        out = [r for q in self.queues.values() for r in q]
        out.sort(key=lambda r: r.rid)
        return out

    @property
    def n_queued(self) -> int:
        return sum(len(q) for q in self.queues.values())

    # -- paged helpers -------------------------------------------------------
    def _block_tables_for(self, sids: Sequence[Optional[int]]) -> jnp.ndarray:
        return jnp.asarray(self.pm.block_table_array(sids, self.max_blocks))

    def _slot_block_tables(self) -> jnp.ndarray:
        sids = [r.rid if r is not None else None for r in self.active]
        return self._block_tables_for(sids)

    def _apply_copies(self, copies):
        """Perform CoW page copies on every layer pool."""
        if not copies:
            return
        from repro.paged import copy_pages
        src = [s for s, _ in copies]
        dst = [d for _, d in copies]
        self.pools = [
            {k: jax.vmap(copy_pages, in_axes=(0, None, None))(pool, src, dst)
             for k, pool in seg.items()}
            for seg in self.pools]

    def _preempt_youngest(self, *, protect: Optional[int] = None) -> bool:
        """Free a victim request's pages and re-queue it; re-admission
        recomputes its prompt *plus* generated-so-far prefill (``prompt``
        itself is never mutated, so repeated preemption cannot duplicate
        tokens). The victim is the youngest active request; with the
        prefix cache on, ties in actual reclaim matter — the victim is
        the one holding the most *exclusively owned* pages (refcount 1),
        since shared prefix pages survive preemption and free nothing.
        Returns False if no victim is available."""
        victims = [s for s, r in enumerate(self.active)
                   if r is not None and s != protect]
        if not victims:
            return False
        if self.prefix_cache:
            s = max(victims, key=lambda s: (
                self.pm.reclaimable_pages(self.active[s].rid),
                self.active[s].rid))
        else:
            s = max(victims, key=lambda s: self.active[s].rid)
        req = self.active[s]
        self.pm.free_seq(req.rid)
        req.n_preempted += 1
        self.queues[req.tenant].appendleft(req)
        self.active[s] = None
        if self.telemetry is not None:
            self.telemetry.registry.counter(
                "serving_preemptions_total",
                "requests preempted on page-pool exhaustion").inc()
            self.telemetry.tracer.instant(
                f"preempt:r{req.rid}", "serving", rid=req.rid,
                n_preempted=req.n_preempted)
        return True

    # -- internals -----------------------------------------------------------
    def _pick_tenant(self) -> Optional[str]:
        """Weighted round-robin with anti-starvation aging: among
        backlogged tenants, pick the one minimising ``vtime[tenant] -
        aging * steps_waited`` for its queue head. Lowest virtual time
        (least service per unit weight) wins, and every waiting head's
        score falls by ``aging`` per step — so no tenant starves
        regardless of the weight ratio. Ties break on oldest request."""
        best, best_score = None, None
        for t, q in self.queues.items():
            if not q:
                continue
            score = (self._vtime.get(t, 0.0)
                     - self.aging * (self.steps - q[0].step_submit))
            if best is None or score < best_score or \
                    (score == best_score
                     and q[0].rid < self.queues[best][0].rid):
                best, best_score = t, score
        return best

    def _admit(self):
        for s in range(self.B):
            if self.active[s] is not None:
                continue
            tenant = self._pick_tenant()
            if tenant is None:
                break
            req = self.queues[tenant][0]
            # recompute prefill: original prompt plus anything generated
            # before a preemption (empty for fresh requests)
            full = np.concatenate(
                [req.prompt, np.asarray(req.out_tokens, np.int32)])
            P = len(full)
            # gate paged admission on pages for the prefill (with the
            # prefix cache, its non-cached tail) + first decode token
            if self.backend == "paged" and not (
                    self.pm.can_allocate_prefix(full, 1) if self.prefix_cache
                    else self.pm.can_allocate(P + 1)):
                break
            self.queues[tenant].popleft()
            # a re-admission's wait is a preemption artifact, not queueing
            first = req.n_preempted == 0
            waited = {}
            if first:
                req.t_admitted = time.perf_counter()
                waited["wait_us"] = round(
                    (req.t_admitted - req.t_submit) * 1e6)
            with self.spans.span("serve.admit", "serving", rid=req.rid,
                                 prompt=P, **waited) as sp:
                with self.spans.span("serve.prefill", "serving"):
                    lg, h1, Sb, n_cached = self._prefill_into(s, req, full)
                sp.set(bucket=Sb, cached=n_cached)
                # charge the tenant's virtual time for the service
                # footprint it just claimed (prompt + remaining budget)
                cost = P + req.max_new_tokens - len(req.out_tokens)
                self._vtime[tenant] = self._vtime.get(tenant, 0.0) \
                    + cost / max(self.tenant_weights.get(tenant, 1.0), 1e-9)
                with self.spans.span("serve.first_token", "serving"):
                    self.key, k = jax.random.split(self.key)
                    tok, _ = sample_token(k, lg, temperature=self.temperature,
                                          top_k=self.top_k)
                    tok = int(tok[0])
                if first:
                    req.t_first_token = time.perf_counter()
                self.active[s] = req
                self.pos[s] = P
                self.last_tok[s] = tok
                req.out_tokens.append(tok)
                if self.spec_decode:
                    self.h_last = self.h_last.at[s].set(h1[0])
            if self.telemetry is not None:
                reg = self.telemetry.registry
                reg.counter("serving_admissions_total",
                            "admissions incl. preemption re-admits").inc()
                if n_cached:
                    reg.counter(
                        "paged_prefix_hit_tokens_total",
                        "prompt tokens served from the prefix cache").inc(
                        n_cached)
                if first:
                    reg.histogram(
                        "serving_admission_latency_s",
                        "submit -> first admission wall time").observe(
                        req.t_admitted - req.t_submit)

    def _prefill_into(self, s: int, req: Request, full: np.ndarray):
        """Dispatch the prefill of ``full`` for ``req`` into slot ``s``
        (paged: its pages are claimed here; the caller checked they fit).
        Returns the logits, the trunk state (None where the program gives
        none), the prompt bucket and the tokens the prefix cache served."""
        P = len(full)
        lens = jnp.full((1,), P, jnp.int32)
        if self.backend == "paged" and self.prefix_cache:
            _, n_cached = self.pm.allocate_prefix(req.rid, full)
            suffix = full[n_cached:]
            # bucket on the *suffix* length — a hash hit compiles and
            # computes only the tail
            Sb = self.prefill_ladder.fit(len(suffix)) \
                if self.prefill_ladder else len(suffix)
            padded = np.zeros(Sb, np.int32)
            padded[:len(suffix)] = suffix
            start = jnp.full((1,), n_cached, jnp.int32)
            args = (self.params, {"tokens": jnp.asarray(padded)[None]},
                    self.pools, self._block_tables_for([req.rid]), start,
                    lens)
            with self._compiling(("prefill", self.backend, Sb),
                                 self._prefill_suffix, *args):
                lg, self.pools, h1 = self._prefill_suffix(*args)
            self.pm.commit_prefix(req.rid, full)
            self._prefix_tokens_hit += n_cached
            self._prefix_tokens_total += P
            req.n_cached_tokens = n_cached
            return lg, h1, Sb, n_cached
        # pad the prompt up to its capture bucket; the per-row ``lengths``
        # makes the padding exactly invisible
        Sb = self.prefill_ladder.fit(P) if self.prefill_ladder else P
        padded = np.zeros(Sb, np.int32)
        padded[:P] = full
        pb = {"tokens": jnp.asarray(padded)[None]}
        key = ("prefill", self.backend, Sb)
        if self.backend == "paged":
            self.pm.allocate(req.rid, P)
            args = (self.params, pb, self.pools,
                    self._block_tables_for([req.rid]), lens)
            with self._compiling(key, self._prefill, *args):
                lg, self.pools, h1 = self._prefill(*args)
            return lg, h1, Sb, 0
        args = (self.params, pb, lens) if self._rich_prefill \
            else (self.params, pb)
        with self._compiling(key, self._prefill, *args):
            out = self._prefill(*args)
        lg, caches1, h1 = out if self._rich_prefill else (*out, None)
        # write slot s of the pool from the batch-of-1 prefill
        self.caches["segments"] = jax.tree.map(
            lambda pool, new: pool.at[:, s:s + 1].set(new),
            self.caches["segments"], caches1["segments"])
        return lg, h1, Sb, 0

    def _retire(self):
        done = []
        with self.spans.span("serve.retire", "serving") as sp:
            for s, req in enumerate(self.active):
                if req is None:
                    continue
                hit_eos = (self.eos_id is not None
                           and req.out_tokens
                           and req.out_tokens[-1] == self.eos_id)
                if hit_eos or len(req.out_tokens) >= req.max_new_tokens:
                    req.done = True
                    done.append(req)
                    if self.backend == "paged":
                        self.pm.free_seq(req.rid)   # pages back to the pool
                    self.active[s] = None           # slot freed
            sp.set(done=len(done))
        return done

    def _grow_pages(self, n: int = 1):
        """Claim the page(s) each live slot's next ``n`` tokens will write
        (spec decode grows by ``spec_k + 1`` before the verify forward);
        preempt the youngest request when the pool is dry."""
        from repro.paged import PagePoolExhausted
        with self.spans.span("serve.grow_pages", "serving") as sp:
            claimed, preempted = self.pm.stats.n_page_alloc, 0
            for s in range(self.B):
                req = self.active[s]
                if req is None:
                    continue
                while True:
                    try:
                        self._apply_copies(self.pm.append_tokens(req.rid, n))
                        break
                    except PagePoolExhausted:
                        if not self._preempt_youngest(protect=s):
                            raise
                        preempted += 1
            sp.set(claimed=self.pm.stats.n_page_alloc - claimed,
                   preempted=preempted)

    # -- decode flavours -----------------------------------------------------
    def _append_emitted(self, s: int, emitted_toks) -> int:
        """Append a run of emitted tokens to slot ``s``'s request, stopping
        at EOS or the request's token budget. Returns the count actually
        taken (== position advance). Any truncation here retires the slot
        this very step, so the cache's extra draft entries — masked by
        position until overwritten — are never observed."""
        req = self.active[s]
        taken = 0
        for tokv in emitted_toks:
            req.out_tokens.append(int(tokv))
            taken += 1
            if (self.eos_id is not None and int(tokv) == self.eos_id) or \
                    len(req.out_tokens) >= req.max_new_tokens:
                break
        self.pos[s] += taken
        self.last_tok[s] = req.out_tokens[-1]
        return taken

    def _decode_rows(self, n_live: int) -> int:
        """Rows the decode program computes for ``n_live`` live slots: the
        live-slot bucket (paged with a ladder), else every slot."""
        if self.backend == "paged" and self.slot_ladder is not None:
            return self.slot_ladder.fit(n_live)
        return self.B

    def _decode_span(self, live_slots: List[int], rows: int,
                     kernel: bool = False):
        """The ``serve.decode`` span: rows computed and live, the KV
        positions the gather path's attention gathers, those the attention
        reads (the paged decode kernel's, ``_kv_read``, where ``kernel``;
        else the gathered ones) and those the live rows hold."""
        per_row = self.max_blocks * self.page_size \
            if self.backend == "paged" else self.capacity
        gathered = rows * per_row
        return self.spans.span(
            "serve.decode", "serving", rows=rows, live=len(live_slots),
            kv_gathered=gathered,
            kv_read=self._kv_read(live_slots) if kernel else gathered,
            kv_live=int(self.pos[live_slots].sum()) + len(live_slots))

    def _kv_read(self, live_slots: List[int]) -> int:
        """KV positions the paged decode kernel reads in a step: each lane
        tile of ``GROUP`` pages that holds a page a live row attends to
        (its blocks ``0 .. pos // page_size``), all of the tile."""
        from repro.paged.attention import GROUP
        bt = self.pm.block_table_array(
            [self.active[s].rid for s in live_slots], self.max_blocks)
        n = self.pos[live_slots] // self.page_size + 1
        pages = bt[np.arange(self.max_blocks)[None, :] < n[:, None]]
        tiles = np.unique(pages[pages >= 0] // GROUP)
        return int(tiles.size) * GROUP * self.page_size

    def _vanilla_decode(self, live_slots: List[int]) -> None:
        nb = self._decode_rows(len(live_slots))
        with self._decode_span(live_slots, nb, self._decode_kernel):
            self.key, k = jax.random.split(self.key)
            if self.backend == "paged" and self.slot_ladder is not None:
                # gather live rows into a slot bucket; pad rows are idle
                # (position -1 -> dropped writes, masked sampling)
                tok_in = np.zeros(nb, np.int64)
                pos_in = np.full(nb, -1, np.int64)
                tok_in[:len(live_slots)] = self.last_tok[live_slots]
                pos_in[:len(live_slots)] = self.pos[live_slots]
                sids = [self.active[s].rid for s in live_slots]
                sids += [None] * (nb - len(live_slots))
                live_v = jnp.asarray(np.arange(nb) < len(live_slots))
                with self._compiling(self._decode_key(nb)):
                    tok, self.pools = self._decode(
                        self.params, self.pools,
                        jnp.asarray(tok_in, jnp.int32),
                        jnp.asarray(pos_in, jnp.int32),
                        self._block_tables_for(sids), k, live_v)
                rows = range(len(live_slots))
            else:
                live = np.array([r is not None for r in self.active])
                tok_in = jnp.asarray(self.last_tok, jnp.int32)
                pos_in = jnp.asarray(self.pos, jnp.int32)
                with self._compiling(self._decode_key(nb)):
                    if self.backend == "paged":
                        pos_in = jnp.where(jnp.asarray(live), pos_in, -1)
                        tok, self.pools = self._decode(
                            self.params, self.pools, tok_in, pos_in,
                            self._slot_block_tables(), k, jnp.asarray(live))
                    else:
                        tok, self.caches = self._decode(
                            self.params, self.caches, tok_in, pos_in, k,
                            jnp.asarray(live))
                rows = live_slots
        with self.spans.span("serve.readback", "serving"):
            tok = np.asarray(tok)
        for j, s in zip(rows, live_slots):
            self._append_emitted(s, [tok[j]])

    def _spec_decode_step(self, live_slots: List[int]) -> None:
        """Draft + one batched verify + accept for all live slots."""
        n_live = len(live_slots)
        nb = self._decode_rows(n_live)
        with self._decode_span(live_slots, nb):
            if self.backend == "paged":
                tok_in = np.zeros(nb, np.int64)
                pos_in = np.full(nb, -1, np.int64)
                tok_in[:n_live] = self.last_tok[live_slots]
                pos_in[:n_live] = self.pos[live_slots]
                sids = [self.active[s].rid for s in live_slots]
                sids += [None] * (nb - n_live)
                live_v = jnp.asarray(np.arange(nb) < n_live)
                h_in = self.h_last[np.asarray(live_slots, np.int32)]
                if nb > n_live:
                    h_in = jnp.concatenate(
                        [h_in, jnp.zeros((nb - n_live,) + h_in.shape[1:],
                                         h_in.dtype)])
                with self._compiling(self._decode_key(nb)):
                    greedy, _lp, n_acc, h_new, self.pools = self._spec(
                        self.params, self.pools, h_in,
                        jnp.asarray(tok_in, jnp.int32),
                        jnp.asarray(pos_in, jnp.int32),
                        self._block_tables_for(sids), live_v)
                rows = range(n_live)
            else:
                live = np.array([r is not None for r in self.active])
                pos_in = np.where(live, self.pos, -1)
                with self._compiling(self._decode_key(nb)):
                    greedy, _lp, n_acc, h_new, self.caches = self._spec(
                        self.params, self.caches, self.h_last,
                        jnp.asarray(self.last_tok, jnp.int32),
                        jnp.asarray(pos_in, jnp.int32), jnp.asarray(live))
                rows = live_slots
        with self.spans.span("serve.readback", "serving"):
            greedy = np.asarray(greedy)
            n_acc_np = np.asarray(n_acc)
        reg = self.telemetry.registry if self.telemetry is not None else None
        for j, s in zip(rows, live_slots):
            pos_before = int(self.pos[s])
            take = int(n_acc_np[j]) + 1
            taken = self._append_emitted(s, greedy[j, :take])
            if self.backend == "paged":
                # drop the page claim for rejected (and untaken) drafts
                self.pm.truncate(self.active[s].rid, pos_before + taken)
            if reg is not None:
                reg.histogram(
                    "serving_specdec_accepted_len",
                    "accepted draft-prefix length per slot step").observe(
                    int(n_acc_np[j]))
                rejected = self.spec_k - int(n_acc_np[j])
                if rejected:
                    reg.counter(
                        "serving_specdec_drafts_rejected_total",
                        "draft tokens rejected by the verify step").inc(
                        rejected)
        # live rows of h_new are the trunk state at each slot's new last
        # accepted position; stale rows are refreshed at admission
        if self.backend == "paged":
            self.h_last = self.h_last.at[
                np.asarray(live_slots, np.int32)].set(h_new[:n_live])
        else:
            self.h_last = h_new

    def _count_step(self, n_tokens: int) -> None:
        """The step's registry metrics, all read from state the step
        already maintains. ``n_tokens`` is a delta of per-request token
        counts, so bucket padding and idle decode rows can never inflate
        it — only tokens appended to live (admitted, non-padded) requests
        count."""
        reg = self.telemetry.registry
        if n_tokens:
            reg.counter("serving_tokens_total",
                        "tokens generated (prefill-sampled + decoded)").inc(
                n_tokens)
        rec = reg.counter("serving_recompiles_total",
                          "post-warmup compile-cache misses (bucket escapes)")
        rec.inc(self.compile_cache.recompiles - rec.value())
        if self.backend == "paged":
            st = self.pm.stats
            reg.gauge("paged_pages_in_use",
                      "pages currently allocated").set(st.pages_in_use)
            reg.gauge("paged_pages_free", "pages currently free").set(
                self.pm.num_pages - st.pages_in_use)
            cow = reg.counter("paged_cow_copies_total",
                              "copy-on-write page copies")
            cow.inc(st.n_cow_copies - cow.value())
            forks = reg.counter("paged_forks_total", "sequence forks")
            forks.inc(st.n_forks - forks.value())
            if self.prefix_cache:
                reg.gauge("paged_prefix_cached_pages",
                          "zero-ref pages parked in the prefix LRU").set(
                    self.pm.num_cached_pages)
                reg.gauge("serving_prefix_hit_rate",
                          "cumulative prompt tokens served from cache").set(
                    self.prefix_hit_rate())
                hits = reg.counter("paged_prefix_hits_total",
                                   "pages reused via prefix match")
                hits.inc(st.n_prefix_hits - hits.value())
                ev = reg.counter("paged_prefix_evictions_total",
                                 "parked pages evicted under pool pressure")
                ev.inc(st.n_prefix_evictions - ev.value())

    def step(self) -> List[Request]:
        """Admit, one decode step for all live slots, retire. Returns the
        requests completed this step. With a flight recorder attached the
        step is watermark-checked, and a caught ``RESOURCE_EXHAUSTED``
        is captured (owner table, top buffers, recent serve steps) before
        the re-raise."""
        try:
            with shctx.use_mesh(self.mesh), self.spans.span(
                    "serve.step", "serving", step=self.steps,
                    queued=self.n_queued,
                    live=sum(r is not None for r in self.active)):
                done = self._step_inner()
        except Exception as e:
            fl = self.flight
            if fl is not None and fl.is_oom(e):
                from repro.rlhf.trainer import live_device_bytes
                at = self.attributor
                fl.record_oom(
                    e, snapshot_fn=(at.snapshot if at is not None else None),
                    live_bytes=live_device_bytes(), source="serving")
            raise
        if self.flight is not None:
            from repro.rlhf.trainer import per_device_live_bytes
            live = per_device_live_bytes()
            self.flight.note("serve_step", step=self.steps,
                             live_bytes=live, queued=self.n_queued,
                             kv_reserved_bytes=self.kv_reserved_bytes())
            at = self.attributor
            self.flight.check(
                live, snapshot_fn=(at.snapshot if at is not None else None),
                source="serving")
        return done

    def _step_inner(self) -> List[Request]:
        tokens_before = self._tokens_outstanding() \
            if self.telemetry is not None else 0
        self._admit()
        if self.backend == "paged":
            # spec decode writes up to k+1 tokens per slot this step
            self._grow_pages(self.spec_k + 1 if self.spec_decode else 1)
        # recompute after growth: preemption may have evicted a slot
        live_slots = [s for s, r in enumerate(self.active) if r is not None]
        if live_slots:
            if self.spec_decode:
                self._spec_decode_step(live_slots)
            else:
                self._vanilla_decode(live_slots)
        self.steps += 1
        done = self._retire()
        if self.telemetry is not None:
            self._count_step(self._tokens_outstanding()
                             + sum(len(r.out_tokens) for r in done)
                             - tokens_before)
        return done

    def _tokens_outstanding(self) -> int:
        """Generated tokens held by not-yet-retired requests (active or
        queued — preemption re-queues with tokens kept, so the per-step
        delta against this sum counts each token exactly once)."""
        return (sum(len(r.out_tokens) for r in self.active if r is not None)
                + sum(len(r.out_tokens) for r in self.queue))

    def run_until_drained(self, max_steps: int = 10_000) -> List[Request]:
        finished = []
        for _ in range(max_steps):
            finished.extend(self.step())
            if not self.n_queued and all(r is None for r in self.active):
                break
        return finished

    # -- weight updates ------------------------------------------------------
    def update_params(self, params, *,
                      weight_version: Optional[int] = None) -> None:
        """Swap serving weights (an RLHF iteration just updated the
        policy). With the prefix cache on this *must* be the entry point:
        the pool's weight version is bumped and every cached prefix is
        invalidated, so KV produced under old weights is never matched
        again. In-flight sequences are unaffected — callers swap weights
        between rollouts, when nothing is active."""
        self.params = params
        if self.backend == "paged" and self.prefix_cache:
            self.pm.set_weight_version(
                self.pm.weight_version + 1 if weight_version is None
                else weight_version)

    # -- introspection -------------------------------------------------------
    def prefix_hit_rate(self) -> float:
        """Cumulative fraction of admitted prompt tokens served from the
        prefix cache (0.0 before any admission)."""
        if not self._prefix_tokens_total:
            return 0.0
        return self._prefix_tokens_hit / self._prefix_tokens_total
    def kv_reserved_bytes(self) -> int:
        """Bytes of KV/state the backend currently reserves. Dense reserves
        the whole [B, capacity] cache up front (measured from the actual
        cache arrays, so Mamba/MLA states are counted correctly); paged
        reserves live pages."""
        if self.backend == "paged":
            return self.pm.reserved_bytes()
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(self.caches["segments"]))
