"""Paged KV-cache subsystem: page-manager properties, paged-vs-dense
attention equivalence, scheduler/rollout backend equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.models import Model
from repro.paged import (PageManager, PagePoolExhausted, append_decode,
                         paged_attention_reference, paged_decode_attention,
                         scatter_prefill)
from repro.paged.attention import paged_append
from repro.rlhf import Rollout
from repro.serving import ContinuousBatcher


def _tiny_cfg():
    return dataclasses.replace(
        get_config("llama3_2_3b").smoke(), num_layers=2, d_model=128,
        d_ff=256, vocab_size=64, num_heads=4, num_kv_heads=2, head_dim=32)


# ---------------------------------------------------------------------------
# PageManager properties
# ---------------------------------------------------------------------------
def test_page_manager_basics():
    pm = PageManager(8, 4)
    bt = pm.allocate(0, 6)                       # 2 pages
    assert len(bt) == 2 and pm.stats.pages_in_use == 2
    assert pm.fragmentation_slots() == 2         # 8 slots reserved, 6 used
    pm.free_seq(0)
    assert pm.stats.pages_in_use == 0
    pm.check_invariants()


def test_page_manager_exhaustion_is_atomic():
    pm = PageManager(4, 4)
    pm.allocate(0, 12)                           # 3 of 4 pages
    with pytest.raises(PagePoolExhausted):
        pm.allocate(1, 8)                        # needs 2, only 1 free
    pm.check_invariants()
    assert pm.num_free_pages == 1                # nothing leaked
    pm.allocate(2, 4)
    pm.check_invariants()


def test_page_manager_double_free_rejected():
    pm = PageManager(4, 4)
    pm.allocate(0, 4)
    pm.free_seq(0)
    with pytest.raises(KeyError):
        pm.free_seq(0)


def test_fork_shares_pages_and_cow_copies_on_append():
    pm = PageManager(8, 4)
    pm.allocate(0, 6)                            # page 1 is partial (2 used)
    pm.fork(0, 1)
    assert pm.stats.pages_in_use == 2            # fully shared
    copies = pm.append_token(1)                  # writes into shared partial
    assert len(copies) == 1                      # CoW copy of the last page
    assert pm.stats.n_cow_copies == 1
    assert pm.stats.pages_in_use == 3
    # parent still sees its original page; tables diverge at the tail
    assert pm.block_table(0)[0] == pm.block_table(1)[0]
    assert pm.block_table(0)[1] != pm.block_table(1)[1]
    # appending on a page boundary shares nothing -> no copy
    pm.free_seq(0)
    pm.free_seq(1)
    pm.check_invariants()
    assert pm.stats.pages_in_use == 0


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 30)),
                min_size=1, max_size=60))
def test_page_manager_invariants_random_traffic(ops):
    """Pages conserved, refcounts exact, fragmentation bounded by one page
    per live sequence, under random alloc/append/fork/free traffic."""
    pm = PageManager(32, 4)
    next_id = 0
    live = []
    for op, arg in ops:
        try:
            if op == 0 or not live:                      # allocate
                pm.allocate(next_id, arg)
                live.append(next_id)
                next_id += 1
            elif op == 1:                                # append
                pm.append_token(live[arg % len(live)])
            elif op == 2:                                # fork
                pm.fork(live[arg % len(live)], next_id)
                live.append(next_id)
                next_id += 1
            else:                                        # free
                pm.free_seq(live.pop(arg % len(live)))
        except PagePoolExhausted:
            pass
        pm.check_invariants()
        assert pm.fragmentation_slots() <= len(live) * (pm.page_size - 1)
    for sid in live:
        pm.free_seq(sid)
    pm.check_invariants()
    assert pm.stats.pages_in_use == 0
    assert pm.num_free_pages == pm.num_pages


def test_event_stream_replays_through_allocator_sim():
    pm = PageManager(16, 8, bytes_per_token=4096)
    pm.allocate(0, 20)
    pm.allocate(1, 9)
    for _ in range(5):
        pm.append_token(0)
    pm.free_seq(0)
    pm.free_seq(1)
    alloc = pm.replay_into()
    assert alloc.allocated == 0
    assert alloc.stats.peak_allocated == \
        pm.stats.peak_pages_in_use * pm.page_bytes
    assert alloc.stats.n_alloc == pm.stats.n_page_alloc


# ---------------------------------------------------------------------------
# Attention equivalence: paged reference / Pallas kernel vs dense kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,H,K,D,ps,nb,dt", [
    (2, 4, 2, 32, 8, 6, jnp.float32),
    (3, 8, 8, 16, 4, 9, jnp.float32),
    (1, 4, 1, 64, 16, 4, jnp.bfloat16),
])
def test_paged_attention_matches_dense(B, H, K, D, ps, nb, dt):
    rng = np.random.RandomState(B * H + D)
    C = nb * ps
    lens = rng.randint(1, C, size=B)
    pm = PageManager(B * nb, ps)
    for b in range(B):
        pm.allocate(b, int(lens[b]))
    bt = jnp.asarray(pm.block_table_array(list(range(B)), nb))

    S = int(lens.max())
    k_new = jnp.asarray(rng.randn(B, S, K, D), dt)
    v_new = jnp.asarray(rng.randn(B, S, K, D), dt)
    pool = scatter_prefill(
        {"k": jnp.zeros((B * nb, ps, K, D), dt),
         "v": jnp.zeros((B * nb, ps, K, D), dt)},
        k_new, v_new, bt, jnp.asarray(lens))
    q = jnp.asarray(rng.randn(B, H, D), dt)
    position = jnp.asarray(lens - 1, jnp.int32)

    # dense oracle: same K/V packed [B, C] with explicit per-slot positions
    kd = np.zeros((B, C, K, D), np.float32)
    vd = np.zeros_like(kd)
    posd = np.full((B, C), -1, np.int32)
    kf = np.asarray(k_new, np.float32)
    vf = np.asarray(v_new, np.float32)
    for b in range(B):
        kd[b, :lens[b]] = kf[b, :lens[b]]
        vd[b, :lens[b]] = vf[b, :lens[b]]
        posd[b, :lens[b]] = np.arange(lens[b])
    dense = decode_attention(q.astype(jnp.float32), jnp.asarray(kd),
                             jnp.asarray(vd), jnp.asarray(posd), position)

    tol = 2e-2 if dt == jnp.bfloat16 else 1e-5
    ref = paged_attention_reference(q, pool, bt, position)
    np.testing.assert_allclose(np.asarray(ref, np.float32),
                               np.asarray(dense, np.float32), atol=tol)
    ker = paged_decode_attention(q, pool["k"], pool["v"], bt, position,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(ker, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def _paged_case(rng, P, ps, nb, K, D, positions, pages_of, dt):
    """A random pool and block tables: row b holds ``pages_of(b, n)`` for
    the ``n`` blocks its position needs (-1 past them)."""
    pool = {"k": jnp.asarray(rng.randn(P, ps, K, D), dt),
            "v": jnp.asarray(rng.randn(P, ps, K, D), dt)}
    bt = np.full((len(positions), nb), -1, np.int32)
    for b, pos in enumerate(positions):
        if pos >= 0:
            n = pos // ps + 1
            bt[b, :n] = pages_of(b, n)
    return pool, jnp.asarray(bt), jnp.asarray(positions, jnp.int32)


# (H, K, D, ps, nb, P, positions, where the pages come from)
KERNEL_CASES = {
    # 25 x 64 in small: an odd head count at head dim 64
    "mha-odd-heads": (5, 5, 64, 4, 6, 128, [9, 22, 3, 15], "ordered"),
    "gqa": (8, 2, 64, 4, 6, 128, [7, 20, 11], "ordered"),
    # a pad row, a row whose last page holds one token, a row inside one
    # page (every row here is shorter than one 128-page block)
    "ragged": (4, 4, 32, 4, 6, 128, [-1, 8, 2, 23, -1], "ordered"),
    # tables out of order over the whole pool, -1 tails
    "shuffled-tables": (4, 2, 64, 4, 8, 256, [13, 30, 5, 26], "shuffled"),
    # pages in the first and third tile only: the middle one is skipped
    "dead-tile": (4, 4, 64, 4, 6, 384, [17, 22, 6], "tiles-0-2"),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_paged_decode_kernel_matches_reference(case):
    H, K, D, ps, nb, P, positions, order = KERNEL_CASES[case]
    rng = np.random.RandomState(sum(map(ord, case)))
    pages = {"ordered": np.arange(P), "shuffled": rng.permutation(P),
             "tiles-0-2": np.concatenate(
                 [rng.permutation(128), 256 + rng.permutation(128)])[
                     rng.permutation(256)]}[order]
    taken = iter(pages)
    pool, bt, pos = _paged_case(
        rng, P, ps, nb, K, D, positions,
        lambda b, n: [next(taken) for _ in range(n)], jnp.bfloat16)
    q = jnp.asarray(rng.randn(len(positions), H, D), jnp.bfloat16)
    ref = np.asarray(paged_attention_reference(q, pool, bt, pos), np.float32)
    ker = np.asarray(paged_decode_attention(q, pool["k"], pool["v"], bt, pos,
                                            interpret=True), np.float32)
    live = np.asarray(pos) >= 0
    np.testing.assert_allclose(ker[live], ref[live], atol=2e-2)
    assert (ker[~live] == 0).all()          # pad rows read nothing


@pytest.mark.parametrize("P,positions", [
    # two rows at slot 1 (one slab when their pages share a tile), a pad
    # row, a row whose position is past its blocks (no page: dropped)
    (256, [1, 5, 6, 1, -1, 9]),
    (12, [0, 3, 4, 7, 2, -1]),          # fewer pages than a tile
])
def test_paged_append_matches_append_decode(P, positions):
    ps, K, D, nb = 4, 5, 64, 2
    rng = np.random.RandomState(P)
    B = len(positions)
    pool = {n: jnp.asarray(rng.randn(P, ps, K, D), jnp.bfloat16)
            for n in ("k", "v")}
    bt = jnp.asarray(rng.permutation(P)[:B * nb].reshape(B, nb), jnp.int32)
    if P > 128:                          # rows 0 and 3 in one slab
        bt = bt.at[3, 0].set(bt[0, 0] ^ 1)
    pos = jnp.asarray(positions, jnp.int32)
    kt, vt = (jnp.asarray(rng.randn(B, K, D), jnp.bfloat16) for _ in "kv")
    want = append_decode(pool, kt, vt, bt, pos)
    got = paged_append(pool["k"], pool["v"], kt, vt, bt, pos,
                       interpret=True)
    for g, n in zip(got, ("k", "v")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want[n]))


def test_model_paged_decode_kernel_path_matches_reference_path():
    """``Model.paged_decode_step`` through the kernel (interpret mode) gives
    the gather path's logits and greedy tokens, a pad row included."""
    cfg = _tiny_cfg()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, P_len, ps = 3, 8, 4
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (B, P_len)))
    pm = PageManager(128, ps)
    for b in range(2):
        pm.allocate(b, P_len + 6)
    bt = jnp.asarray(pm.block_table_array([0, 1, None], 4))
    lens = jnp.asarray([P_len, P_len - 3, 0], jnp.int32)
    pools0 = model.init_paged_pools(128, ps, jnp.float32)
    _, pools0 = model.paged_prefill(params, {"tokens": toks}, pools0, bt,
                                    lens)
    pools = {True: pools0, False: pools0}
    tok = jnp.asarray([3, 5, 0], jnp.int32)
    pos = jnp.asarray([P_len, P_len - 3, -1], jnp.int32)
    for _ in range(4):
        lg = {}
        for kernel in (True, False):
            lg[kernel], pools[kernel] = model.paged_decode_step(
                params, pools[kernel], tok, pos, bt, use_kernel=kernel)
        np.testing.assert_allclose(np.asarray(lg[True])[:2],
                                   np.asarray(lg[False])[:2], atol=1e-4)
        tok = jnp.argmax(lg[False], -1).astype(jnp.int32)
        assert (jnp.argmax(lg[True], -1)[:2] == tok[:2]).all()
        pos = jnp.where(pos >= 0, pos + 1, -1)


def test_append_decode_writes_only_live_rows():
    ps, P_, K, D = 4, 8, 2, 16
    pool = {"k": jnp.zeros((P_, ps, K, D)), "v": jnp.zeros((P_, ps, K, D))}
    bt = jnp.asarray([[0, 1], [2, -1]], jnp.int32)
    kt = jnp.ones((2, K, D))
    out = append_decode(pool, kt, kt, bt, jnp.asarray([5, -1], jnp.int32))
    k = np.asarray(out["k"])
    assert k[1, 1].sum() == K * D          # seq 0, logical idx 5 -> page 1
    assert k.sum() == K * D                # idle row dropped, nothing else


# ---------------------------------------------------------------------------
# Model / scheduler / rollout backends
# ---------------------------------------------------------------------------
def test_model_paged_decode_matches_dense_logits():
    cfg = _tiny_cfg()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, P_len, ps = 2, 8, 4
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (B, P_len)))
    lg_d, caches = model.prefill(params, {"tokens": toks}, 32)
    caches = {"segments": caches["segments"], "cross_kv": None}
    pm = PageManager(32, ps)
    for b in range(B):
        pm.allocate(b, P_len + 6)
    bt = jnp.asarray(pm.block_table_array([0, 1], -(-(P_len + 6) // ps)))
    pools = model.init_paged_pools(32, ps, jnp.float32)
    lg_p, pools = model.paged_prefill(params, {"tokens": toks}, pools, bt,
                                      jnp.full((B,), P_len, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_p), atol=1e-5)
    tok = jnp.argmax(lg_d, -1).astype(jnp.int32)
    pos = jnp.full((B,), P_len, jnp.int32)
    for _ in range(4):
        lg_d, caches = model.decode_step(params, caches, tok, pos)
        lg_p, pools = model.paged_decode_step(params, pools, tok, pos, bt)
        np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_p),
                                   atol=1e-5)
        tok = jnp.argmax(lg_d, -1).astype(jnp.int32)
        pos = pos + 1


def test_batcher_paged_matches_dense_greedy():
    cfg = _tiny_cfg()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.arange(8) % cfg.vocab_size

    def run(backend):
        cb = ContinuousBatcher(model, cfg, params, slots=3, capacity=64,
                               temperature=0.0, seed=7,
                               cache_backend=backend, page_size=8)
        r = cb.submit(prompt, 10)
        rng = np.random.RandomState(1)
        for _ in range(2):
            cb.submit(rng.randint(0, 64, size=8), 10)
        cb.run_until_drained()
        return r.out_tokens, cb

    dense, _ = run("dense")
    paged, cb = run("paged")
    assert dense == paged
    assert cb.pm.stats.pages_in_use == 0         # everything retired
    cb.pm.check_invariants()
    # ragged completions must reserve less than the dense worst case
    assert cb.pm.stats.peak_pages_in_use * cb.page_size < 3 * 64


def test_batcher_paged_preempts_and_completes_on_tiny_pool():
    cfg = _tiny_cfg()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cb = ContinuousBatcher(model, cfg, params, slots=3, capacity=64,
                           temperature=0.0, seed=7, cache_backend="paged",
                           page_size=8, num_pages=9)   # < 3 full sequences
    reqs = [cb.submit((np.arange(8) + i) % cfg.vocab_size, 20)
            for i in range(4)]
    done = cb.run_until_drained()
    assert len(done) == 4
    assert all(len(r.out_tokens) == 20 for r in reqs)
    assert sum(r.n_preempted for r in reqs) >= 1
    cb.pm.check_invariants()
    assert cb.pm.stats.pages_in_use == 0


def test_batcher_paged_preemption_preserves_greedy_output():
    """Recompute preemption (even repeated) must not corrupt context:
    greedy completions from a starved pool equal the unstarved ones."""
    cfg = _tiny_cfg()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def run(num_pages):
        cb = ContinuousBatcher(model, cfg, params, slots=3, capacity=64,
                               temperature=0.0, seed=7,
                               cache_backend="paged", page_size=8,
                               num_pages=num_pages)
        reqs = [cb.submit((np.arange(8) + i) % cfg.vocab_size, 24)
                for i in range(4)]
        cb.run_until_drained()
        return [r.out_tokens for r in reqs], sum(r.n_preempted for r in reqs)

    roomy, p0 = run(24)
    tight, p1 = run(8)            # pool of one max-length sequence
    assert p0 == 0 and p1 >= 1
    assert roomy == tight
    assert all(len(t) == 24 for t in tight)


def test_batcher_rejects_request_beyond_capacity():
    cfg = _tiny_cfg()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cb = ContinuousBatcher(model, cfg, params, slots=2, capacity=32,
                           cache_backend="paged", page_size=8)
    with pytest.raises(ValueError):
        cb.submit(np.arange(30), 10)
    cb.submit(np.arange(8), 10)            # servable request still accepted
    assert len(cb.queue) == 1


def test_rollout_paged_matches_dense_exactly():
    cfg = _tiny_cfg()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (3, 9)))
    key = jax.random.PRNGKey(5)
    kw = dict(capacity=40, temperature=0.7, top_k=8, eos_id=3)
    a = Rollout(model, cfg, **kw).generate(params, {"tokens": toks}, 12, key)
    rp = Rollout(model, cfg, backend="paged", page_size=4, **kw)
    b = rp.generate(params, {"tokens": toks}, 12, key)
    assert bool((a.tokens == b.tokens).all())
    assert bool((a.mask == b.mask).all())
    np.testing.assert_allclose(np.asarray(a.logp), np.asarray(b.logp),
                               atol=1e-5)
    pm = rp.page_manager
    assert pm.stats.pages_in_use == 0
    # replays cleanly through the paper's allocator simulator
    alloc = pm.replay_into()
    assert alloc.allocated == 0 and alloc.stats.peak_allocated > 0
