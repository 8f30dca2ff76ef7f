"""Unified runtime telemetry (repro.obs): metric registry semantics and
JSONL round-trip, span nesting/ordering and Chrome-trace schema validity,
phase-span coverage (one span per canonical runtime phase, both engines),
the sim-vs-measured delta on a 2-step PPO run, offload/serving
instrumentation, and the live_host_bytes / per_device_live_bytes("host")
accounting."""
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.phases import RUNTIME_RLHF_PHASE_SEQUENCE
from repro.launch.report import load, phase_table, render
from repro.obs import (MetricsRegistry, RunTelemetry, SpanTracer,
                       set_global_registry)
from repro.rlhf import RLHFConfig, RLHFTrainer, live_host_bytes
from repro.rlhf.reward import make_target_token_reward
from repro.rlhf.trainer import per_device_live_bytes


def micro_cfg(**kw):
    base = dict(num_layers=2, d_model=32, d_ff=64, vocab_size=32,
                num_heads=2, num_kv_heads=1, head_dim=16)
    base.update(kw)
    return dataclasses.replace(get_config("llama3_2_3b").smoke(), **base)


def micro_rl(**kw):
    base = dict(prompt_len=4, gen_len=4, lr=1e-3, critic_lr=1e-3,
                kl_coef=0.0, top_k=0, engine="hydra", lora_rank=2)
    base.update(kw)
    return RLHFConfig(**base)


def run_ppo(engine, telemetry, steps=2, **rl_kw):
    cfg = micro_cfg()
    rl = micro_rl(engine=engine, **rl_kw)
    tr = RLHFTrainer(cfg, cfg, rl, jax.random.PRNGKey(0),
                     reward_fn=make_target_token_reward(7),
                     telemetry=telemetry)
    key = jax.random.PRNGKey(1)
    ms = []
    for s in range(steps):
        prompts = jax.random.randint(jax.random.fold_in(key, s),
                                     (2, rl.prompt_len), 0, cfg.vocab_size)
        ms.append(tr.train_step(prompts, jax.random.fold_in(key, 100 + s)))
    return tr, ms


# ---------------------------------------------------------------- metrics
def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("c", "a counter")
    c.inc()
    c.inc(2.5, phase="rollout")
    assert c.value() == 1.0 and c.value(phase="rollout") == 2.5
    with pytest.raises(AssertionError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(5)
    g.set(3)
    assert g.value() == 3 and g.peak() == 5
    h = reg.histogram("h")
    for v in (1e-5, 1e-3, 0.1, 7.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["min"] == 1e-5 and s["max"] == 7.0
    # idempotent re-registration returns the same instrument; kind clash
    # raises
    assert reg.counter("c") is c
    with pytest.raises(TypeError):
        reg.gauge("c")


def test_registry_snapshot_and_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("x").inc(3, phase="train_actor")
    reg.gauge("y").set(1.5)
    reg.histogram("z").observe(0.25)
    path = tmp_path / "m.jsonl"
    n = reg.write_jsonl(str(path))
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(recs) == n == 3
    assert recs == reg.snapshot()
    byname = {r["name"]: r for r in recs}
    assert byname["x"]["labels"] == {"phase": "train_actor"}
    assert byname["x"]["value"] == 3
    assert byname["y"]["peak"] == 1.5
    assert byname["z"]["count"] == 1 and byname["z"]["buckets"]["+Inf"] == 1


# ----------------------------------------------------------------- tracer
def test_span_nesting_and_ordering():
    tr = SpanTracer()
    with tr.span("outer", "iteration"):
        with tr.span("inner", "phase"):
            pass
        tr.instant("evt", "phase")
    assert [s.name for s in tr.spans] == ["inner", "outer"]  # completion order
    inner, outer = tr.spans
    assert inner.depth == 1 and outer.depth == 0
    assert outer.ts_us <= inner.ts_us
    assert inner.ts_us + inner.dur_us <= outer.ts_us + outer.dur_us + 1
    # records() re-sorts by start time
    recs = tr.records()
    assert [r["name"] for r in recs] == ["outer", "inner", "evt"]
    # retroactive spans: a completed interval lands where it says it was
    sp = tr.complete("retro", "phase", 5.0, 2.0, foo=1)
    assert sp.ts_us == 5.0 and sp.dur_us == 2.0 and sp.args == {"foo": 1}
    assert tr.self_time_s > 0


def test_unbalanced_end_asserts():
    tr = SpanTracer()
    with pytest.raises(AssertionError):
        tr.end()


def test_chrome_trace_schema(tmp_path):
    tr = SpanTracer()
    with tr.span("a", "phase", bytes=7):
        pass
    tr.instant("i", "offload")
    tr.sample("memory", {"device_mib": 1.0})
    path = tr.write_chrome_trace(str(tmp_path / "t.json"))
    d = json.load(open(path))
    assert isinstance(d["traceEvents"], list)
    phases = {e["ph"] for e in d["traceEvents"]}
    assert phases == {"M", "X", "i", "C"}
    for e in d["traceEvents"]:
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["dur"] > 0 and e["name"] == "a"
            assert e["args"] == {"bytes": 7}
    names = {e["args"]["name"] for e in d["traceEvents"] if e["ph"] == "M"}
    assert {"repro-telemetry", "phase", "offload"} <= names
    assert d["otherData"]["self_time_s"] >= 0


# ---------------------------------------------------- trainer integration
@pytest.mark.parametrize("engine", ["hydra", "separate"])
def test_phase_span_coverage_both_engines(engine):
    tel = RunTelemetry.create(sim_delta=False)
    run_ppo(engine, tel, steps=2)[0]
    phase_spans = [s for s in tel.tracer.spans if s.cat == "phase"]
    assert len(phase_spans) == 2 * len(RUNTIME_RLHF_PHASE_SEQUENCE)
    # each iteration tiles the canonical sequence, in order
    names = [s.name for s in phase_spans]
    assert names == list(RUNTIME_RLHF_PHASE_SEQUENCE) * 2
    for s in phase_spans:
        assert s.args["measured_bytes"] >= 0
        assert "measured_peak_bytes" in s.args
        assert "host_bytes" in s.args and "pcie_bytes" in s.args
    iters = [s for s in tel.tracer.spans if s.cat == "iteration"]
    assert len(iters) == 2
    assert tel.registry.counter("rlhf_iterations_total").value() == 2


@pytest.mark.slow
def test_sim_vs_measured_delta_smoke():
    tel = RunTelemetry.create(sim_delta=True)
    tr, _ = run_ppo("hydra", tel, steps=2, offload="all")
    assert set(tr.memory.sim_phase_bytes) == set(RUNTIME_RLHF_PHASE_SEQUENCE)
    for s in tel.tracer.spans:
        if s.cat == "phase":
            assert "sim_peak_bytes" in s.args
            assert s.args["sim_delta_bytes"] == \
                s.args["measured_bytes"] - s.args["sim_bytes"]
    # offload instrumentation rode along
    off = [s for s in tel.tracer.spans if s.cat == "offload"]
    assert any(s.name.startswith("park:") for s in off)
    assert any(s.name.startswith("fetch:") for s in off)
    assert tel.registry.counter("offload_parked_bytes_total").value() > 0


def test_telemetry_does_not_change_numerics():
    # instrumentation must be a pure observer: PPO losses bit-identical
    # with and without a telemetry bundle attached
    _, ms_plain = run_ppo("hydra", None, steps=2, offload="all")
    tel = RunTelemetry.create(sim_delta=False)
    _, ms_tel = run_ppo("hydra", tel, steps=2, offload="all")
    for a, b in zip(ms_plain, ms_tel):
        for k in ("loss", "ppo_loss", "vf_loss"):
            assert a[k] == b[k], k


# --------------------------------------------------------------- report
def test_report_renders_from_jsonl(tmp_path):
    tel = RunTelemetry.create(sim_delta=False, engine="hydra")
    run_ppo("hydra", tel, steps=1, offload="all")
    path = str(tmp_path / "run.jsonl")
    tel.write_jsonl(path)
    meta, events, samples, metrics = load(path)
    assert meta["type"] == "meta" and "self_time_s" in meta
    assert any(e["cat"] == "phase" for e in events)
    assert any(s["track"] == "memory" for s in samples)
    assert metrics
    table = phase_table(events)
    for ph in RUNTIME_RLHF_PHASE_SEQUENCE:
        assert ph in table
    out = render(path, show_metrics=True)
    assert "live device memory" in out and "rlhf_iterations_total" in out


# -------------------------------------------------- serving instrumentation
def test_serving_batcher_metrics():
    from repro.models import Model
    from repro.serving import ContinuousBatcher
    cfg = micro_cfg(num_kv_heads=2, head_dim=16, d_model=64)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tel = RunTelemetry.create(sim_delta=False)
    cb = ContinuousBatcher(model, cfg, params, slots=2, capacity=32,
                           temperature=0.0, seed=0, cache_backend="paged",
                           page_size=8, telemetry=tel)
    rng = np.random.RandomState(0)
    for g in (4, 6, 5):
        cb.submit(rng.randint(0, cfg.vocab_size, size=4), g)
    done = cb.run_until_drained()
    assert len(done) == 3
    reg = tel.registry
    assert reg.counter("serving_requests_total").value() == 3
    assert reg.counter("serving_admissions_total").value() >= 3
    total_toks = sum(len(r.out_tokens) for r in done)
    assert reg.counter("serving_tokens_total").value() == total_toks
    lat = reg.histogram("serving_admission_latency_s").summary()
    assert lat["count"] == 3
    assert reg.gauge("paged_pages_in_use").peak() > 0
    steps = [s for s in tel.tracer.spans if s.name == "serve.step"]
    assert len(steps) == cb.steps
    assert [s.args["step"] for s in steps] == list(range(cb.steps))
    assert all({"queued", "live"} <= s.args.keys() for s in steps)
    assert all(s.cat == "serving" for s in tel.tracer.spans)
    admits = [s for s in tel.tracer.spans if s.name == "serve.admit"]
    assert sorted(s.args["rid"] for s in admits) == [0, 1, 2]


def _profile_events(log_dir, prefix):
    """Host events of the newest profile under ``log_dir`` whose names
    start with ``prefix``: (name, start, end, stats, line), times in
    wall-clock ns (the capture's start plus the event's offset)."""
    import glob
    import os
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    pd = ProfileData.from_file(path)
    t0 = next(dict(p.stats)["profile_start_time"] for p in pd.planes
              if "profile_start_time" in dict(p.stats))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, t0 + e.start_ns, t0 + e.end_ns,
                                dict(e.stats), (plane.name, i)))
    return sorted(out, key=lambda ev: ev[1])


def _paged_batcher(telemetry=None, num_pages=None):
    from repro.models import Model
    from repro.serving import ContinuousBatcher
    cfg = micro_cfg(num_kv_heads=2, head_dim=16, d_model=64)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # slots 4 over the ladder (2, 4): one to four live rows decode in 2-
    # and 4-row programs; the 10-token prompt is past the prompt buckets
    # (2, 8), so its prefill compiles after warm-up
    cb = ContinuousBatcher(model, cfg, params, slots=4, capacity=32,
                           temperature=0.0, seed=0, cache_backend="paged",
                           page_size=8, capture_buckets=(2, 8),
                           telemetry=telemetry, num_pages=num_pages)
    rng = np.random.RandomState(0)
    for plen, g in ((4, 3), (6, 7), (3, 5), (10, 2)):
        cb.submit(rng.randint(0, cfg.vocab_size, size=plen), g)
    return cb


def test_serving_spans_reach_the_profile(tmp_path):
    cb = _paged_batcher()
    seen = []                                 # (live, kv_live) per decode
    decode = cb._vanilla_decode

    def spy(live_slots):
        seen.append((len(live_slots),
                     sum(int(cb.pos[s]) + 1 for s in live_slots)))
        return decode(live_slots)

    cb._vanilla_decode = spy
    with jax.profiler.trace(str(tmp_path)):
        done = cb.run_until_drained()
    assert len(done) == 4
    ev = _profile_events(tmp_path, "serve.")
    steps = [e for e in ev if e[0] == "serve.step"]
    assert len(steps) == cb.steps
    assert [e[3]["step"] for e in steps] == list(range(cb.steps))
    decodes = [e for e in ev if e[0] == "serve.decode"]
    assert len(decodes) == len(seen) == cb.steps
    for e in decodes + [e for e in ev if e[0] == "serve.readback"]:
        assert any(s[4] == e[4] and s[1] <= e[1] and e[2] <= s[2]
                   for s in steps), e[0]
    for e, (live, kv_live) in zip(decodes, seen):
        rows = 2 if live <= 2 else 4
        gathered = rows * cb.max_blocks * cb.page_size
        # the gather path reads what it gathers
        assert e[3] == {"rows": rows, "live": live, "kv_gathered": gathered,
                        "kv_read": gathered, "kv_live": kv_live}
    admits = [e for e in ev if e[0] == "serve.admit"]
    assert [e[3]["rid"] for e in admits] == [0, 1, 2, 3]
    assert [e[3]["bucket"] for e in admits] == [8, 8, 8, 10]
    assert all(e[3]["wait_us"] >= 0 for e in admits)
    assert sum(e[3]["done"] for e in ev if e[0] == "serve.retire") == 4
    grow = [e for e in ev if e[0] == "serve.grow_pages"]
    assert sum(e[3]["claimed"] for e in grow) > 0
    (rec,) = [e for e in ev if e[0] == "serve.recompile"]
    assert rec[3] == {"key": "prefill:paged:10"}
    assert any(p[0] == "serve.prefill" and p[1] <= rec[1] and rec[2] <= p[2]
               for p in ev)
    for r in done:
        assert r.t_submit <= r.t_admitted <= r.t_first_token


def test_decode_span_counts_the_kernels_reads(tmp_path, monkeypatch):
    """Where the paged decode kernel runs (forced here, in interpret mode),
    ``serve.decode``'s ``kv_read`` is every position of each 128-page tile
    that holds a page a live row attends to."""
    import repro.paged.attention as A
    monkeypatch.setattr(A, "decode_kernel_applies", lambda *a: True)
    cb = _paged_batcher(num_pages=256)
    assert cb._decode_kernel
    # hand pages out from both tiles in turn
    cb.pm._free = [p for pair in zip(range(128), range(128, 256))
                   for p in pair][::-1]
    seen = []
    decode = cb._vanilla_decode

    def spy(live_slots):
        tiles = set()
        for s in live_slots:
            bt = cb.pm.block_table_array([cb.active[s].rid], cb.max_blocks)
            tiles |= {int(p) // 128
                      for p in bt[0, :cb.pos[s] // cb.page_size + 1]}
        seen.append(len(tiles) * 128 * cb.page_size)
        return decode(live_slots)

    cb._vanilla_decode = spy
    with jax.profiler.trace(str(tmp_path)):
        assert len(cb.run_until_drained()) == 4
    decodes = [e for e in _profile_events(tmp_path, "serve.decode")]
    assert [e[3]["kv_read"] for e in decodes] == seen
    assert max(seen) == 2 * 128 * cb.page_size


def test_serving_without_telemetry_keeps_nothing():
    from repro.obs import ProfilerSpans
    plain = _paged_batcher()
    assert type(plain.spans) is ProfilerSpans
    toks = [r.out_tokens for r in sorted(plain.run_until_drained(),
                                         key=lambda r: r.rid)]
    # the recorder holds its clock and an empty stack, nothing else
    assert set(vars(plain.spans)) == {"_t0", "_stack"}
    assert plain.spans.depth == 0
    tel = RunTelemetry.create(sim_delta=False)
    kept = _paged_batcher(tel)
    assert kept.spans is tel.tracer
    assert toks == [r.out_tokens for r in sorted(kept.run_until_drained(),
                                                 key=lambda r: r.rid)]
    assert sum(s.name == "serve.step" for s in tel.tracer.spans) \
        == kept.steps


def test_span_export_and_profile_share_a_clock(tmp_path):
    tr = SpanTracer()
    with jax.profiler.trace(str(tmp_path)):
        time.sleep(0.01)
        with tr.span("serve.anchor", "serving", n=3):
            time.sleep(0.005)
    (name, start, end, stats, _), = _profile_events(tmp_path, "serve.")
    assert stats == {"n": 3}
    ev = next(e for e in tr.chrome_trace()["traceEvents"]
              if e.get("name") == "serve.anchor")
    anchor_ns = tr.chrome_trace()["otherData"]["t0_wall"] * 1e9
    assert abs(anchor_ns + ev["ts"] * 1e3 - start) < 1e6
    assert abs(ev["dur"] * 1e3 - (end - start)) < 1e6


# ------------------------------------------------- host-bytes accounting
def test_live_host_bytes_and_per_device_host():
    from repro.kernels import compat
    base = live_host_bytes()
    assert base >= 0
    with pytest.raises(AssertionError):
        per_device_live_bytes(memory="neither")
    if compat.host_memory_kind() is None:
        assert per_device_live_bytes(memory="host") == 0
        pytest.skip("no host memory kind on this backend")
    x = jax.device_put(
        jnp.ones((128, 128), jnp.float32),
        jax.sharding.SingleDeviceSharding(
            jax.devices()[0], memory_kind=compat.host_memory_kind()))
    x.block_until_ready()
    grew = live_host_bytes() - base
    assert grew >= x.nbytes
    assert per_device_live_bytes(memory="host") >= x.nbytes
    del x


def test_gather_copy_counts_bytes():
    reg = set_global_registry(None)
    tr, _ = run_ppo("hydra", None, steps=1)
    # ndp=1 / unsharded: gather_copy is pass-through, nothing counted
    assert reg.counter("sharding_gather_copy_bytes_total").value() == 0
    del tr
    set_global_registry(None)
