"""Memory attribution observatory (PR 8): MemoryAttributor exactness and
alias priority, FlightRecorder triggers/ring/dump schema, the attribution
tables riding RLHF phase spans (sum + residue == measured, per-owner sim
deltas), the watermark dump from a real PPO run, serving-side attribution
in ContinuousBatcher, and compiled-memory accounting."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.obs import (FlightRecorder, MemoryAttributor, MetricsRegistry,
                       RunTelemetry, record_compiled_memory)
from repro.rlhf import RLHFConfig, RLHFTrainer, live_device_bytes
from repro.rlhf.reward import make_target_token_reward


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


def _phase_spans(tel):
    return [sp for sp in tel.tracer.spans if sp.cat == "phase"]


# ------------------------------------------------------------- attributor
def test_attributor_exactness_and_residue():
    """sum(owners) + unattributed == total_bytes, and total matches the
    independent live_device_bytes() walk."""
    a = jnp.ones((64, 64))
    b = jnp.ones((32, 32))
    at = MemoryAttributor()
    at.register("a", lambda: {"x": a})
    at.register("b", lambda: b)
    snap = at.snapshot()
    assert snap.owners["a"] >= a.nbytes and snap.owners["b"] >= b.nbytes
    assert sum(snap.owners.values()) + snap.unattributed == snap.total_bytes
    assert snap.total_bytes == live_device_bytes()
    # an unregistered array lands in the residue
    c = jnp.ones((16, 16))
    snap2 = at.snapshot()
    assert snap2.unattributed >= snap.unattributed + c.nbytes
    del c


def test_attributor_alias_first_registration_wins():
    shared = jnp.ones((8, 8))
    at = MemoryAttributor()
    at.register("first", lambda: shared)
    at.register("second", lambda: {"alias": shared})
    snap = at.snapshot()
    assert snap.owners["first"] >= shared.nbytes
    assert snap.owners["second"] == 0
    # no double counting: the alias contributes once to the total
    assert sum(snap.owners.values()) + snap.unattributed == snap.total_bytes


def test_attributor_none_getter_and_top_buffers():
    big = jnp.ones((128, 128))
    at = MemoryAttributor(top_k=3)
    at.register("gone", lambda: None)          # owner holds nothing now
    at.register("big", lambda: big)
    snap = at.snapshot()
    assert snap.owners["gone"] == 0
    assert 1 <= len(snap.top_buffers) <= 3      # capped at top_k
    tb = snap.top_buffers[0]
    assert tb["owner"] == "big" and tb["nbytes"] == big.nbytes
    # metadata only — shape/dtype are strings, no array refs retained
    assert isinstance(tb["shape"], str) and isinstance(tb["dtype"], str)
    assert snap.ranked()[0] == "big"
    assert snap.table() == {k: v for k, v in snap.owners.items() if v}


# --------------------------------------------------------- flight recorder
def test_flight_watermark_trigger_and_latch(tmp_path):
    path = str(tmp_path / "dump.json")
    fl = FlightRecorder(watermark=0.5, capacity_bytes=1000, ring=4,
                        path=path)
    for i in range(10):
        fl.note("tick", i=i)
    assert len(fl.ring) == 4                    # bounded
    assert fl.check(100) is None                # below watermark
    at = MemoryAttributor()
    x = jnp.ones((4, 4))
    at.register("x", lambda: x)
    dump = fl.check(600, snapshot_fn=at.snapshot, phase="p", source="t")
    assert dump is not None and dump["trigger"] == "watermark"
    assert dump["schema"] == "flight-recorder/v1"
    assert dump["live_bytes"] == 600 and dump["capacity_bytes"] == 1000
    assert dump["owners"].get("x", 0) >= x.nbytes
    assert dump["owners_ranked"][0] == "x"
    assert len(dump["ring"]) == 4
    # latched: a second breach does not dump again
    assert fl.check(999) is None and len(fl.dumps) == 1
    disk = json.load(open(path))
    assert disk["trigger"] == "watermark"


def test_flight_calibration_fallback():
    """With no explicit capacity and no device bytes_limit info used, the
    first check at a boundary latches the budget and cannot itself
    breach; the next check crossing watermark * budget trips. Checks
    before the first boundary (mid-phase samples) do not calibrate."""
    fl = FlightRecorder(watermark=0.5, ring=8)
    fl.capacity_bytes, fl._calibrated = None, False      # force fallback
    fl.note("sample", phase="rollout_decode", live_bytes=5000)
    assert fl.check(5000) is None                        # not a boundary
    assert fl.capacity_bytes is None
    fl.note("phase", phase="rollout", live_bytes=1000)
    assert fl.check(1000) is None                        # calibrates
    assert fl.capacity_bytes == 1000
    assert fl.check(400) is None                         # 0.4 < 0.5
    assert fl.check(600) is not None                     # 0.6 >= 0.5


def test_flight_is_oom_and_record_oom():
    fl = FlightRecorder(capacity_bytes=1 << 30)
    assert fl.is_oom(RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    assert not fl.is_oom(ValueError("shape mismatch"))
    exc = RuntimeError("RESOURCE_EXHAUSTED: 2.5GiB")
    dump = fl.record_oom(exc, live_bytes=123, phase="train_actor",
                         source="rlhf")
    assert dump["trigger"] == "resource_exhausted"
    assert "RESOURCE_EXHAUSTED" in dump["error"]
    assert dump["phase"] == "train_actor"
    assert fl.record_oom(exc) is None            # latched per kind
    # watermark latch is independent of the OOM latch
    assert fl.check(1 << 30) is not None


def test_flight_phase_history():
    fl = FlightRecorder(capacity_bytes=1 << 30)
    fl.note("phase", phase="rollout", live_bytes=10, host_bytes=5)
    fl.note("sample", phase="x", live_bytes=99)          # not a boundary
    fl.note("phase", phase="train_actor", live_bytes=20, host_bytes=0)
    assert [p["phase"] for p in fl.phase_history] == \
        ["rollout", "train_actor"]


# ------------------------------------------------- trainer integration
@pytest.mark.parametrize("engine", ["hydra", "separate"])
def test_ppo_spans_carry_exact_attribution(engine):
    tel = RunTelemetry.create(engine=engine)
    tr, _ = run_ppo(engine, tel, steps=2)
    spans = _phase_spans(tel)
    assert spans, "no phase spans"
    for sp in spans:
        a = sp.args
        assert "attrib" in a, sp.name
        assert sum(a["attrib"].values()) + a["attrib_unattributed"] \
            == a["measured_bytes"], sp.name
    # the sim join: at least some spans diff the owner table against the
    # simulator's per-state ledger, per-owner
    deltas = [sp.args["attrib_sim_delta"] for sp in spans
              if "attrib_sim_delta" in sp.args]
    assert deltas
    sim_names = set().union(*(d.keys() for d in deltas))
    assert sim_names & {"actor_params", "critic_opt", "base_params",
                        "ref_params"}
    # owner gauges reached the registry
    g = tel.registry.get("rlhf_owner_live_bytes")
    assert g is not None


def test_ppo_watermark_dump_names_owners(tmp_path):
    path = str(tmp_path / "flight.json")
    fl = FlightRecorder(watermark=0.9, ring=64, path=path)
    tel = RunTelemetry.create(engine="hydra", flight=fl)
    run_ppo("hydra", tel, steps=2)
    assert fl.dumps, "watermark never tripped"
    dump = fl.dumps[0]
    assert dump["trigger"] == "watermark" and dump["source"] == "rlhf"
    assert dump["owners_ranked"] and dump["top_buffers"]
    assert all(dump["owners"][o] > 0 for o in dump["owners_ranked"][:3])
    assert dump["phase_history"], "dump carries no phase history"
    assert json.load(open(path))["schema"] == "flight-recorder/v1"


def test_telemetry_is_pure_observer():
    """Attribution + flight recorder must not change training math: losses
    bit-equal with and without them attached."""
    tel = RunTelemetry.create(engine="hydra",
                              flight=FlightRecorder(watermark=0.9))
    _, with_obs = run_ppo("hydra", tel, steps=2)
    _, without = run_ppo("hydra", None, steps=2)
    for a, b in zip(with_obs, without):
        for k in ("loss", "vf_loss", "ppo_loss"):
            assert a[k] == b[k], (k, a[k], b[k])


# ------------------------------------------------- serving + compiled mem
def test_serving_attribution_and_compiled_memory():
    from repro.models import Model
    from repro.serving import ContinuousBatcher
    cfg = micro_cfg()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    fl = FlightRecorder(watermark=0.99, ring=32)
    tel = RunTelemetry.create(run="serving-test", flight=fl)
    cb = ContinuousBatcher(model, cfg, params, slots=2, capacity=32,
                           temperature=0.0, seed=0, cache_backend="paged",
                           page_size=8, telemetry=tel)
    rng = np.random.RandomState(0)
    for _ in range(3):
        cb.submit(rng.randint(0, cfg.vocab_size, size=4), 4)
    cb.run_until_drained()
    at = tel.attribution
    assert at is not None
    snap = at.snapshot()
    assert snap.owners["serving_params"] > 0
    assert snap.owners["kv_pool"] > 0
    assert sum(snap.owners.values()) + snap.unattributed == snap.total_bytes
    # CompileCache keys joined with compiled-memory stats
    assert cb.compiled_memory, "no compiled programs recorded"
    for key, stats in cb.compiled_memory.items():
        assert stats is None or "temp_bytes" in stats
    names = {m["name"] for m in tel.registry.snapshot()}
    assert "compiled_temp_bytes" in names
    # the forced near-1.0 watermark tripped during serving with context
    if fl.dumps:
        assert fl.dumps[0]["source"] == "serving"


def test_record_compiled_memory_unit():
    reg = MetricsRegistry()
    fn = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones((8, 8))
    stats = record_compiled_memory(reg, "double", fn, x)
    if stats is not None:                  # backend exposes memory_analysis
        assert set(stats) == {"temp_bytes", "argument_bytes",
                              "output_bytes", "generated_code_bytes"}
        g = reg.get("compiled_output_bytes")
        assert g.value(program="double") == stats["output_bytes"]
    # a non-lowerable callable degrades to None, not an exception
    assert record_compiled_memory(reg, "plain", lambda y: y, x) is None
