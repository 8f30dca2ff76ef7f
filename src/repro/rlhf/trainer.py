"""The RLHF PPO trainer, with the paper's phase-boundary memory management
as a first-class feature — in two engine layouts:

  * ``engine="separate"`` — the four-model seed path (actor, critic,
    reference, reward as full parameter trees, two full optimizer states):
    the configuration the paper profiles.
  * ``engine="hydra"``    — the shared-base engine (``rlhf.engine``): ONE
    frozen trunk, per-role LoRA adapters + value heads, adapter-only
    optimizer states. Reference logp is the plain base forward (the ref
    copy disappears); rollout generates from merged weights re-merged at
    phase boundaries.

``PhaseMemoryManager`` is the JAX/TPU-native analogue of the paper's
``empty_cache()`` insertion (§3.3): at each phase boundary it deterministically
drops dead device buffers (explicit ``.delete()`` of phase-local arrays),
triggers host GC, and reports live device bytes — so the memory timeline of
a real run is observable, phase by phase, exactly like the paper's profiler
(App. B). On TPU, buffer *placement* churn is already avoided by design
(static shapes + donation — see rollout.py); what remains at boundaries is
reference hygiene, which this manager enforces.

``RLHFConfig.offload`` adds the runtime half of the paper's
phase-exclusivity story (``repro.offload``): role state is parked to host
between the phases that touch it and async-fetched back at the boundary —
``"optimizer"`` swaps the moments, ``"roles"`` adds the per-role
params/adapters, ``"all"`` also parks the hydra trunk's adapted leaves
while merged weights serve rollout. Parking is bit-exact, so every offload
level reproduces the ``"none"`` losses to the last ulp.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import Model
from repro.rlhf.engine import ModelEngine
from repro.rlhf.ppo import gae, kl_shaped_rewards, whiten
from repro.rlhf.rollout import Rollout
from repro.steps import (init_lora_train_state, init_train_state,
                         make_lora_train_step, make_train_step, _prefix_len)

MEMORY_POLICIES = ("none", "after_inference", "after_training", "after_all")


def _jit_step(step):
    """Jit a train step unless the builder already jitted it internally
    (ZeRO steps are two programs with an eager grad re-shard between —
    see ``steps.make_train_step(shard=...)``)."""
    if getattr(step, "prejitted", False):
        return step
    return jax.jit(step, donate_argnums=(0,))


def live_device_bytes() -> int:
    """Live *device* bytes: arrays parked in the host memory kind by the
    offload subsystem don't count (numpy fallback copies never did) — they
    are accounted by :func:`live_host_bytes` instead."""
    from repro.kernels import compat
    host_kind = compat.host_memory_kind()
    total = 0
    for a in jax.live_arrays():
        if host_kind is not None and \
                getattr(a.sharding, "memory_kind", None) == host_kind:
            continue
        total += getattr(a, "nbytes", 0)
    return total


def live_host_bytes() -> int:
    """Live bytes of jax arrays placed in the *host* memory kind — the
    other half of :func:`live_device_bytes`, so offloaded state (parked
    role trees, remat-offloaded residuals) no longer vanishes from all
    accounting. Note the committed-numpy fallback transport parks plain
    ``np.ndarray`` copies that are not jax arrays; those are accounted by
    ``HostParkingLot.parked_bytes()`` and the two figures are merged with
    ``max`` (never summed — memory-kind parks appear in both) by
    ``PhaseMemoryManager._record``."""
    from repro.kernels import compat
    host_kind = compat.host_memory_kind()
    if host_kind is None:
        return 0
    return sum(getattr(a, "nbytes", 0) for a in jax.live_arrays()
               if getattr(a.sharding, "memory_kind", None) == host_kind)


def per_device_live_bytes(memory: str = "device") -> int:
    """Max-over-devices live bytes — the per-device HBM figure ZeRO cuts.
    Replicated arrays cost full size on every device; ZeRO-3-sharded trees
    cost 1/ndp. Equal to :func:`live_device_bytes` on one device.

    ``memory="host"`` counts host-memory-kind arrays instead (their
    shards live in each device's pinned host segment), so parked state is
    accounted per device by the same shard walk rather than vanishing."""
    assert memory in ("device", "host"), memory
    from repro.kernels import compat
    host_kind = compat.host_memory_kind()
    per: Dict[Any, int] = {}
    for a in jax.live_arrays():
        on_host = host_kind is not None and \
            getattr(a.sharding, "memory_kind", None) == host_kind
        if on_host != (memory == "host"):
            continue
        shards = getattr(a, "addressable_shards", None)
        if not shards:
            per[None] = per.get(None, 0) + getattr(a, "nbytes", 0)
        else:
            for s in shards:
                per[s.device] = per.get(s.device, 0) + s.data.nbytes
    return max(per.values()) if per else 0


@dataclass
class PhaseMemoryManager:
    """Phase-boundary memory hygiene + per-phase live-memory profiling.

    With an ``offload`` executor attached (``rl.offload != "none"``), each
    boundary also runs the offload schedule: park the trees the next phase
    doesn't touch *before* the live-bytes record (so eviction shows in the
    curve), async-fetch the next phase's trees after it — mirroring the
    park -> empty_cache -> record -> fetch order of the allocator
    simulator's boundary model.

    With a ``telemetry`` bundle attached (``obs.RunTelemetry``), every
    boundary additionally closes one tracer span per canonical runtime
    phase — carrying the measured live/host/PCIe bytes of the record it
    just took (zero recomputation) plus, when the trainer attached
    ``sim_phase_bytes``, the traced allocator-simulator's predicted bytes
    for that phase and the sim-vs-measured delta — and feeds the metrics
    registry (``rlhf_phase_*``). Phase spans tile the iteration exactly:
    each span runs from the previous boundary (or ``iteration_start``) to
    this one."""
    # none | after_inference | after_training | after_all
    policy: str = "after_inference"
    records: List[dict] = field(default_factory=list)
    offload: Optional[Any] = None      # offload.OffloadExecutor
    telemetry: Optional[Any] = None    # obs.RunTelemetry
    # obs.MemoryAttributor: when attached, every record classifies the
    # live set by owner in ONE walk — the record's live_bytes IS the
    # snapshot total, so the per-owner table on a phase span sums (with
    # the unattributed residue) to measured_bytes exactly
    attributor: Optional[Any] = None
    # runtime phase -> {"sim_bytes", "sim_peak_bytes"} from the traced
    # simulator (attached lazily by RLHFTrainer when sim_delta is on)
    sim_phase_bytes: Dict[str, dict] = field(default_factory=dict)

    def __post_init__(self):
        if self.policy not in MEMORY_POLICIES:
            raise ValueError(
                f"unknown memory policy {self.policy!r}; "
                f"expected one of {MEMORY_POLICIES}")
        self._phase_t0: Optional[float] = None   # tracer µs of phase start
        self._phase_peak = 0                     # mid-phase sample peak
        self._pcie_mark = 0                      # lot traffic at phase start
        self._iter_n = 0
        self._last_snap = None                   # most recent attribution

    def _record(self, phase: str, kind: str, **extra) -> dict:
        snap = None
        if self.attributor is not None:
            snap = self.attributor.snapshot()
            self._last_snap = snap
            live = snap.total_bytes
            # device and host totals come from the snapshot's single walk
            host = snap.host_unattributed + sum(snap.host_owners.values())
            if self.telemetry is not None:
                # the classification pass is telemetry work: charge it to
                # self-time so the <=2% overhead gate covers attribution
                self.telemetry.tracer.self_time_s += snap.walk_s
        else:
            live = live_device_bytes()
            host = live_host_bytes()
        # host-side accounting: memory-kind parks are live jax arrays
        # (live_host_bytes) AND lot entries; numpy-fallback parks are lot
        # entries only — max() merges without double counting
        if self.offload is not None:
            host = max(host, self.offload.lot.parked_bytes())
        rec = {"phase": phase, "kind": kind,
               "live_bytes": live,
               "live_bytes_per_device": (per_device_live_bytes()
                                         if jax.device_count() > 1 else live),
               "host_bytes": host,
               "t": time.time()}
        if snap is not None:
            rec["attrib"] = snap.table()
            rec["attrib_unattributed"] = snap.unattributed
        rec.update(extra)
        self.records.append(rec)
        return rec

    def _snapshot_for_dump(self):
        """Lazy snapshot source for the flight recorder: reuse the one the
        triggering record just took (same live set) instead of re-walking."""
        if self._last_snap is not None:
            return self._last_snap
        if self.attributor is not None:
            return self.attributor.snapshot()
        return None

    def _flight(self):
        return getattr(self.telemetry, "flight", None) \
            if self.telemetry is not None else None

    # ----------------------------------------------------------- telemetry
    def _pcie_total(self) -> int:
        if self.offload is None:
            return 0
        st = self.offload.lot.stats
        return st.bytes_parked_total + st.bytes_fetched_total

    def iteration_start(self):
        """Open the per-iteration parent span (telemetry only)."""
        if self.telemetry is None:
            return
        tr = self.telemetry.tracer
        tr.begin("ppo_iteration", cat="iteration", n=self._iter_n)
        self._phase_t0 = tr.now_us()
        self._phase_peak = 0
        self._pcie_mark = self._pcie_total()

    def iteration_end(self, **args):
        if self.telemetry is None:
            return
        self.telemetry.tracer.end(**args)
        self.telemetry.registry.counter(
            "rlhf_iterations_total", "completed PPO iterations").inc()
        self._iter_n += 1
        self._phase_t0 = None

    def _emit_phase_span(self, phase: str, kind: str, rec: dict):
        tel = self.telemetry
        tr = tel.tracer
        now = tr.now_us()
        t0 = self._phase_t0 if self._phase_t0 is not None else now
        pcie_now = self._pcie_total()
        args = {"kind": kind,
                "measured_bytes": rec["live_bytes"],
                "measured_peak_bytes": max(rec["live_bytes"],
                                           self._phase_peak),
                "measured_bytes_per_device": rec["live_bytes_per_device"],
                "host_bytes": rec["host_bytes"],
                "pcie_bytes": pcie_now - self._pcie_mark}
        if "attrib" in rec:
            args["attrib"] = rec["attrib"]
            args["attrib_unattributed"] = rec["attrib_unattributed"]
        sim = self.sim_phase_bytes.get(phase)
        if sim is not None:
            args.update(sim)
            args["sim_delta_bytes"] = rec["live_bytes"] - sim["sim_bytes"]
            # per-owner sim deltas: measured owner table vs the simulator's
            # per-state ledger at this phase's boundary record. Restricted
            # to the sim's group names — both sides use the same taxonomy
            sim_owners = sim.get("sim_owner_bytes")
            if sim_owners and "attrib" in rec:
                args["attrib_sim_delta"] = {
                    k: rec["attrib"].get(k, 0) - v
                    for k, v in sim_owners.items()}
        tr.complete(phase, "phase", t0, now - t0, **args)
        tr.sample("memory", {"device_mib": rec["live_bytes"] / 2**20,
                             "host_mib": rec["host_bytes"] / 2**20},
                  ts_us=now)
        reg = tel.registry
        reg.counter("rlhf_phase_total", "phase boundaries crossed").inc(
            phase=phase)
        reg.gauge("rlhf_phase_live_bytes",
                  "live device bytes at phase end").set(
            rec["live_bytes"], phase=phase)
        reg.gauge("rlhf_phase_host_bytes",
                  "host-resident bytes at phase end").set(
            rec["host_bytes"], phase=phase)
        reg.histogram("rlhf_phase_seconds", "wall time per phase").observe(
            (now - t0) / 1e6, phase=phase)
        for owner, b in rec.get("attrib", {}).items():
            reg.gauge("rlhf_owner_live_bytes",
                      "live device bytes by owner at phase end").set(
                b, owner=owner, phase=phase)
        self._phase_t0 = now
        self._phase_peak = 0
        self._pcie_mark = pcie_now

    def sample(self, phase: str, kind: str = "inference"):
        """Mid-phase measurement point (no hygiene): used where the live
        set changes inside a phase — e.g. hydra rollout decode, where the
        trunk's adapted leaves are parked while merged weights serve."""
        rec = self._record(phase, kind, sample=True)
        self._phase_peak = max(self._phase_peak, rec["live_bytes"])
        if self.telemetry is not None:
            tr = self.telemetry.tracer
            extra = {k: rec[k] for k in ("attrib", "attrib_unattributed")
                     if k in rec}
            tr.instant(f"{phase}:sample", cat="phase",
                       measured_bytes=rec["live_bytes"],
                       host_bytes=rec["host_bytes"], **extra)
            tr.sample("memory", {"device_mib": rec["live_bytes"] / 2**20,
                                 "host_mib": rec["host_bytes"] / 2**20})
        fl = self._flight()
        if fl is not None:
            fl.note("sample", phase=phase, live_bytes=rec["live_bytes"],
                    host_bytes=rec["host_bytes"])
            fl.check(rec["live_bytes_per_device"],
                     snapshot_fn=self._snapshot_for_dump,
                     phase=phase, source="rlhf")

    def boundary(self, phase: str, kind: str, *drop):
        for tree in drop:
            jax.tree.map(
                lambda x: x.delete()
                if hasattr(x, "delete") and not x.is_deleted() else None,
                tree)
        if self.offload is not None:
            self.offload.park_for_boundary(phase)
        if (self.policy == "after_all"
                or (self.policy == "after_inference" and kind == "inference")
                or (self.policy == "after_training" and kind == "training")):
            gc.collect()
        rec = self._record(phase, kind)
        if self.telemetry is not None:
            self._emit_phase_span(phase, kind, rec)
        fl = self._flight()
        if fl is not None:
            # checked before the fetch: the record is the post-hygiene,
            # pre-fetch trough — the same point the simulator records
            fl.note("phase", phase=phase, kind=kind,
                    live_bytes=rec["live_bytes"],
                    host_bytes=rec["host_bytes"])
            fl.check(rec["live_bytes_per_device"],
                     snapshot_fn=self._snapshot_for_dump,
                     phase=phase, source="rlhf")
        if self.offload is not None:
            self.offload.fetch_for_boundary(phase)


@dataclass
class RLHFConfig:
    prompt_len: int = 32
    gen_len: int = 32
    kl_coef: float = 0.1
    gamma: float = 1.0
    lam: float = 0.95
    ppo_epochs: int = 1
    lr: float = 1e-5
    critic_lr: float = 1e-5
    temperature: float = 1.0
    top_k: int = 50
    whiten_advantages: bool = True
    memory_policy: str = "after_inference"
    engine: str = "separate"        # separate | hydra
    lora_rank: int = 128            # hydra adapter rank (paper grid: 128)
    # runtime host-offload level (repro.offload): none | optimizer | roles
    # | all — which role state is parked to host between the phases that
    # touch it ("all" also parks the hydra trunk's adapted leaves while
    # merged weights serve rollout)
    offload: str = "none"
    # DP batch sharding of the scoring/training batches under a mesh
    # (DESIGN.md §3.6):
    #   "throughput" (default) — shard the batch over the DP axis when it
    #     divides; batch-dim loss reductions then run as per-device
    #     partials + a cross-device sum, which changes reduction ORDER vs
    #     the replicated batch — a documented ~ulp drift, accepted for
    #     the ndp-times-smaller per-device activations. A non-divisible
    #     batch falls back to replication WITH a warning (never silent).
    #   "strict" — sharded semantics are required: a batch that does not
    #     divide the DP size raises instead of silently replicating.
    # The bit-identity validation harness (zero_smoke, test_zero_rlhf)
    # deliberately uses non-divisible batches so state shards but batches
    # replicate and the arithmetic stays exactly single-device.
    batch_shard: str = "throughput"
    # fast decode path (DESIGN.md "Fast decode path"): MTP self-speculative
    # greedy rollout — bit-identical tokens/logps to vanilla greedy, fewer
    # decode dispatches. Forces temperature=0 / top_k=0 for the rollout.
    spec_decode: bool = False
    spec_k: int = 2
    # compile-bucket ladder for ragged prompt lengths (None = off)
    capture_buckets: Optional[Sequence[int]] = None


class RLHFTrainer:
    """PPO over (actor, critic, reference, reward). The reward model is any
    callable ``(tokens, mask) -> [B] float`` — a learned value-head model or
    a programmatic reward for the examples.

    With ``rl.engine == "hydra"`` the four roles share one frozen trunk
    (``critic_cfg`` is ignored — the critic/reward heads ride the actor
    trunk) and only adapter leaves train.

    ``shard`` (a ``sharding.ShardedContext``) makes the whole pipeline
    mesh-aware: params, grads, and optimizer state partition over the DP
    axis per ``shard.strat.zero_stage`` on *both* engines — the hydra path
    shards the frozen trunk with ZeRO-3 and the per-role adapters by rule,
    the separate path shards all four role trees. Rollout and merged-weight
    generation run under the same mesh from a gathered compute copy, and
    ``offload`` composes: the parking lot round-trips sharded leaves
    sharding-intact, so ``offload != "none"`` still parks exactly the
    per-device ZeRO shards. Every stage reproduces the unsharded losses
    bit-for-bit (the gather-compute/slice-update contract of
    ``steps.make_train_step`` — DESIGN.md §3).
    """

    def __init__(self, actor_cfg: ModelConfig, critic_cfg: ModelConfig,
                 rl: RLHFConfig, key, reward_fn: Optional[Callable] = None,
                 shard=None, telemetry=None):
        assert rl.engine in ("separate", "hydra"), rl.engine
        if rl.batch_shard not in ("strict", "throughput"):
            raise ValueError(
                f"unknown batch_shard {rl.batch_shard!r}; "
                "expected 'strict' or 'throughput'")
        self.rl = rl
        self.actor_cfg, self.critic_cfg = actor_cfg, critic_cfg
        self.reward_fn = reward_fn
        self.shard = shard
        # ambient mesh for the scoring/rollout programs: only a TP context
        # (ntp > 1) activates it, so the in-jit "model" constraint hints
        # resolve — pure-DP runs keep the historical mesh-free traces and
        # their bitwise contract intact (DESIGN.md §3 vs §9)
        self._tp_mesh = shard.mesh if shard is not None and \
            getattr(shard, "ntp", 1) > 1 else None
        self.telemetry = telemetry          # obs.RunTelemetry | None
        self._sim_attached = False
        self._gather_step_bytes: Optional[int] = None
        self.memory = PhaseMemoryManager(policy=rl.memory_policy,
                                         telemetry=telemetry)
        if rl.engine == "hydra":
            self._init_hydra(actor_cfg, rl, key)
        else:
            self._init_separate(actor_cfg, critic_cfg, rl, key)
        self.rollout = Rollout(
            self.actor, actor_cfg, capacity=rl.prompt_len + rl.gen_len,
            temperature=0.0 if rl.spec_decode else rl.temperature,
            top_k=0 if rl.spec_decode else rl.top_k,
            spec_decode=rl.spec_decode, spec_k=rl.spec_k,
            capture_buckets=rl.capture_buckets, mesh=self._tp_mesh)
        self.offload = self.offload_lot = None
        if rl.offload != "none":
            self._init_offload(rl)
        # phase-scoped buffer trees the attribution engine reads through
        # (merged rollout weights, rollout outputs, experience) — set and
        # cleared by _gen/make_experience/train_step
        self._live_buffers: Dict[str, Any] = {}
        self._compiled_recorded: set = set()
        if telemetry is not None:
            self._init_attribution(telemetry)

    # --------------------------------------------------------- attribution
    def _init_attribution(self, telemetry) -> None:
        """Create (or adopt) the run's MemoryAttributor and register this
        trainer's owner trees. Registration order is priority order on
        aliased arrays: the hydra trunk goes FIRST so the reference (which
        IS the base) and the merged-rollout leaves that alias non-adapted
        trunk arrays attribute to ``base_params``; the ``merged_rollout``
        owner then claims only the freshly merged copies."""
        from repro.obs import MemoryAttributor
        at = telemetry.attribution
        if at is None:
            at = telemetry.attribution = MemoryAttributor()
        if self.rl.engine == "hydra":
            at.register("base_params", lambda: self.base_params)
            at.register("reward_params", lambda: self.reward_adapter)
        else:
            at.register("ref_params", lambda: self.ref_params)
            at.register("reward_params", lambda: self.reward_params)
        at.register("actor_params", lambda: self.actor_state["params"])
        at.register("actor_opt", lambda: self.actor_state["opt"])
        at.register("critic_params", lambda: self.critic_state["params"])
        at.register("critic_opt", lambda: self.critic_state["opt"])
        # the ZeRO-3 rollout gather copies register BEFORE merged_rollout:
        # the merged tree's non-adapted leaves alias the gathered trunk,
        # and they are gather traffic, not freshly merged weights.
        # Under TP (shard.ntp > 1) the same copies are DP-gathered but stay
        # model-sharded at 1/ntp per device — a different animal in an OOM
        # report, so they get their own ``tp_gather`` owner (the _gen paths
        # pick the key by ntp; exactly one of the two is ever populated)
        at.register("zero_gather",
                    lambda: self._live_buffers.get("zero_gather"))
        at.register("tp_gather",
                    lambda: self._live_buffers.get("tp_gather"))
        at.register("merged_rollout",
                    lambda: self._live_buffers.get("merged_rollout"))
        at.register("rollout_buffers",
                    lambda: self._live_buffers.get("rollout"))
        at.register("experience",
                    lambda: self._live_buffers.get("experience"))
        self.memory.attributor = at

    # ------------------------------------------------------------- sharding
    @property
    def _gather_key(self) -> str:
        """Attribution owner of the rollout gather copies: ``zero_gather``
        in pure DP, ``tp_gather`` when the mesh has a model axis (the
        copies are DP-gathered but TP-resident at 1/ntp per device)."""
        return "tp_gather" if self._tp_mesh is not None else "zero_gather"

    def per_device_state_bytes(self) -> int:
        """Max-over-devices bytes of the persistent role state (params +
        optimizer moments) — the figure the ZeRO stages cut. Replicated
        trees cost full size per device; ZeRO-3 trees cost 1/ndp."""
        from repro.sharding import tree_per_device_bytes
        return tree_per_device_bytes(list(self._persistent_trees().values()))

    def _shard_batch(self, tree):
        """DP batch sharding per ``rl.batch_shard`` (DESIGN.md §3.6): place
        every batch-leading array in ``tree`` onto the data axis. Applied
        to the scoring batch and the training experience — the phases
        whose activations dominate — not to rollout (generation runs from
        the gathered compute copy on its own schedule). Reduction-order
        drift under a sharded batch is documented and accepted in
        throughput mode; strict mode refuses to fall back."""
        if self.shard is None or self.shard.ndp <= 1:
            return tree
        leaves = [x for x in jax.tree.leaves(tree)
                  if getattr(x, "ndim", 0) >= 1]
        if not leaves:
            return tree
        B = leaves[0].shape[0]
        ndp = self.shard.ndp
        if B % ndp != 0:
            if self.rl.batch_shard == "strict":
                raise ValueError(
                    f"batch_shard='strict': global batch {B} does not "
                    f"divide the DP size {ndp} — the batch would silently "
                    "replicate. Pad the batch or use "
                    "batch_shard='throughput'.")
            if not getattr(self, "_batch_shard_warned", False):
                self._batch_shard_warned = True
                import warnings
                warnings.warn(
                    f"RLHF batch {B} does not divide ndp={ndp}: "
                    "replicating the batch over the DP axis (state still "
                    "shards; see RLHFConfig.batch_shard)", stacklevel=3)
            return tree
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from repro.sharding import dp_axes
        mesh = self.shard.mesh
        dp = dp_axes(mesh)
        dp = dp if len(dp) > 1 else dp[0]

        def place(x):
            if getattr(x, "ndim", 0) < 1 or x.shape[0] != B:
                return x
            spec = P(dp, *([None] * (x.ndim - 1)))
            return jax.device_put(x, NamedSharding(mesh, spec))

        return jax.tree.map(place, tree)

    def _persistent_trees(self) -> Dict[str, Any]:
        out = {"actor_params": self.actor_state["params"],
               "actor_opt": self.actor_state["opt"],
               "critic_params": self.critic_state["params"],
               "critic_opt": self.critic_state["opt"]}
        if self.rl.engine == "hydra":
            out["base_params"] = self.base_params
            out["reward_params"] = self.reward_adapter
        else:
            out["ref_params"] = self.ref_params
            out["reward_params"] = self.reward_params
        return out

    # --------------------------------------------------------------- offload
    def _init_offload(self, rl: RLHFConfig):
        """Runtime host-offload: compile the phase plan, bind it to a
        parking lot over the trainer's state accessors, and do the initial
        placement (everything the first phase doesn't touch goes to host)."""
        from repro.offload import HostParkingLot, OffloadExecutor, OffloadPlan
        states = self._offload_states()
        # a programmatic reward_fn means score_reward never touches the
        # reward model: park it once at start instead of swapping it
        # host<->device every iteration
        unused = ("reward_params",) if self.reward_fn is not None else ()
        plan = OffloadPlan.compile(rl.offload, engine=rl.engine,
                                   states=states, frozen_unused=unused)
        self.offload_lot = HostParkingLot()
        self.offload = OffloadExecutor(plan, self.offload_lot, states,
                                       telemetry=self.telemetry)
        self.memory.offload = self.offload
        self.offload.start()

    def _offload_states(self) -> Dict[str, Any]:
        """name -> (get, set) accessors over the trainer's live trees. The
        setters repoint every alias (train-state dicts, engine adapter
        views) so parked device buffers have no surviving references."""

        def state_slot(state_attr, slot, alias=None):
            def get():
                return getattr(self, state_attr)[slot]

            def set_(v):
                getattr(self, state_attr)[slot] = v
                if alias is not None:
                    self.engine.adapters[alias] = v
            return (get, set_)

        if self.rl.engine == "separate":
            def attr(name):
                return (lambda: getattr(self, name),
                        lambda v: setattr(self, name, v))
            return {
                "actor_params": state_slot("actor_state", "params"),
                "actor_opt": state_slot("actor_state", "opt"),
                "critic_params": state_slot("critic_state", "params"),
                "critic_opt": state_slot("critic_state", "opt"),
                "ref_params": attr("ref_params"),
                "reward_params": attr("reward_params"),
            }

        # hydra: the swappable unit of the trunk is its *adapted-site*
        # subtree — exactly the leaves merge_adapter replaces; the merged
        # rollout copy aliases everything else, which must stay put
        from repro.models import lora as LORA
        lora_sites = self.engine.lora_sites()

        def get_base():
            return LORA.adapted_subtree(self.base_params, lora_sites)

        def set_base(subtree):
            new = LORA.with_adapted_leaves(self.base_params, lora_sites,
                                           subtree)
            self.base_params = new
            self.engine.base_params = new
            self.ref_params = new          # reference IS the base

        def reward_acc():
            def get():
                return self.reward_adapter

            def set_(v):
                self.reward_adapter = v
                self.engine.adapters["reward"] = v
            return (get, set_)

        return {
            "base_params": (get_base, set_base),
            "actor_params": state_slot("actor_state", "params",
                                       alias="actor"),
            "actor_opt": state_slot("actor_state", "opt"),
            "critic_params": state_slot("critic_state", "params",
                                        alias="critic"),
            "critic_opt": state_slot("critic_state", "opt"),
            "reward_params": reward_acc(),
        }

    # -------------------------------------------------------------- separate
    def _init_separate(self, actor_cfg, critic_cfg, rl, key):
        self.engine = None
        self.actor = Model(actor_cfg)
        self.critic = Model(critic_cfg, with_value=True)
        self.reward_model = Model(critic_cfg, with_value=True)
        self.ref = Model(actor_cfg)
        ks = jax.random.split(key, 2)

        # ZeRO plans (one per role tree) when a ShardedContext is threaded
        self.actor_plan = self.critic_plan = None
        if self.shard is not None:
            from repro.optim import make_optimizer
            a_shapes = jax.eval_shape(self.actor.init, ks[0])
            c_shapes = jax.eval_shape(self.critic.init, ks[1])
            self.actor_plan = self.shard.plan_params(
                actor_cfg, a_shapes, make_optimizer(actor_cfg.optimizer))
            self.critic_plan = self.shard.plan_params(
                critic_cfg, c_shapes, make_optimizer(critic_cfg.optimizer))

        self.actor_step = make_train_step(self.actor, actor_cfg, kind="ppo",
                                          lr=rl.lr, kl_coef=rl.kl_coef,
                                          shard=self.actor_plan)
        self.critic_step = make_train_step(self.critic, critic_cfg,
                                           kind="critic", lr=rl.critic_lr,
                                           shard=self.critic_plan)
        # under a plan the state is built on its ZeRO layout (params + opt
        # sharded over DP per stage), never whole on one device
        self.actor_state = init_train_state(self.actor, actor_cfg, ks[0],
                                            self.actor_step.optimizer,
                                            plan=self.actor_plan)
        self.critic_state = init_train_state(self.critic, critic_cfg, ks[1],
                                             self.critic_step.optimizer,
                                             plan=self.critic_plan)
        # reference = frozen copy of the (SFT) actor init; reward = frozen
        # copy of the critic init (same value-head structure — the reward
        # model is "a critic that stopped learning at preference time")
        self.ref_params = jax.tree.map(jnp.copy, self.actor_state["params"])
        self.reward_params = jax.tree.map(jnp.copy,
                                          self.critic_state["params"])

        ga = lambda p: p if self.actor_plan is None \
            else self.actor_plan.gather(p)
        gc_ = lambda p: p if self.critic_plan is None \
            else self.critic_plan.gather(p)
        # per-layer ZeRO-3 gather specs for the scoring forwards (None in
        # tree mode / unsharded — DESIGN.md §3.7)
        ls_a = getattr(self.actor_plan, "layer_specs", None)
        ls_c = getattr(self.critic_plan, "layer_specs", None)
        self._jit_actor_step = _jit_step(self.actor_step)
        self._jit_critic_step = _jit_step(self.critic_step)
        self._jit_logp = jax.jit(
            lambda p, b: self._token_logp(ga(p), b, ls_a))
        self._jit_values = jax.jit(
            lambda p, b: self.critic.forward_value(gc_(p), b,
                                                   layer_specs=ls_c))
        self._jit_reward = jax.jit(
            lambda p, b: self.reward_model.forward_value(gc_(p), b,
                                                         layer_specs=ls_c))

        # engine-bound callables: make_experience / train_step are the same
        # straight-line code for both engines over these seven.
        # Rollout generates from a gathered compute copy of the ZeRO-3
        # actor shards (below stage 3 gather_copy returns the live
        # buffers, owned=False); an owned copy is deleted deterministically
        # when the rollout phase ends — never left to the GC.
        def _gen(prompts, key):
            from repro.sharding import delete_tree
            p, owned = self.actor_state["params"], False
            if self.actor_plan is not None:
                p, owned = self.actor_plan.gather_copy(p)
                self._live_buffers[self._gather_key] = {"actor": p}
            try:
                return self.rollout.generate(p, {"tokens": prompts},
                                             self.rl.gen_len, key)
            finally:
                self._live_buffers.pop(self._gather_key, None)
                if owned:
                    delete_tree(p)

        self._gen = _gen
        self._old_logp = lambda b: self._jit_logp(
            self.actor_state["params"], b)
        self._ref_logp = lambda b: self._jit_logp(self.ref_params, b)
        self._values = lambda b: self._jit_values(
            self.critic_state["params"], b)
        self._reward_scores = lambda b: self._jit_reward(
            self.reward_params, b)

        def _actor_update(exp):
            self.actor_state, m = self._jit_actor_step(self.actor_state, exp)
            return m

        def _critic_update(cbatch):
            self.critic_state, m = self._jit_critic_step(self.critic_state,
                                                         cbatch)
            return m

        self._actor_update, self._critic_update = _actor_update, _critic_update

    # ----------------------------------------------------------------- hydra
    def _init_hydra(self, cfg: ModelConfig, rl: RLHFConfig, key):
        self.engine = ModelEngine(cfg, key, rank=rl.lora_rank,
                                  shard=self.shard)
        self.actor = self.engine.model          # shared headless trunk
        self.critic = self.reward_model = self.ref = self.actor
        self.base_params = self.engine.base_params
        base_plan = self.engine.base_plan
        a_plan = self.engine.adapter_plans.get("actor")
        c_plan = self.engine.adapter_plans.get("critic")

        self.actor_step = make_lora_train_step(self.actor, cfg, kind="ppo",
                                               lr=rl.lr, kl_coef=rl.kl_coef,
                                               shard=a_plan,
                                               base_shard=base_plan)
        self.critic_step = make_lora_train_step(self.actor, cfg,
                                                kind="critic",
                                                lr=rl.critic_lr,
                                                shard=c_plan,
                                                base_shard=base_plan)
        self.actor_state = init_lora_train_state(
            self.engine.adapters["actor"], self.actor_step.optimizer)
        self.critic_state = init_lora_train_state(
            self.engine.adapters["critic"], self.critic_step.optimizer)
        if a_plan is not None:
            self.actor_state = a_plan.place_state(self.actor_state)
            self.critic_state = c_plan.place_state(self.critic_state)
            self.engine.adapters["actor"] = self.actor_state["params"]
            self.engine.adapters["critic"] = self.critic_state["params"]
        # frozen roles: reference IS the base (no copy at all); reward is
        # the frozen reward adapter over the same base (seeded from the
        # critic adapter init inside ModelEngine)
        self.ref_params = self.base_params
        self.reward_adapter = self.engine.adapters["reward"]

        gb = lambda p: p if base_plan is None else base_plan.gather(p)
        gad = lambda plan: (lambda ad: ad if plan is None
                            else plan.gather(ad))
        ga, gc_ = gad(a_plan), gad(c_plan)
        rw_plan = self.engine.adapter_plans.get("reward")
        grw = gad(rw_plan)
        # per-layer ZeRO-3 gather of the frozen trunk (DESIGN.md §3.7)
        ls_b = getattr(base_plan, "layer_specs", None)
        self._jit_actor_step = _jit_step(self.actor_step)
        self._jit_critic_step = _jit_step(self.critic_step)
        self._jit_logp = jax.jit(
            lambda p, ad, b: self._token_logp_adapter(gb(p), ga(ad), b,
                                                      ls_b))
        self._jit_ref_logp = jax.jit(
            lambda p, b: self._token_logp_ref(gb(p), b, ls_b))
        self._jit_values = jax.jit(
            lambda p, ad, b: self.engine.values(gb(p), gc_(ad), b,
                                                layer_specs=ls_b))
        self._jit_reward = jax.jit(
            lambda p, ad, b: self.engine.values(gb(p), grw(ad), b,
                                                layer_specs=ls_b))

        # engine-bound callables (hydra flavor: the frozen trunk threads
        # through every call; rollout merges A·B into it once per phase).
        # The merge happens here rather than inside Rollout.generate so the
        # offload scheduler can park the trunk's now-redundant adapted
        # leaves for the duration of generation (offload="all"). Under a
        # mesh, the merge runs on gathered compute copies of the ZeRO-3
        # trunk shards (and the actor adapter) — merged generation and the
        # paged decode path both execute under the same mesh.
        def _gen(prompts, key):
            from repro.models.lora import delete_merged
            from repro.sharding import delete_tree
            adapter, owned_a = self.actor_state["params"], False
            base, owned_b = self.base_params, False
            if base_plan is not None:
                base, owned_b = base_plan.gather_copy(self.base_params)
                adapter, owned_a = a_plan.gather_copy(
                    self.actor_state["params"])
                # the gather copies are live Python-held trees for the
                # whole generation — own them in the attribution table
                # (the merged tree's non-adapted leaves alias ``base``)
                self._live_buffers[self._gather_key] = {
                    "base": base, "adapter": adapter}
            merged = self.actor.merge_adapter(base, adapter)
            # visible to the attribution engine for the duration of the
            # phase (the mid-phase rollout_decode sample sees it); the
            # non-adapted leaves alias the live trunk and attribute to
            # base_params (it registered first)
            self._live_buffers["merged_rollout"] = merged
            if self.offload is not None:
                self.offload.rollout_merged()
            try:
                ro = self.rollout.generate(merged, {"tokens": prompts},
                                           self.rl.gen_len, key)
                # live set changes inside this phase (merged weights serve,
                # trunk possibly parked): record it before the merged
                # leaves die at the boundary
                self.memory.sample("rollout_decode")
                return ro
            finally:
                # deterministic phase-boundary hygiene. Order matters:
                # delete_merged reads the adapter tree's structure first,
                # then the owned ZeRO-3 gather copies are dropped (below
                # stage 3 owned=False — merged aliases the LIVE base, and
                # only the freshly-merged leaves may die).
                delete_merged(merged, adapter.get("lora"))
                self._live_buffers.pop("merged_rollout", None)
                self._live_buffers.pop(self._gather_key, None)
                if owned_a:
                    delete_tree(adapter)
                if owned_b:
                    delete_tree(base)

        self._gen = _gen
        self._old_logp = lambda b: self._jit_logp(
            self.base_params, self.actor_state["params"], b)
        # reference logp IS the plain base forward — no ref replica
        self._ref_logp = lambda b: self._jit_ref_logp(self.base_params, b)
        self._values = lambda b: self._jit_values(
            self.base_params, self.critic_state["params"], b)
        self._reward_scores = lambda b: self._jit_reward(
            self.base_params, self.reward_adapter, b)

        # The donated step consumes the previous adapter arrays, so the
        # engine's adapter view is re-pointed at the updated train state —
        # engine.adapters always reads the live trained values.
        def _actor_update(exp):
            self.actor_state, m = self._jit_actor_step(
                self.actor_state, self.base_params, exp)
            self.engine.adapters["actor"] = self.actor_state["params"]
            return m

        def _critic_update(cbatch):
            self.critic_state, m = self._jit_critic_step(
                self.critic_state, self.base_params, cbatch)
            self.engine.adapters["critic"] = self.critic_state["params"]
            return m

        self._actor_update, self._critic_update = _actor_update, _critic_update

    # ------------------------------------------------------------------
    def _token_logp(self, params, batch, layer_specs=None):
        from repro.steps import _action_logp
        logits, _, _ = self.actor.forward(params, batch,
                                          layer_specs=layer_specs)
        return _action_logp(logits, batch["tokens"],
                            _prefix_len(self.actor_cfg))

    def _token_logp_adapter(self, params, adapter, batch, layer_specs=None):
        from repro.steps import _action_logp
        logits = self.engine.logits(params, adapter, batch,
                                    layer_specs=layer_specs)
        return _action_logp(logits, batch["tokens"],
                            _prefix_len(self.actor_cfg))

    def _token_logp_ref(self, params, batch, layer_specs=None):
        from repro.steps import _action_logp
        return _action_logp(
            self.engine.ref_logits(params, batch, layer_specs=layer_specs),
            batch["tokens"], _prefix_len(self.actor_cfg))

    # ----------------------------------------------------------- telemetry
    def _attach_sim_predictions(self, batch_size: int) -> None:
        """Run the traced allocator simulator once for THIS run's exact
        shape (engine, batch, lengths, offload level) and attach its
        per-phase predicted bytes to the memory manager, so every phase
        span carries a sim-vs-measured delta. One-time setup (lazy, at the
        first train_step); failures degrade to spans without predictions
        rather than killing the run."""
        try:
            from repro.core import (MemoryStrategy, build_rlhf_phases,
                                    run_iteration)
            from repro.models import layers as _L
            # build_rlhf_phases raises the flash threshold for its traces;
            # restore it so telemetry can never perturb the run's numerics
            flash_min = _L.FLASH_MIN_ELEMS
            try:
                ph, persist = build_rlhf_phases(
                    self.actor_cfg, self.critic_cfg, batch=batch_size,
                    prompt_len=self.rl.prompt_len, gen_len=self.rl.gen_len,
                    engine=self.rl.engine, lora_rank=self.rl.lora_rank,
                    grad_ckpt=(self.actor_cfg.remat == "full"),
                    ppo_epochs=self.rl.ppo_epochs, min_bytes=2048)
            finally:
                _L.FLASH_MIN_ELEMS = flash_min
            strat = MemoryStrategy(
                "None", offload=self.rl.offload,
                grad_ckpt=(self.actor_cfg.remat == "full"))
            ndp = ntp = 1
            if self.shard is not None:
                # predict the run's REAL dp x tp layout: per-group
                # fractions traced from the same spec trees the runtime
                # placed its state with (core.strategies.traced_strategy)
                from repro.core.strategies import traced_strategy
                ndp, ntp = self.shard.ndp, self.shard.ntp
                strat = dataclasses.replace(
                    strat, zero_stage=self.shard.zero_stage,
                    gather_mode=self.shard.strat.gather_mode, ntp=ntp)
                strat = traced_strategy(
                    strat, self.actor_cfg, self.critic_cfg, ndp=ndp,
                    engine=self.rl.engine, lora_rank=self.rl.lora_rank)
            r = run_iteration(
                ph, persist, strat,
                "none", ndp=ndp, ntp=ntp, trainable_fraction=1.0,
                capacity=None)
            sim: Dict[str, dict] = {}
            for rec in r.phase_records:
                name = "rollout" if rec.name.startswith("rollout") \
                    else rec.name
                cur = sim.setdefault(name, {"sim_bytes": 0,
                                            "sim_peak_bytes": 0})
                cur["sim_bytes"] = rec.allocated_end
                cur["sim_peak_bytes"] = max(cur["sim_peak_bytes"],
                                            rec.alloc_peak)
                # the simulator's per-state ledger at this boundary — the
                # sim side of the per-owner measured-vs-sim diff (for a
                # collapsed rollout, the last sub-phase record wins, same
                # as sim_bytes)
                if rec.state_bytes_end:
                    cur["sim_owner_bytes"] = dict(rec.state_bytes_end)
            self.memory.sim_phase_bytes = sim
        except Exception as e:                        # pragma: no cover
            import warnings
            warnings.warn(f"telemetry: simulator prediction unavailable "
                          f"({e!r}); phase spans carry measured bytes only",
                          stacklevel=2)

    def _maybe_record_compiled(self, program: str, fn, *args) -> None:
        """Per-jitted-program compiled-memory accounting: feed XLA's
        ``memory_analysis()`` temp/arg/output bytes for ``program`` into
        the metrics registry, once. Lowering only traces (never executes),
        so like the simulator replay this is one-time setup excluded from
        the tracer's self-time. Pre-jitted ZeRO steps (two programs with
        an eager re-shard between) expose no ``.lower`` and are skipped."""
        if self.telemetry is None or program in self._compiled_recorded:
            return
        self._compiled_recorded.add(program)
        if not hasattr(fn, "lower"):
            return
        from repro.obs import record_compiled_memory
        record_compiled_memory(self.telemetry.registry, program, fn, *args)

    def _record_compiled_programs(self, batch) -> None:
        """Compiled-memory stats for the four scoring programs (lazy, at
        the first make_experience — the args are the real batch)."""
        if self.telemetry is None or "score_old_logp" in \
                self._compiled_recorded:
            return
        rec = self._maybe_record_compiled
        try:
            if self.rl.engine == "hydra":
                rec("score_old_logp", self._jit_logp, self.base_params,
                    self.actor_state["params"], batch)
                rec("score_ref", self._jit_ref_logp, self.base_params, batch)
                rec("score_values", self._jit_values, self.base_params,
                    self.critic_state["params"], batch)
                if self.reward_fn is None:
                    rec("score_reward", self._jit_reward, self.base_params,
                        self.reward_adapter, batch)
            else:
                rec("score_old_logp", self._jit_logp,
                    self.actor_state["params"], batch)
                rec("score_ref", self._jit_logp, self.ref_params, batch)
                rec("score_values", self._jit_values,
                    self.critic_state["params"], batch)
                if self.reward_fn is None:
                    rec("score_reward", self._jit_reward,
                        self.reward_params, batch)
        except Exception:                             # pragma: no cover
            pass

    def _role_gather_bytes(self) -> Dict[str, int]:
        """Analytic ZeRO-3 all-gather bytes per update program (cached):
        what the in-jit tree/layer gathers move each time the actor /
        critic step runs — Python can't observe in-scan collectives, so
        the counter is fed from the plan (DESIGN.md §4)."""
        if self._gather_step_bytes is None:
            ga = gc_ = 0
            if self.rl.engine == "hydra":
                bp = self.engine.base_plan
                trunk = 0 if bp is None else \
                    bp.gathered_bytes(self.base_params)

                def role_bytes(role):
                    pl = self.engine.adapter_plans.get(role)
                    ad = self.engine.adapters[role]
                    return trunk + (0 if pl is None
                                    else pl.gathered_bytes(ad))

                ga, gc_ = role_bytes("actor"), role_bytes("critic")
            else:
                if self.actor_plan is not None:
                    ga = self.actor_plan.gathered_bytes(
                        self.actor_state["params"])
                if self.critic_plan is not None:
                    gc_ = self.critic_plan.gathered_bytes(
                        self.critic_state["params"])
            self._gather_step_bytes = {"train_actor": ga, "train_critic": gc_}
        return self._gather_step_bytes

    def _count_gather(self, program: str) -> None:
        if self.telemetry is None:
            return
        b = self._role_gather_bytes().get(program, 0)
        if b:
            self.telemetry.registry.counter(
                "sharding_step_gathered_bytes_total",
                "bytes all-gathered by ZeRO-3 per update program "
                "(analytic, from the TreePlan)").inc(b, program=program)

    def make_experience(self, prompts: jax.Array, key) -> Dict[str, Any]:
        """Phases 1-5: rollout + the four scoring inferences -> experience.
        Straight-line over the engine-bound callables from ``_init_*``, in
        the canonical order of ``core.phases.RLHF_PHASE_SEQUENCE`` (the
        order the offload plan prefetches against). Under TP the whole
        sequence runs with the mesh ambient (``ctx.use_mesh``) so the
        scoring programs trace with their "model" constraint hints live."""
        from repro.sharding import ctx as _sctx
        with _sctx.use_mesh(self._tp_mesh):
            return self._make_experience_inner(prompts, key)

    def _make_experience_inner(self, prompts: jax.Array, key):
        mm = self.memory
        ro = self._gen(prompts, key)
        self._live_buffers["rollout"] = {
            "tokens": ro.tokens, "logp": ro.logp, "mask": ro.mask}
        mm.boundary("rollout", "inference")

        batch = self._shard_batch({"tokens": ro.tokens})
        self._record_compiled_programs(batch)
        if self.reward_fn is not None:
            terminal = self.reward_fn(ro.tokens, ro.mask)
        else:
            rm = self._reward_scores(batch)
            idx = jnp.maximum(ro.mask.sum(-1).astype(jnp.int32) - 1, 0)
            terminal = jnp.take_along_axis(rm, idx[:, None], 1)[:, 0]
        mm.boundary("score_reward", "inference")
        ref_logp = self._ref_logp(batch)
        mm.boundary("score_ref", "inference")
        values = self._values(batch) * ro.mask
        mm.boundary("score_values", "inference")
        old_logp = self._old_logp(batch)
        mm.boundary("score_old_logp", "inference")

        rewards = kl_shaped_rewards(old_logp, ref_logp, terminal, ro.mask,
                                    kl_coef=self.rl.kl_coef)
        adv, returns = gae(rewards, values, ro.mask,
                           gamma=self.rl.gamma, lam=self.rl.lam)
        if self.rl.whiten_advantages:
            adv = whiten(adv, ro.mask)
        exp = self._shard_batch({
            "tokens": ro.tokens, "loss_mask": ro.mask,
            "advantages": adv, "old_logp": old_logp * ro.mask,
            "ref_logp": ref_logp * ro.mask, "returns": returns,
            "old_values": values,
        })
        exp["mean_reward"] = terminal.mean()
        return exp

    def train_step(self, prompts: jax.Array, key) -> Dict[str, float]:
        """One full PPO iteration (all seven phases). A caught XLA
        ``RESOURCE_EXHAUSTED`` is captured by the flight recorder (owner
        table + top buffers at the moment of death) and re-raised — the
        recorder observes, it never swallows."""
        try:
            return self._train_step_inner(prompts, key)
        except Exception as e:
            fl = self.memory._flight()
            if fl is not None and fl.is_oom(e):
                at = self.memory.attributor
                fl.record_oom(
                    e, snapshot_fn=(at.snapshot if at is not None else None),
                    live_bytes=live_device_bytes(), source="rlhf")
            raise

    def _train_step_inner(self, prompts: jax.Array, key) -> Dict[str, float]:
        if self.telemetry is not None:
            if self.telemetry.sim_delta and not self._sim_attached:
                self._sim_attached = True
                self._attach_sim_predictions(int(prompts.shape[0]))
            self.memory.iteration_start()
        exp = self.make_experience(prompts, key)
        # the copy (same arrays) keeps popped members attributed to the
        # experience owner for the rest of the iteration
        self._live_buffers["experience"] = dict(exp)
        mean_reward = float(exp.pop("mean_reward"))
        old_values = exp.pop("old_values")
        if self.rl.engine == "hydra":
            self._maybe_record_compiled("train_actor", self._jit_actor_step,
                                        self.actor_state, self.base_params,
                                        exp)
        else:
            self._maybe_record_compiled("train_actor", self._jit_actor_step,
                                        self.actor_state, exp)
        metrics = {}
        for _ in range(self.rl.ppo_epochs):
            m = self._actor_update(exp)
            metrics.update({k: float(v) for k, v in m.items()})
            self._count_gather("train_actor")
        self.memory.boundary("train_actor", "training")
        cbatch = dict(exp, old_values=old_values)
        if self.rl.engine == "hydra":
            self._maybe_record_compiled("train_critic", self._jit_critic_step,
                                        self.critic_state, self.base_params,
                                        cbatch)
        else:
            self._maybe_record_compiled("train_critic", self._jit_critic_step,
                                        self.critic_state, cbatch)
        for _ in range(self.rl.ppo_epochs):
            mc = self._critic_update(cbatch)
            metrics.update({k: float(v) for k, v in mc.items()})
            self._count_gather("train_critic")
        self.memory.boundary("train_critic", "training", exp, cbatch)
        self._live_buffers.pop("rollout", None)
        self._live_buffers.pop("experience", None)
        metrics["mean_reward"] = mean_reward
        if self.telemetry is not None:
            self.memory.iteration_end(mean_reward=mean_reward)
        return metrics
