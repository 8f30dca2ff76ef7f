"""ShardedContext: the mesh + rule set that makes ZeRO execution real for
the RLHF engines (DESIGN.md §3).

``sharding.rules`` builds PartitionSpecs; this module owns their *runtime*
application for the RLHF trainer: a :class:`ShardedContext` wraps a mesh
and a :class:`~repro.sharding.rules.ShardingStrategy` and hands out
:class:`TreePlan` objects — one per parameter tree (full model trees and
hydra LoRA adapters alike) — that know

  * the **state specs** the tree is stored under between steps (ZeRO-3
    shards params over the DP domain; 1/2 keep them replicated),
  * the **optimizer-state specs** (sharded over DP from ZeRO-1 up, via
    ``zero_opt_pspecs`` + the optimizer's ``init_specs``),
  * the **compute specs** — the state specs with the DP entries stripped
    (tensor-parallel entries survive): what a forward/backward gathers to.

TP composes orthogonally (DESIGN.md §9): with ``strat.ntp > 1`` every
spec set above carries the Megatron column/row "model" entries from
``rules.param_pspecs``/``adapter_pspecs``, and every gather in this module
— ``gather``, ``gather_copy``, the per-layer ``layer_specs`` — moves ONLY
the DP dimension. TP entries are never gathered: the model-sharded layout
IS the compute layout, at every ZeRO stage and in both gather modes.

The execution contract (validated bit-level on forced multi-device CPU,
see ``benchmarks/zero_smoke.py``): step functions gather parameters to the
compute specs *before* any matmul, run the loss/gradient computation on
the gathered (DP-replicated) values, clip on the replicated gradients, and
only then re-shard gradients onto the optimizer layout — a slice, not a
reduction, so every ZeRO stage reproduces the single-device arithmetic to
the last ulp while persistent state lives at 1/ndp per device.

The gather itself comes in two granularities
(``ShardingStrategy.gather_mode``, DESIGN.md §3.7):

  * ``"tree"``  — the whole parameter tree is constrained to the compute
    specs before the forward; the transient HBM peak is the full
    replicated model (what PR 4 shipped);
  * ``"layer"`` — scanned (stacked) leaves stay ZeRO-sharded at the step
    boundary and each ``jax.lax.scan`` iteration constrains only its own
    sliced layer period to the DP-stripped specs (``TreePlan.layer_specs``
    threaded into the scan body by ``Model._stack_fwd``). The gathered
    slice dies when the iteration exits (under remat, the backward
    re-gathers per layer from the saved *sharded* slice), so the
    transient peak is ONE layer period — exactly the ``layer_slice``
    schedule the allocator simulator has always charged ZeRO-3 for.
    Non-stacked leaves (embeddings, lm head, norms, value heads) still
    gather whole: they are touched at both ends of every forward.

Both modes run the same replicated arithmetic inside the scan body, so
they are bit-identical to each other and to the single device.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import (ShardingStrategy, adapter_pspecs,
                                  param_pspecs, zero_opt_pspecs)

_IS_SPEC = lambda x: isinstance(x, P)


def _constrain(tree, spec_tree, mesh):
    """with_sharding_constraint over a (tree, spec tree) pair — usable
    inside jit; the constraint is its own transpose, so gradients of a
    gathered tree re-shard automatically."""
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, s)),
        tree, spec_tree, is_leaf=lambda x: _IS_SPEC(x))


def _place(tree, spec_tree, mesh):
    """Committed device placement (outside jit): ``jax.device_put`` each
    leaf onto its NamedSharding. Re-placing an already-conforming leaf is
    a no-op (same buffers), so this is safe to call idempotently."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tree, spec_tree, is_leaf=lambda x: _IS_SPEC(x))


def delete_tree(tree) -> None:
    """Deterministically delete every device buffer in ``tree`` (phase
    boundary hygiene for owned copies — see ``TreePlan.gather_copy``)."""
    jax.tree.map(
        lambda x: x.delete()
        if hasattr(x, "delete") and not x.is_deleted() else None, tree)


def tree_per_device_bytes(tree) -> int:
    """Max-over-devices resident bytes of ``tree`` — the number that OOMs.
    Replicated leaves count full size (every device holds a copy); ZeRO-3
    leaves count 1/ndp. Host-committed (numpy) leaves count zero."""
    per: dict = {}
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            continue
        for s in shards:
            per[s.device] = per.get(s.device, 0) + s.data.nbytes
    return max(per.values()) if per else 0


@dataclass(frozen=True)
class TreePlan:
    """Sharding plan for one parameter tree (+ its optimizer state)."""
    mesh: Mesh
    strat: ShardingStrategy
    param_specs: Any               # state placement (ZeRO-3: DP-sharded)
    compute_specs: Any             # DP entries stripped (gather target)
    opt_specs: Optional[Any] = None
    # param-shaped layout of the optimizer shards (``zero_opt_pspecs``):
    # the *uniform* sharding every update-program operand — gradients
    # included — is eagerly placed on, so the elementwise optimizer math
    # is partitioned identically for params, grads, and moments. Mixed
    # layouts make XLA fuse (FMA) differently per operand and cost a ulp
    # (DESIGN.md §3).
    update_specs: Optional[Any] = None
    # per-layer gather mode (``ShardingStrategy.gather_mode == "layer"``
    # at ZeRO-3, DESIGN.md §3.7): ``layer_param_specs`` is the full-tree
    # gather target where stacked (scanned) leaves KEEP their sharded
    # state specs and only non-stacked leaves go to compute specs;
    # ``layer_specs`` is the per-segment list of NamedSharding trees for
    # one *sliced* layer period (DP stripped) that ``Model._stack_fwd``
    # constrains inside the scan body — the actual per-iteration
    # all-gather. Both None in "tree" mode / below stage 3.
    layer_param_specs: Optional[Any] = None
    layer_specs: Optional[Any] = None

    @property
    def gather_mode(self) -> str:
        return "layer" if self.layer_param_specs is not None else "tree"

    # ----------------------------------------------------------- in-jit
    def gather(self, params):
        """Constrain ``params`` to the gather target — the per-step
        all-gather of ZeRO-3 (a no-op below stage 3). In layer mode the
        stacked leaves stay sharded here; the per-layer gather happens
        inside the scan body (``layer_specs``)."""
        if self.layer_param_specs is not None:
            return _constrain(params, self.layer_param_specs, self.mesh)
        return _constrain(params, self.compute_specs, self.mesh)

    def place_grads(self, grads):
        """Eager re-shard of DP-identical gradients onto the update layout
        — a committed ``device_put`` slice between the grad and update
        programs, so the layout change can never exert sharding pressure
        on either graph (the bit-identity contract)."""
        if self.update_specs is None:
            return grads
        return _place(grads, self.update_specs, self.mesh)

    def place_update_params(self, params):
        """Params on the update layout: at ZeRO-3 these are the state
        buffers themselves; below, a transient 1/ndp slice copy so the
        update program sees uniformly-sharded operands."""
        if self.update_specs is None:
            return params
        return _place(params, self.update_specs, self.mesh)

    def constrain_update(self, params):
        """Pin param-shaped values to the uniform update layout (a
        same-layout constraint — never a reshard, so codegen-neutral)."""
        if self.update_specs is None:
            return params
        return _constrain(params, self.update_specs, self.mesh)

    def constrain_opt(self, opt):
        if self.opt_specs is None:
            return opt
        return _constrain(opt, self.opt_specs, self.mesh)

    # ------------------------------------------------------ out-of-jit
    def place_params(self, params):
        return _place(params, self.param_specs, self.mesh)

    def place_opt(self, opt):
        if self.opt_specs is None:
            return opt
        return _place(opt, self.opt_specs, self.mesh)

    def init_state(self, init_params, key, optimizer):
        """A ``{"params", "opt", "step"}`` train state built straight onto
        this plan's layout, so that no device ever holds the whole tree:
        ``init_params(key)`` runs on the host CPU and only each device's
        shard is transferred; the optimizer state is created sharded."""
        host = jax.devices("cpu")[0]
        with jax.default_device(host):
            params = init_params(jax.device_put(key, host))
        params = self.place_params(params)
        if self.opt_specs is None:
            opt = optimizer.init(params)
        else:
            opt = jax.jit(optimizer.init, out_shardings=jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), self.opt_specs,
                is_leaf=_IS_SPEC))(params)
        return {"params": params, "opt": opt,
                "step": jnp.zeros((), jnp.int32)}

    def place_state(self, state):
        """Place a ``{"params", "opt", "step"}`` train state."""
        out = dict(state)
        out["params"] = self.place_params(state["params"])
        if "opt" in state:
            out["opt"] = self.place_opt(state["opt"])
        return out

    def gather_copy(self, params):
        """Materialize a DP-gathered copy of ``params`` (committed
        ``device_put`` onto the compute shardings) for rollout / merged
        generation. Under TP the copies stay model-sharded — only the DP
        dimension is gathered, so the per-device cost of a rollout copy is
        1/ntp of the tree (the trainer attributes it to the ``tp_gather``
        owner instead of ``zero_gather``). Returns ``(tree, owned)``:

          * ``owned=False`` (below ZeRO-3): the compute specs equal the
            state specs, so the returned tree is the SAME buffers as the
            live state — the caller must NOT delete it;
          * ``owned=True`` (ZeRO-3): every leaf is a fresh buffer the
            caller owns and should ``delete_tree`` at the phase boundary.
            Leaves whose sharding is unchanged (replicated norms, value
            heads) are explicitly copied rather than aliased, so deleting
            the returned tree can never free live state.
        """
        if self.compute_specs is self.param_specs or self.strat.zero_stage < 3:
            return params, False

        def copy_leaf(x, s):
            ns = NamedSharding(self.mesh, s)
            if getattr(x, "sharding", None) is not None and \
                    x.sharding.is_equivalent_to(ns, x.ndim):
                # device_put would be a no-op sharing buffers with the
                # live state; force a real copy so ownership is uniform
                return jnp.copy(x)
            return jax.device_put(x, ns)

        gathered = jax.tree.map(copy_leaf, params, self.compute_specs,
                                is_leaf=lambda x: _IS_SPEC(x))
        # telemetry: real bytes materialized by this gather (the rollout /
        # merged-generation copies) — counted on the process-global
        # registry so the frozen plan needs no telemetry handle threaded
        from repro.obs.metrics import global_registry
        global_registry().counter(
            "sharding_gather_copy_bytes_total",
            "bytes materialized by TreePlan.gather_copy (ZeRO-3 rollout "
            "gathers)").inc(
            sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(gathered)))
        return gathered, True

    def gathered_bytes(self, params) -> int:
        """Global bytes this plan all-gathers per step at ZeRO-3: the
        leaves whose state spec differs from the compute target. Tree and
        layer gather modes move the same total per step — layer mode just
        stages it one scan period at a time (DESIGN.md §3.7) — so one
        figure serves both; the RLHF trainer multiplies it into the
        ``sharding_step_gathered_bytes_total`` counter per update."""
        if self.strat.zero_stage < 3 or \
                self.compute_specs is self.param_specs:
            return 0
        total = 0

        def add(x, s, c):
            nonlocal total
            if s != c:
                total += getattr(x, "nbytes", 0)

        jax.tree.map(add, params, self.param_specs, self.compute_specs)
        return total

    # (per-device byte *accounting* lives in core.strategies —
    # ``traced_zero_scales`` / ``_tree_fraction`` — so the simulator and
    # the runtime read one implementation)


class ShardedContext:
    """Mesh + ZeRO strategy, threaded through trainer / engine / steps."""

    def __init__(self, mesh: Mesh, strat: Optional[ShardingStrategy] = None):
        self.mesh = mesh
        self.strat = strat or ShardingStrategy()

    @classmethod
    def create(cls, ndp: int = 1, *, zero_stage: int = 3, model: int = 1,
               gather_mode: str = "layer",
               devices=None) -> "ShardedContext":
        """Build a ``(data=ndp, model=ntp)`` mesh from the first
        ``ndp * model`` local devices (so an 8-device process can host both
        the ndp=1 baseline and the ndp=8 sharded run). ``model`` is the TP
        degree: the strategy records it as ``ntp`` so every spec the
        context emits partitions over dp x tp (DESIGN.md §9). Callers with
        a concrete ModelConfig should run ``rules.validate_tp(cfg, model)``
        first for the friendly divisibility error."""
        from repro.launch.mesh import make_zero_mesh
        assert gather_mode in ("layer", "tree"), gather_mode
        mesh = make_zero_mesh(ndp, model=model, devices=devices)
        # model == 1 keeps tensor_parallel off so the size-1 "model" axis
        # never decorates specs — the pre-TP (pure-ZeRO) spec trees, and
        # their bit-identity contract, are byte-for-byte unchanged
        return cls(mesh, ShardingStrategy(zero_stage=zero_stage,
                                          tensor_parallel=model > 1,
                                          ntp=model,
                                          gather_mode=gather_mode))

    @property
    def ndp(self) -> int:
        from repro.sharding.rules import _axsize, dp_axes
        return _axsize(self.mesh, dp_axes(self.mesh))

    @property
    def ntp(self) -> int:
        """Runtime TP degree — the mesh's "model" axis size (1 without)."""
        return dict(self.mesh.shape).get("model", 1)

    @property
    def zero_stage(self) -> int:
        return self.strat.zero_stage

    # ------------------------------------------------------------- plans
    def _plan(self, pspecs, shapes, optimizer, *,
              layerwise: bool = False) -> TreePlan:
        strat = self.strat
        opt_specs = update_specs = None
        if optimizer is not None:
            base = zero_opt_pspecs(pspecs, shapes, self.mesh, strat)
            opt_specs = optimizer.init_specs(base, shapes)
            # optimizers with element-crossing reductions (adafactor)
            # override the param-shaped update layout (DESIGN.md §3.3)
            upd = getattr(optimizer, "update_pspecs", None)
            update_specs = upd(base, shapes) if upd is not None else base
        compute = jax.tree.map(
            lambda s: _strip_dp(s, self.mesh), pspecs,
            is_leaf=_IS_SPEC) if strat.zero_stage >= 3 else pspecs
        layer_full = layer_slices = None
        if layerwise and strat.zero_stage >= 3 and \
                strat.gather_mode == "layer":
            layer_full, layer_slices = _layer_specs(pspecs, self.mesh)
        return TreePlan(self.mesh, strat, pspecs, compute,
                        opt_specs, update_specs,
                        layer_param_specs=layer_full,
                        layer_specs=layer_slices)

    def plan_params(self, cfg, params_shape, optimizer=None) -> TreePlan:
        """Plan for a full model tree (``rules.param_pspecs``).

        Per-layer gathers require every stacked leaf to be touched ONLY
        inside the scan body. Encoder-decoder models break that premise:
        ``Model._cross_kvs`` vmaps over the stacked decoder cross-attn
        weights before the scan, which under layer specs would all-gather
        them in-graph (a bit-identity hazard per DESIGN.md §3 rule 2) and
        re-materialize the whole stacked set at once. Those configs fall
        back to whole-tree gathers."""
        pspecs = param_pspecs(cfg, self.mesh, self.strat, params_shape)
        layerwise = getattr(cfg, "input_mode", "tokens") != "encdec"
        return self._plan(pspecs, params_shape, optimizer,
                          layerwise=layerwise)

    def plan_adapter(self, adapter_shape, optimizer=None) -> TreePlan:
        """Plan for a hydra LoRA adapter tree (``rules.adapter_pspecs``).
        Adapters always gather whole-tree: the per-role trees are
        paper-small, so the per-layer discipline buys nothing there."""
        pspecs = adapter_pspecs(self.mesh, self.strat, adapter_shape)
        return self._plan(pspecs, adapter_shape, optimizer)


def _layer_specs(pspecs, mesh):
    """Split a full-tree spec dict into the layer-gather pair
    ``(layer_param_specs, layer_specs)`` — see :class:`TreePlan`.

    Stacked decoder segments (top-level ``segment{i}`` keys — the trees
    ``jax.lax.scan`` slices per iteration) keep their sharded state specs
    in the full-tree target and contribute one *sliced* spec tree each
    (leading scan entry dropped, DP stripped, wrapped as NamedShardings so
    the scan body can constrain without a mesh context). Everything else
    — embeddings, lm head, final norm, value heads and the MTP head —
    gathers whole via DP-stripped compute specs. (Encoder-decoder
    configs never reach here: ``plan_params`` falls back to whole-tree
    gathers because ``_cross_kvs`` touches stacked decoder weights
    outside the scan.)"""
    if not isinstance(pspecs, dict):
        return None, None
    seg_keys = sorted((k for k in pspecs if k.startswith("segment")),
                      key=lambda k: int(k[len("segment"):]))
    if not seg_keys:
        return None, None
    full = {}
    for k, sub in pspecs.items():
        if k in seg_keys:
            full[k] = sub            # stays ZeRO-sharded at the boundary
        else:
            full[k] = jax.tree.map(lambda s: _strip_dp(s, mesh), sub,
                                   is_leaf=_IS_SPEC)

    real_mesh = isinstance(mesh, Mesh)   # SpecMesh (devices-free) keeps
    # bare PartitionSpecs — spec-level tests and traced accounting only

    def slice_spec(s: P):
        sp = _strip_dp(P(*tuple(s)[1:]), mesh)
        return NamedSharding(mesh, sp) if real_mesh else sp

    slices = [jax.tree.map(slice_spec, pspecs[k], is_leaf=_IS_SPEC)
              for k in seg_keys]
    return full, slices


def _strip_dp(spec: P, mesh) -> P:
    """Remove DP/FSDP axes from a spec, keeping tensor-parallel entries —
    the compute layout a ZeRO-3 gather targets."""
    from repro.sharding.rules import dp_axes
    dp = set(dp_axes(mesh))

    def keep(entry):
        if entry is None:
            return None
        es = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(e for e in es if e not in dp)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]

    return P(*(keep(e) for e in spec))
