import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh with real shardings but ShapeDtypeStruct inputs (no
allocation). Prints memory_analysis / cost_analysis and the collective
schedule; emits a JSON record per combination for EXPERIMENTS.md §Dry-run
and the roofline (§Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_2_3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] --out results.json
"""

import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.models import Model
from repro.sharding import (ShardedContext, ShardingStrategy, batch_pspecs,
                            cache_pspecs, opt_shardings, to_named,
                            validate_tp)
from repro.steps import (cache_specs, decode_window, input_specs,
                         make_decode_step, make_prefill_step, make_train_step,
                         sds)

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*\(")
_SHAPED = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective byte totals from optimized (per-device) HLO text.
    all-gather / all-reduce / all-to-all / permute: result bytes;
    reduce-scatter: first-operand bytes (the large buffer that moves)."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.search(
            r"=\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?", line)
        if not m:
            continue
        dtype, dims, op = m.group(1), m.group(2), m.group(3)
        if op == "reduce-scatter":
            # operand shape: first shaped arg inside parens
            rhs = line.split(op, 1)[1]
            ops_ = _SHAPED.findall(rhs)
            if ops_:
                dtype, dims = ops_[0]
        # tuple results print as (bf16[..], ..): fall back to per-line sum
        out[op] = out.get(op, 0) + _shape_bytes(dtype, dims)
    return out


def build_lowerable(arch: str, shape_name: str, mesh,
                    strat: ShardingStrategy = None):
    """Returns (fn, args, in_shardings, out_shardings) ready to lower."""
    from repro.sharding.ctx import set_current_mesh, set_segment_param_specs
    set_current_mesh(mesh)
    set_segment_param_specs(None)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    strat = strat or ShardingStrategy()
    # eager Megatron divisibility check (DESIGN.md §9): fail here with the
    # offending dims named, not as an XLA shape error deep inside lower()
    validate_tp(cfg, strat.ntp)
    # the same context the RLHF trainer threads: param/opt specs come from
    # its TreePlans, so the launch path and the runtime engines cannot
    # disagree about what a ZeRO stage means
    sctx = ShardedContext(mesh, strat)
    model = Model(cfg)
    window = decode_window(cfg, shape)

    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = sctx.plan_params(cfg, params_shape).param_specs
    bspecs = batch_pspecs(cfg, shape, mesh)
    batch = input_specs(cfg, shape)

    if shape.kind == "train":
        # (hillclimb C, refuted on this backend: per-layer param-slice
        # constraints via ctx.set_segment_param_specs did not convert the
        # grad all-reduce into reduce-scatter — GSPMD keeps AR+slice. The
        # mechanism stays available in sharding.ctx for TPU/Shardy runs.)
        step = make_train_step(model, cfg, kind="ppo")
        opt = step.optimizer
        opt_specs = sctx.plan_params(cfg, params_shape, opt).opt_specs
        opt_shape = jax.eval_shape(opt.init, params_shape)
        state_shape = {"params": params_shape, "opt": opt_shape,
                       "step": sds((), jnp.int32)}
        state_specs = {"params": pspecs, "opt": opt_specs, "step": P()}
        metric_keys = ("ppo_loss", "kl", "clip_frac", "loss", "grad_norm")
        if cfg.mtp_depth:
            metric_keys = metric_keys + ("mtp_loss",)
        out_specs = (state_specs, {k: P() for k in metric_keys})
        # optimizer state may target the host memory kind
        # (strat.offload_optimizer — the runtime face of cpu_offload)
        in_state_sh = {"params": to_named(mesh, pspecs),
                       "opt": opt_shardings(mesh, opt_specs, strat),
                       "step": NamedSharding(mesh, P())}
        in_sh = (in_state_sh,
                 to_named(mesh, {k: bspecs[k] for k in batch}))
        return (step, (state_shape, batch), in_sh, to_named(mesh, out_specs),
                (0,))  # donate the train state

    if shape.kind == "prefill":
        cap = shape.seq_len
        step = make_prefill_step(model, cfg, capacity=cap, window=window)
        cspecs = _cache_pspec_tree(model, cfg, shape, mesh, strat)
        out_specs = (P(_bspec(shape, mesh)), cspecs)
        in_sh = (to_named(mesh, pspecs),
                 to_named(mesh, {k: bspecs[k] for k in batch}))
        return (step, (params_shape, batch), in_sh, to_named(mesh, out_specs),
                ())

    # decode / long_decode
    step = make_decode_step(model, cfg, window=window)
    cshapes = cache_specs(model, cfg, shape)
    cspecs = _cache_pspec_tree(model, cfg, shape, mesh, strat)
    b = _bspec(shape, mesh)
    in_sh = (to_named(mesh, pspecs), to_named(mesh, cspecs),
             NamedSharding(mesh, P(b)), NamedSharding(mesh, P(b)))
    out_specs = (P(b, None), cspecs)
    args = (params_shape, cshapes, batch["token"], batch["position"])
    return step, args, in_sh, to_named(mesh, out_specs), (1,)  # donate caches


def _bspec(shape, mesh):
    from repro.sharding.rules import dp_axes, _axsize
    dp = dp_axes(mesh)
    if shape.global_batch % _axsize(mesh, dp) == 0 and _axsize(mesh, dp) > 1:
        return dp if len(dp) > 1 else dp[0]
    return None


def _cache_pspec_tree(model, cfg, shape, mesh, strat):
    from repro.steps import cache_capacity
    cshapes = cache_specs(model, cfg, shape)
    seg_specs = cache_pspecs(model, cfg, mesh, shape.global_batch, strat,
                             cshapes["segments"])
    specs = {"segments": seg_specs, "cross_kv": None}
    if cshapes["cross_kv"] is not None:
        b = _bspec(shape, mesh)
        mp = "model" if "model" in mesh.axis_names else None
        kvh = cfg.num_kv_heads
        tp = mp if (mp and kvh % mesh.shape[mp] == 0) else None
        specs["cross_kv"] = jax.tree.map(
            lambda x: P(None, b, None, tp, None), cshapes["cross_kv"])
    return specs


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            strat: ShardingStrategy = None, verbose: bool = True,
            mesh=None) -> dict:
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_desc = "x".join(str(s) for s in dict(mesh.shape).values())
    t0 = time.time()
    fn, args, in_sh, out_sh, donate = build_lowerable(arch, shape_name, mesh,
                                                      strat)
    lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=donate).lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_desc,
        "ok": True,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "bytes_per_device": {
            "arguments": mem.argument_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "temps": mem.temp_size_in_bytes,
            "aliased": mem.alias_size_in_bytes,
        },
        "flops_per_device": ca.get("flops", 0.0),
        "bytes_accessed_per_device": ca.get("bytes accessed", 0.0),
        "collective_bytes_per_device": coll,
    }
    if verbose:
        print(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--zero-stage", type=int, default=3, choices=(1, 2, 3),
                    help="ZeRO stage for the sharding strategy (paper R2)")
    ap.add_argument("--ndp", type=int, default=0,
                    help="with --ntp: data-parallel size of an explicit "
                         "(data=ndp, model=ntp) zero mesh instead of the "
                         "production mesh")
    ap.add_argument("--ntp", type=int, default=0,
                    help="declared TP degree: builds the mesh via "
                         "launch.mesh.make_zero_mesh(ndp, model=ntp), sets "
                         "ShardingStrategy.ntp, and eagerly validates the "
                         "Megatron divisibility contract (DESIGN.md §9)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    mesh = None
    if args.ndp or args.ntp:
        from repro.launch.mesh import make_zero_mesh
        ndp, ntp = max(args.ndp, 1), max(args.ntp, 1)
        strat = ShardingStrategy(zero_stage=args.zero_stage, ntp=ntp)
        mesh = make_zero_mesh(ndp, model=ntp)
    else:
        strat = ShardingStrategy(zero_stage=args.zero_stage)

    combos = []
    if args.all:
        combos = [(a, s) for a in ASSIGNED_ARCHS for s in SHAPES]
    else:
        # default to the smallest assigned arch / shortest shape so a bare
        # `--ndp 2 --ntp 2` invocation has something to compile
        combos = [(args.arch or ASSIGNED_ARCHS[0], args.shape or "train_4k")]

    records = []
    for arch, shape in combos:
        try:
            rec = run_one(arch, shape, multi_pod=args.multi_pod,
                          strat=strat, verbose=not args.all, mesh=mesh)
            status = "OK"
        except Exception as e:
            rec = {"arch": arch, "shape": shape,
                   "mesh": ("x".join(str(s) for s in dict(mesh.shape).values())
                            if mesh is not None else
                            ("2x16x16" if args.multi_pod else "16x16")),
                   "ok": False, "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
            status = f"FAIL {type(e).__name__}"
        records.append(rec)
        print(f"[dryrun] {arch:25s} {shape:12s} "
              f"{rec['mesh']:8s} {status}", flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(records, f, indent=1)
    n_ok = sum(r["ok"] for r in records)
    print(f"[dryrun] {n_ok}/{len(records)} combinations compiled")
    if n_ok < len(records):
        sys.exit(1)


if __name__ == "__main__":
    main()
