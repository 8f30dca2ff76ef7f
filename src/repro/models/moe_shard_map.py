"""Expert-parallel MoE via shard_map with explicit all-to-all dispatch.

GSPMD auto-sharding cannot partition a data-dependent scatter across the
expert axis (it falls back to replication — observed 1.6 TB/device temps on
deepseek-v3 train). This module owns the communication pattern explicitly:

  device grid = (dp = pod x data, model = M shards x E_loc experts each)

  per device (t = T / (ndp * M) local tokens):
    1. route local tokens (top-k over all E experts)
    2. bucket assignments by destination model-shard; capacity-drop into a
       send buffer [M, cap, D] (+ int payload carrying local-expert ids)
    3. all_to_all over the model axis              <- the MoE dispatch
    4. locally sort received rows by expert, run the [E_loc, C, D] x
       [E_loc, D, F] batched MXU matmul
    5. scatter results back into the recv layout, all_to_all back
    6. combine into the original token order with gate weights

  Every buffer is O(t * k * cf) per device; the sorts are over t*k elems.

Experts are zero-padded to a multiple of M when E % M != 0 (granite's 40
experts on a 16-way axis -> 48 padded; dead experts receive no rows). The
FSDP all-gather of expert weights happens outside (pjit inserts it because
the shard_map in_spec asks for dims the params shard over dp).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.sharding import ctx



def _dp_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def usable(cfg: ModelConfig, B: int, S: int) -> bool:
    """shard_map path applies when tokens tile the (dp, model) grid."""
    mesh = ctx.current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return False
    M = mesh.shape["model"]
    ndp = 1
    for a in _dp_axes(mesh):
        ndp *= mesh.shape[a]
    if M <= 1:
        return False
    if B % ndp or S % M:
        return False
    t = (B // ndp) * (S // M)
    return t * cfg.moe.top_k >= 4 * M


def moe_fwd_shard_map(params, x, cfg: ModelConfig, *,
                      capacity_factor: float = 1.25):
    """x [B, S, D] -> (y [B, S, D], aux). Requires usable(cfg, B, S)."""
    mesh = ctx.current_mesh()
    e: MoEConfig = cfg.moe
    B, S, D = x.shape
    M = mesh.shape["model"]
    dp = _dp_axes(mesh)
    ndp = 1
    for a in dp:
        ndp *= mesh.shape[a]
    E = e.num_experts
    E_pad = M * (-(-E // M))
    k = e.top_k
    t = (B // ndp) * (S // M)
    cap = max(4, -(-int(math.ceil(t * k / M * capacity_factor)) // 4) * 4)
    C2 = max(4, -(-int(math.ceil(t * k / (E_pad // M) * capacity_factor)) // 4) * 4)
    E_loc = E_pad // M

    w_in, w_gate, w_out = params["w_in"], params["w_gate"], params["w_out"]
    if E_pad != E:
        padg = ((0, E_pad - E), (0, 0), (0, 0))
        w_in, w_gate, w_out = (jnp.pad(w, padg) for w in (w_in, w_gate, w_out))

    dpspec = dp if len(dp) > 1 else dp[0]

    def local(x_loc, router_w, w_in_l, w_gate_l, w_out_l):
        # x_loc [B/ndp, S/M, D] -> flat [t, D]
        xt = x_loc.reshape(t, D)
        logits = xt.astype(jnp.float32) @ router_w
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eids = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        # load-balance aux (global via pmean)
        me = probs.mean(0)
        cexp = jnp.zeros((E,), jnp.float32).at[eids.reshape(-1)].add(1.0) / (t * k)
        aux = E * jnp.sum(jax.lax.pmean(me, ("model",) + dp)
                          * jax.lax.pmean(cexp, ("model",) + dp))

        token_idx = jnp.repeat(jnp.arange(t), k)
        eid_flat = eids.reshape(-1)
        gate_flat = gates.reshape(-1)
        dshard = eid_flat // E_loc
        eloc = eid_flat % E_loc

        # ---- bucket by destination shard, capacity `cap` per shard
        order = jnp.argsort(dshard)
        ds_s, tok_s, el_s, gate_s = (dshard[order], token_idx[order],
                                     eloc[order], gate_flat[order])
        counts = jnp.zeros((M,), jnp.int32).at[dshard].add(1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(t * k) - starts[ds_s]
        keep = pos < cap
        slot = jnp.where(keep, ds_s * cap + pos, M * cap)
        send = jnp.zeros((M * cap, D), x.dtype).at[slot].set(
            jnp.take(xt, tok_s, axis=0), mode="drop")
        payload = jnp.full((M * cap,), E_loc, jnp.int32).at[slot].set(
            el_s, mode="drop")

        # ---- dispatch all-to-all over the model axis
        recv = jax.lax.all_to_all(send.reshape(M, cap, D), "model",
                                  split_axis=0, concat_axis=0, tiled=False)
        pl_recv = jax.lax.all_to_all(payload.reshape(M, cap), "model",
                                     split_axis=0, concat_axis=0, tiled=False)
        rows = recv.reshape(M * cap, D)
        peid = pl_recv.reshape(M * cap)                 # E_loc = invalid

        # ---- local expert dispatch (second bucket sort)
        order2 = jnp.argsort(peid)
        pe_s = peid[order2]
        counts2 = jnp.zeros((E_loc + 1,), jnp.int32).at[peid].add(1)
        starts2 = jnp.cumsum(counts2) - counts2
        pos2_s = jnp.arange(M * cap) - starts2[pe_s]
        keep2_s = (pos2_s < C2) & (pe_s < E_loc)
        slot2_s = jnp.where(keep2_s, pe_s * C2 + pos2_s, E_loc * C2)
        ebuf = jnp.zeros((E_loc * C2, D), x.dtype).at[slot2_s].set(
            jnp.take(rows, order2, axis=0), mode="drop")
        eb = ebuf.reshape(E_loc, C2, D)
        h = jnp.einsum("ecd,edf->ecf", eb, w_in_l)
        g = jnp.einsum("ecd,edf->ecf", eb, w_gate_l)
        out_e = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, w_out_l)
        out_rows = out_e.reshape(E_loc * C2, D)

        # ---- un-sort back into recv layout
        back = jnp.zeros((M * cap, D), x.dtype).at[order2].set(
            jnp.take(out_rows, jnp.minimum(slot2_s, E_loc * C2 - 1), axis=0)
            * keep2_s[:, None].astype(x.dtype), mode="drop")

        # ---- return all-to-all
        ret = jax.lax.all_to_all(back.reshape(M, cap, D), "model",
                                 split_axis=0, concat_axis=0, tiled=False)
        res_rows = ret.reshape(M * cap, D)

        # ---- combine in original token order
        contrib = jnp.take(res_rows, jnp.minimum(slot, M * cap - 1), axis=0)
        contrib = contrib * (gate_s * keep).astype(x.dtype)[:, None]
        y = jnp.zeros((t, D), x.dtype).at[tok_s].add(contrib)
        return y.reshape(x_loc.shape), aux

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dpspec, "model", None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(dpspec, "model", None), P()),
        check_vma=False)
    y, aux = fn(x, params["router"], w_in, w_gate, w_out)
    return y, aux * e.router_aux_coef
