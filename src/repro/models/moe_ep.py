"""Explicit expert-parallel MoE via shard_map + all-to-all (beyond-paper).

The pjit path (moe.apply_moe) lets GSPMD choose collectives; with E=40
experts on a 16-way 'model' axis it falls back to TP-within-expert and
pays reduce-scatter-sized partial sums per layer (EXPERIMENTS §Perf iter 8).
This path takes manual control instead — the classic EP schedule:

  per device (data row x model col): route LOCAL tokens -> build a
  (E_pad, C_loc, d) dispatch -> all_to_all over 'model' (each device
  receives its E_pad/16 experts' tokens from all 16 peers) -> local expert
  FFN -> all_to_all back -> local combine.

Cross-device traffic = 2 all-to-alls of the dispatched tokens (~top_k x
capacity_factor x activation bytes), with NO partial-sum all-reduce.
Experts are padded to a multiple of the axis size (dummy experts receive
only zero-gated slots). Differentiable (shard_map + all_to_all transpose).

Opt-in: `transformer` uses it when `repro.models.moe_ep.ENABLE` is set and
the mesh fits; everything else keeps the pjit path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import os

from repro.configs.base import ModelConfig
from repro.distributed.partition import active_mesh
from repro.models.moe import _dispatch_group, _topk_iterative, capacity


def enabled() -> bool:
    return os.environ.get("REPRO_MOE_EP", "") == "1"


def ep_applicable(cfg: ModelConfig, x_shape) -> bool:
    """Mesh context present with the axes + divisibility the EP schedule
    needs (G % data == 0, T % model == 0)."""
    m = active_mesh()
    if m is None:
        return False
    if not ({"data", "model"} <= set(m.axis_names)):
        return False
    G, T, _ = x_shape
    return G % m.shape["data"] == 0 and T % m.shape["model"] == 0


def _pad_experts(p: dict, E_pad: int):
    E = p["w_gate"].shape[0]
    if E_pad == E:
        return p
    pad = ((0, E_pad - E), (0, 0), (0, 0))
    return {
        "router": p["router"],
        "w_gate": jnp.pad(p["w_gate"], pad),
        "w_up": jnp.pad(p["w_up"], pad),
        "w_down": jnp.pad(p["w_down"], pad),
    }


def apply_moe_ep(p: dict, x: jax.Array, cfg: ModelConfig):
    """x: (G, T, d) with G divisible by the 'data' axis and T divisible by
    the 'model' axis. Returns (y, aux) like apply_moe."""
    mesh = active_mesh()
    m = cfg.moe
    G, T, d = x.shape
    E, k = m.n_experts, m.top_k
    ep = mesh.shape["model"]
    dp = mesh.shape["data"]
    E_pad = ((E + ep - 1) // ep) * ep
    e_loc = E_pad // ep
    assert G % dp == 0 and T % ep == 0, (x.shape, mesh.shape)
    T_loc = (G // dp) * (T // ep)             # tokens per device
    C_loc = capacity(T_loc, cfg)

    pp = _pad_experts(p, E_pad)

    def body(xb, router, wg, wu, wd):
        # xb: (G/dp, T/ep, d) local tokens; wg/wu/wd: (e_loc, d, f)
        gl, tl, _ = xb.shape
        xt = xb.reshape(T_loc, d)
        logits = xt.astype(jnp.float32) @ router          # (T_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, eids = _topk_iterative(probs, k)       # (T_loc, k)
        gate_vals = gate_vals / jnp.clip(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        # aux loss from local stats; mean over devices via psum
        me = jax.lax.pmean(probs.mean(0), ("data", "model"))
        ce = jax.lax.pmean(
            jnp.zeros(E).at[eids.reshape(-1)].add(1.0) / (T_loc * k),
            ("data", "model"))
        aux = m.router_aux_coef * E * jnp.sum(me * ce)

        slot_tok, slot_gate = _dispatch_group(gate_vals, eids, E_pad, C_loc)
        xe = jnp.take(xt, slot_tok, axis=0).reshape(E_pad, C_loc, d)
        xe = xe * (slot_gate.reshape(E_pad, C_loc, 1) != 0)   # zero dummy slots

        # ---- all_to_all: (E_pad, C_loc, d) -> (e_loc, ep*C_loc, d)
        xr = jax.lax.all_to_all(xe, "model", split_axis=0, concat_axis=1,
                                tiled=True)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xr, wg))
        h = h * jnp.einsum("ecd,edf->ecf", xr, wu)
        yr = jnp.einsum("ecf,efd->ecd", h, wd)                # (e_loc, ep*C_loc, d)
        # ---- all_to_all back: -> (E_pad, C_loc, d)
        ye = jax.lax.all_to_all(yr, "model", split_axis=1, concat_axis=0,
                                tiled=True)

        yw = ye.reshape(E_pad * C_loc, d) * slot_gate[:, None].astype(ye.dtype)
        out = jnp.zeros((T_loc, d), ye.dtype).at[slot_tok].add(yw)
        return out.reshape(gl, tl, d), aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", "model", None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P("data", "model", None), P()),
    )(x, pp["router"], pp["w_gate"], pp["w_up"], pp["w_down"])
    return y, aux
