"""Test fixture: the plain float32 reference of `moe_arch`'s decoder, on
the dense reference's attention and gap code. Per layer:

  h += attention(rmsnorm(h) * g1)
  x = rmsnorm(h) * g2
  p = softmax(x Wrouter); the top k experts e with gates p_e / sum p_e
  h += sum over those e of gate_e * (silu(x Wgate_e) * (x Wup_e)) Wdown_e

Every expert runs over every token and the gates of the others are 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip import reference as ref


def _layer(h, lp, *, arch, quant):
    eps, theta, k = arch
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    x = ref._rms(h, f32["ln1"], eps)
    h = h + ref.attend(x, f32["attn"], eps=eps, theta=theta, qk_norm=False,
                       quant=quant)
    x = ref._rms(h, f32["ln2"], eps)
    m = f32["moe"]
    probs = jax.nn.softmax(ref._mm(x, m["router"], quant, 0), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    E = probs.shape[-1]
    gates = jnp.sum(jax.nn.one_hot(idx, E) * (top / top.sum(-1, keepdims=True))
                    [..., None], axis=1)                       # (T, E)
    for e in range(E):
        up = ref._mm(x, m["w_up"][e], quant, 0)
        gate = ref._mm(x, m["w_gate"][e], quant, 0)
        y = ref._mm(jax.nn.silu(gate) * up, m["w_down"][e], quant, 0)
        h = h + gates[:, e:e + 1] * y
    return h, None


def served_gaps(params, config: dict, prompt: tuple, served: list,
                control: bool = False) -> dict:
    eps = float(config["rms_norm_eps"])
    return ref.gaps(params, prompt, served, control, layer=_layer, eps=eps,
                    arch=(eps, float(config["rope_theta"]),
                          int(config["num_experts_per_tok"])))
