"""Plain float32 reference of a dense pre-norm decoder (Llama and Qwen3
families), independent of the program: the forward pass of the published
architecture over one whole sequence, with no cache, no batching, no
kernels.

  h = embed[tokens]
  per layer:  x = rmsnorm(h) * g1
              q, k, v = x Wq, x Wk, x Wv           (per head; Qwen3 adds
                                                    rmsnorm on q and k)
              q, k = rope(q), rope(k)              (rotate-half pairs)
              h += softmax(q k^T / sqrt(hd) + causal) v  Wo
              x = rmsnorm(h) * g2
              h += (silu(x Wgate) * (x Wup)) Wdown
  logits = (rmsnorm(h) * gf) Wout                  (Wout = embed^T if tied)

Weights come in the served dtype and are widened to float32 one layer at a
time inside the layer loop, so the reference fits beside them on the chip.
Every product runs at `precision="highest"`.

Another architecture's reference module brings its own layer function and
shares the rest: `attend` (the attention above) and `gaps` (the pass, the
comparison and the control).

The control (`quant=True`) is the same pass with every matrix product's
inputs rounded to fp8 (e4m3: 3 mantissa bits, largest 448), scaled
weights per output channel and activations per token: the step below the
bfloat16 the configurations state. The rounding is written out in
float32 arithmetic, so it needs no fp8 type on the device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 1024


def _fp8(x, axis):
    """Round to e4m3 after scaling the largest magnitude along `axis` to
    448: 3 mantissa bits, exponents down to -6, subnormals below."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    y = x / s
    e = jnp.maximum(jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -9))),
                    -6.0)
    q = jnp.exp2(e - 3.0)
    return jnp.clip(jnp.round(y / q) * q, -448.0, 448.0) * s


def _mm(x, w, quant, w_axes):
    """x (..., k) times w (k, ...): float32, or fp8-rounded inputs."""
    if quant:
        x = _fp8(x, -1)
        w = _fp8(w, w_axes)
    return jnp.tensordot(x, w, axes=1, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def attend(x, a, *, eps, theta, qk_norm, quant):
    """Causal self-attention of the normed input `x` (T, d) over the whole
    sequence, through the output projection; `a` holds float32 weights."""
    T = x.shape[0]
    pos = jnp.arange(T)
    q = _mm(x, a["wq"], quant, 0)                      # (T, H, hd)
    k = _mm(x, a["wk"], quant, 0)                      # (T, K, hd)
    v = _mm(x, a["wv"], quant, 0)
    if qk_norm:
        q = _rms(q, a["q_scale"], eps)
        k = _rms(k, a["k_scale"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    H, K, hd = q.shape[1], k.shape[1], q.shape[2]
    G = H // K
    outs = []
    for c0 in range(0, T, Q_CHUNK):                    # query blocks
        qc = q[c0:c0 + Q_CHUNK].reshape(-1, K, G, hd)
        s = jnp.einsum("qkgd,tkd->kgqt", qc, k, precision=HI) * hd ** -0.5
        qpos = pos[c0:c0 + Q_CHUNK]
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)
        outs.append(o.reshape(-1, H, hd))
    o = jnp.concatenate(outs, 0)
    wo = a["wo"].reshape(H * hd, -1)
    return _mm(o.reshape(T, H * hd), wo, quant, 0)


def _layer(h, lp, *, arch, quant):
    eps, theta, qk_norm = arch
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    x = _rms(h, f32["ln1"], eps)
    h = h + attend(x, f32["attn"], eps=eps, theta=theta, qk_norm=qk_norm,
                   quant=quant)
    x = _rms(h, f32["ln2"], eps)
    m = f32["mlp"]
    up = _mm(x, m["w_up"], quant, 0)
    gate = _mm(x, m["w_gate"], quant, 0)
    h = h + _mm(jax.nn.silu(gate) * up, m["w_down"], quant, 0)
    return h, None


@functools.partial(jax.jit, static_argnames=("layer", "eps", "arch", "quant"))
def _forward(params, tokens, rows, *, layer, eps, arch, quant):
    """Logits at `rows` of the causal pass over `tokens`; `layer(h, lp,
    arch=arch, quant=quant)` is one layer's step of the scan."""
    h = params["embedding"][tokens].astype(jnp.float32)
    h, _ = jax.lax.scan(functools.partial(layer, arch=arch, quant=quant),
                        h, params["layers"])
    x = _rms(h[rows], params["final_norm"].astype(jnp.float32), eps)
    if "lm_head" in params:
        w = params["lm_head"].astype(jnp.float32)
    else:
        w = params["embedding"].astype(jnp.float32).T
    return _mm(x, w, quant, 0)                          # (rows, V)


@jax.jit
def _gap_of(logits, picked):
    """Per row: best logit minus the logit of the picked token."""
    got = jnp.take_along_axis(logits, picked[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=1) - got


def _arch(config: dict) -> tuple:
    return (float(config["rms_norm_eps"]), float(config["rope_theta"]),
            bool(config.get("qk_norm", False)))


def _padded(tokens: list, step: int = 512) -> np.ndarray:
    n = -(-len(tokens) // step) * step
    out = np.zeros(n, np.int32)
    out[:len(tokens)] = tokens
    return out


def gaps(params, prompt: tuple, served: list, control: bool, *, layer,
         eps: float, arch: tuple) -> dict:
    """Gaps of one served request, by the float32 reference whose layer is
    `layer` (with its static `arch` tuple).

    Position i of the output is predicted from the prompt and served[:i];
    the program's gap there is the reference's best logit minus its logit
    for served[i]. With `control`, also the gap of the token that the
    fp8 pass puts first at the same position, given the same prefix."""
    seq = list(prompt) + list(served[:-1])
    toks = jnp.asarray(_padded(seq))
    rows = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    run = functools.partial(_forward, params, toks, rows, layer=layer,
                            eps=eps, arch=arch)
    ref = run(quant=False)
    out = {"program": np.asarray(_gap_of(ref, jnp.asarray(served,
                                                          jnp.int32)))}
    if control:
        low = run(quant=True)
        out["control"] = np.asarray(_gap_of(ref, jnp.argmax(low, axis=1)))
    return out


def served_gaps(params, config: dict, prompt: tuple, served: list,
                control: bool = False) -> dict:
    """Gaps of one served request of a dense configuration (see gaps)."""
    arch = _arch(config)
    return gaps(params, prompt, served, control, layer=_layer, eps=arch[0],
                arch=arch)
