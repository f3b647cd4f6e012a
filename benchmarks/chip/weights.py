"""Weights from the seed, made on the device in one jitted call.

The parameter tree has the layout the program's engine takes (layers
stacked on a leading axis, head-major attention projections), in the dtype
the configuration serves. Scales follow the usual initialisation: inputs
of each projection at fan-in**-0.5, residual outputs further divided by
sqrt(2 * layers), embeddings at 0.02. Norm gains are 1 + 0.1 * N(0, 1), so
that a path which drops a gain shows in the comparison with the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.cell import head_dim


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A threefry key from any whole seed (wider than 32 bits too)."""
    words = np.random.SeedSequence([seed % 2**64, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def shapes(config: dict) -> dict:
    """{path: shape} of every leaf, in the program's layout."""
    d, f = config["hidden_size"], config["intermediate_size"]
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    hd, L, V = head_dim(config), config["num_hidden_layers"], config["vocab_size"]
    out = {
        "embedding": (V, d),
        "final_norm": (d,),
        "layers/ln1": (L, d),
        "layers/ln2": (L, d),
        "layers/attn/wq": (L, d, H, hd),
        "layers/attn/wk": (L, d, K, hd),
        "layers/attn/wv": (L, d, K, hd),
        "layers/attn/wo": (L, H, hd, d),
        "layers/mlp/w_up": (L, d, f),
        "layers/mlp/w_gate": (L, d, f),
        "layers/mlp/w_down": (L, f, d),
    }
    if not config["tie_word_embeddings"]:
        out["lm_head"] = (d, V)
    if config.get("qk_norm"):
        out["layers/attn/q_scale"] = (L, hd)
        out["layers/attn/k_scale"] = (L, hd)
    return out


def _scale(path: str, config: dict) -> float | None:
    """Standard deviation of a weight leaf; None for a norm gain."""
    d, f = config["hidden_size"], config["intermediate_size"]
    H, L = config["num_attention_heads"], config["num_hidden_layers"]
    resid = (2 * L) ** -0.5
    leaf = path.rsplit("/", 1)[-1]
    return {"embedding": 0.02, "lm_head": d ** -0.5,
            "wq": d ** -0.5, "wk": d ** -0.5, "wv": d ** -0.5,
            "wo": (H * head_dim(config)) ** -0.5 * resid,
            "w_up": d ** -0.5, "w_gate": d ** -0.5,
            "w_down": f ** -0.5 * resid}.get(leaf)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, x in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return tree


def _init(key, *, config_items: tuple, dtype) -> dict:
    config = dict(config_items)
    flat = {}
    paths = sorted(shapes(config).items())
    keys = jax.random.split(key, len(paths))
    for k, (path, shp) in zip(keys, paths):
        s = _scale(path, config)
        if s is None:
            x = 1.0 + 0.1 * jax.random.normal(k, shp, jnp.float32)
            flat[path] = x.astype(dtype)
        else:
            flat[path] = (jax.random.normal(k, shp, dtype)
                          * jnp.asarray(s, dtype))
    return _nest(flat)


def _hashable(config: dict) -> tuple:
    keep = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "tie_word_embeddings", "qk_norm")
    return tuple((k, config[k]) for k in keep if k in config)


def make_params(config: dict, seed: int, device=None) -> dict:
    """The whole parameter tree on `device`, from one jitted call."""
    dtype = jnp.dtype(config["dtype"])
    device = device or jax.devices()[0]
    fn = jax.jit(functools.partial(_init, config_items=_hashable(config),
                                   dtype=dtype),
                 out_shardings=jax.sharding.SingleDeviceSharding(device))
    return fn(seed_key(seed))
