"""Weights from the seed, made on the device in one jitted call.

The configuration's architecture module (arch/) gives the leaves, in the
layout the program's engine takes, and each weight leaf's scale; every
leaf is made in the dtype the configuration serves, from its own key of a
split over the sorted leaf paths. Norm gains (leaves with no scale) are
1 + 0.1 * N(0, 1), so that a path which drops a gain shows in the
comparison with the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import arch


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A threefry key from any whole seed (wider than 32 bits too)."""
    words = np.random.SeedSequence([seed % 2**64, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


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
    mod = arch.load(config)
    flat = {}
    paths = sorted(mod.shapes(config).items())
    keys = jax.random.split(key, len(paths))
    for k, (path, shp) in zip(keys, paths):
        s = mod.scale(path, config)
        if s is None:
            x = 1.0 + 0.1 * jax.random.normal(k, shp, jnp.float32)
            flat[path] = x.astype(dtype)
        else:
            flat[path] = (jax.random.normal(k, shp, dtype)
                          * jnp.asarray(s, dtype))
    return _nest(flat)


def make_params(config: dict, seed: int, device=None) -> dict:
    """The whole parameter tree on `device`, from one jitted call."""
    dtype = jnp.dtype(config["dtype"])
    device = device or jax.devices()[0]
    mod = arch.load(config)
    items = (("arch", config["arch"]),) + mod.init_keys(config)
    fn = jax.jit(functools.partial(_init, config_items=items, dtype=dtype),
                 out_shardings=jax.sharding.SingleDeviceSharding(device))
    return fn(seed_key(seed))
