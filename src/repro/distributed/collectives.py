"""Explicit-collective helpers for shard_map paths.

The pjit/GSPMD path lets XLA choose collectives; these helpers exist for
the places we take manual control:

- ``hierarchical_psum``: two-level gradient reduction for the multi-pod mesh
  — reduce-scatter within the pod (ICI), all-reduce the shards across pods
  (DCN), all-gather back within the pod. Cross-pod wire bytes drop from
  full-tensor to 1/pod_size of the tensor — the training-side mirror of the
  paper's 'aggregate where bandwidth is cheap, cross regions with the
  minimum' insight.
- ``compressed_hierarchical_psum``: same, with the DCN hop int8-compressed
  (training.compress) — stacking both cross-pod optimizations.
- ``ring_allgather``: ppermute ring all-gather, one hop per step, so XLA's
  latency-hiding scheduler can overlap each hop with compute (used by the
  overlap microbenchmark).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.training.compress import compressed_psum_sum


def psum_mean(tree: Any, axis_names) -> Any:
    n = 1
    for a in (axis_names if isinstance(axis_names, (tuple, list))
              else (axis_names,)):
        n *= jax.lax.axis_size(a)
    return jax.tree.map(lambda g: jax.lax.psum(g, axis_names) / n, tree)


def _flat_pad(x: jax.Array, parts: int) -> jax.Array:
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % parts
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat


def hierarchical_psum(tree: Any, *, inner_axis: str = "data",
                      outer_axis: str = "pod") -> Any:
    """Sum over (outer, inner) with minimal traffic on the outer (slow) hop:
    reduce-scatter(inner) -> psum(outer, on 1/inner of the bytes) ->
    all-gather(inner). Exact (no compression)."""
    inner_n = jax.lax.axis_size(inner_axis)

    def one(g):
        shape = g.shape
        flat = _flat_pad(g.astype(jnp.float32), inner_n)
        shard = jax.lax.psum_scatter(
            flat.reshape(inner_n, -1), inner_axis, scatter_dimension=0,
            tiled=False)                                   # (chunk,)
        shard = jax.lax.psum(shard, outer_axis)            # DCN hop: 1/inner bytes
        full = jax.lax.all_gather(shard, inner_axis, axis=0, tiled=False)
        return full.reshape(-1)[:g.size].reshape(shape).astype(g.dtype)

    return jax.tree.map(one, tree)


def compressed_hierarchical_psum(tree: Any, err_state: Any, *,
                                 inner_axis: str = "data",
                                 outer_axis: str = "pod") -> tuple:
    """hierarchical_psum with the cross-pod hop int8-compressed (+ error
    feedback on the shard). Returns (sums, new_err_state)."""
    inner_n = jax.lax.axis_size(inner_axis)

    def one(g, e):
        shape = g.shape
        flat = _flat_pad(g.astype(jnp.float32), inner_n)
        shard = jax.lax.psum_scatter(
            flat.reshape(inner_n, -1), inner_axis, scatter_dimension=0,
            tiled=False)
        summed, new_e = compressed_psum_sum(shard, e, outer_axis)
        full = jax.lax.all_gather(summed, inner_axis, axis=0, tiled=False)
        return (full.reshape(-1)[:g.size].reshape(shape).astype(g.dtype),
                new_e)

    flat_g, treedef = jax.tree_util.tree_flatten(tree)
    flat_e = jax.tree_util.tree_leaves(err_state)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (jax.tree_util.tree_unflatten(treedef, [o[0] for o in out]),
            jax.tree_util.tree_unflatten(treedef, [o[1] for o in out]))


def shard_error_state(params: Any, inner_n: int) -> Any:
    """Error-feedback buffers for compressed_hierarchical_psum: one buffer
    per REDUCE-SCATTERED shard (1/inner_n of each tensor, padded)."""
    def one(p):
        n = p.size
        chunk = (n + (-n) % inner_n) // inner_n
        return jnp.zeros((chunk,), jnp.float32)
    return jax.tree.map(one, params)


def ring_allgather(x: jax.Array, axis_name: str) -> jax.Array:
    """All-gather along axis_name via N-1 ppermute hops (overlappable)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    pieces = [x]
    cur = x
    for _ in range(n - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        pieces.append(cur)

    def at_slot(i):                     # piece j originated at rank idx - j
        return (idx - i) % n
    order = [at_slot(i) for i in range(len(pieces))]
    stacked = jnp.stack(pieces)         # [idx, idx-1, ...]
    inv = jnp.argsort(jnp.stack(order))
    return stacked[inv]
