"""Seed+position PRNG keying — the single source of truth for every
sampling site in the serving stack.

The contract (established by the shape-stable hot path, relied on by the
speculative verify path): row i's randomness depends ONLY on
(base_key, sampling.seed, absolute token position) — never on the row's
batch index, the padded batch size, or any process-global counter. The
sequential decode step, the packed-prefill boundary sample, the drafter's
proposal draws, and the target's verify draws at position p therefore all
derive the SAME key and the same categorical draw, which is what makes
speculative acceptance bit-identical to the non-speculative engine.

Both `model_runner.sample` (the fallback batch sampler) and the fused
decode/verify steps route through `fold_key` / `sample_rows_impl`; deriving
the key anywhere else is a bug (drift here silently breaks spec-vs-baseline
token parity).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fold_key(base_key, seed, pos):
    """The per-draw PRNG key: fold the request's sampling seed, then the
    absolute position of the token being sampled, into the engine's base
    key. `seed` / `pos` may be scalars or arrays (folded elementwise by
    callers via vmap)."""
    return jax.random.fold_in(jax.random.fold_in(base_key, seed), pos)


def kth_largest(x, k):
    """Exact k-th largest value of each row: x (B, V) float32, k (B,)
    int32 in [1, V]. The top-k threshold, found by bisection over an
    order-preserving int32 view of the floats (32 counting passes over the
    row) instead of a full-row sort: the same value a descending sort puts
    at index k-1, ties included. Compiled for a TPU v5e it builds in under
    a second; a sort over a 152k-token vocab took about twenty."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    # negative floats order backwards as ints: flip their magnitude bits
    keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)

    def bisect(i, lo):
        # largest t with count(keys >= t) >= k, built from the top bit down
        step = jnp.left_shift(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        cand = (lo.astype(jnp.uint32) + step).astype(jnp.int32)
        enough = jnp.sum(keys >= cand[:, None], axis=-1) >= k
        return jnp.where(enough, cand, lo)

    lo = jnp.full(x.shape[:1], jnp.iinfo(jnp.int32).min, jnp.int32)
    kth = jax.lax.fori_loop(0, 32, bisect, lo)
    kth = jnp.where(kth < 0, kth ^ jnp.int32(0x7FFFFFFF), kth)
    return jax.lax.bitcast_convert_type(kth, jnp.float32)


def sample_rows_impl(logits, base_key, seeds, pos, temps, top_ks):
    """Per-row sampling, batch-shape-invariant and run-stable.

    logits: (B, V); seeds/pos: (B,) int32 identity of each draw (the
    request's sampling seed and the sampled token's position); temps: (B,)
    float32 (<= 0 => greedy); top_ks: (B,) int32 (0 => disabled).
    Row i's randomness depends only on (base_key, seeds[i], pos[i]) — NOT
    on i, B, or any process-global counter — so padded/bucketed batches
    sample identical tokens and reruns reproduce.
    """
    lg = logits.astype(jnp.float32)
    V = lg.shape[-1]
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)

    def topk_mask():
        kth = kth_largest(lg, jnp.clip(top_ks, 1, V))[:, None]    # (B, 1)
        return jnp.where((top_ks[:, None] > 0) & (lg < kth), -jnp.inf, lg)

    def stochastic():
        masked = jax.lax.cond(jnp.any(top_ks > 0), topk_mask, lambda: lg)
        scaled = masked / jnp.maximum(temps, 1e-6)[:, None]

        def draw(seed, p, row):
            return jax.random.categorical(fold_key(base_key, seed, p), row)

        sampled = jax.vmap(draw)(seeds, pos, scaled).astype(jnp.int32)
        return jnp.where(temps <= 0.0, greedy, sampled)

    # all-greedy batches (the common case) skip the sort + categorical
    return jax.lax.cond(jnp.any(temps > 0.0), stochastic, lambda: greedy)
