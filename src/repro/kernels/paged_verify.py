"""Ragged multi-query paged verify attention as a Pallas TPU kernel — the
target-model half of draft-k/verify-1 speculative decoding.

Extends `paged_decode` with a q axis of Q = k_spec+1 positions per
sequence: the engine writes the K/V of all Q candidate positions into the
paged pool first, then verifies them in one dispatch. The grid walks
(batch, kv-page) exactly like `paged_decode` — scalar-prefetched block
table drives the BlockSpec index map, scalar-prefetched `seq_lens` clamp
it to the sequence's last live page — but the online softmax accumulates
H*Q rows per sequence, and the causal mask is PER QUERY: with
`base = seq_len - Q` tokens already committed before this step, query qi
may attend positions < base + qi + 1 (its own just-written position and
everything before it, but none of the later candidates).

Contract (same garbage-past-ragged-edge rules as `paged_decode`):
`seq_lens` counts ALL valid tokens INCLUDING the Q candidate positions, so
`seq_lens >= Q` (inactive bucket-padding rows pass seq_len = Q and read
only scratch-page garbage that the caller discards); block-table entries
at or beyond ceil(seq_len / page) are never dereferenced and may hold
arbitrary int32 garbage. The jnp oracle `ref.paged_verify_ref` implements
the identical contract and reduces to `paged_decode_ref`'s math at Q=1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _last_page(seq_len, page: int):
    """Index of the last live page for a sequence (seq_len >= 1)."""
    return jnp.maximum(seq_len - 1, 0) // page


def _kernel(bt_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page: int, Q: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    seq_len = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * page < seq_len)
    def _body():
        q = q_ref[0].astype(jnp.float32)                     # (Q, H, hd)
        k = k_ref[0].astype(jnp.float32)                     # (page, K, hd)
        v = v_ref[0].astype(jnp.float32)
        _, H, hd = q.shape
        K = k.shape[1]
        G = H // K
        # fold the query axis into the grouped-query axis: row g*Q + qi
        qg = q.transpose(1, 0, 2).reshape(K, G * Q, hd)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)              # (K, G*Q, page)
        s = s * scale
        pos = j * page + jax.lax.broadcasted_iota(
            jnp.int32, (K, G * Q, page), 2)
        qi = jax.lax.broadcasted_iota(jnp.int32, (K, G * Q, page), 1) % Q
        # per-query causal edge: base = seq_len - Q committed tokens, then
        # query qi additionally sees candidates 0..qi (incl. itself)
        s = jnp.where(pos < seq_len - Q + qi + 1, s, NEG_INF)
        s = s.reshape(H * Q, page)
        m_prev = m_ref[...]                                  # (H*Q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                               # (H*Q, page)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pg = p.reshape(K, G * Q, page)
        pv = jax.lax.dot_general(
            pg, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)              # (K, G*Q, hd)
        acc_ref[...] = acc_ref[...] * alpha + pv.reshape(H * Q, hd)
        m_ref[...] = m_new

    @pl.when(j == _last_page(seq_len, page))
    def _out():
        acc = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)  # (H*Q, hd)
        hd = acc.shape[-1]
        HQ = acc.shape[0]
        H = HQ // Q
        K = k_ref.shape[2]
        G = H // K
        out = acc.reshape(K, G, Q, hd).transpose(2, 0, 1, 3).reshape(Q, H, hd)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_verify(q, k_pages, v_pages, block_table, seq_lens, *,
                 interpret: bool = False) -> jax.Array:
    """q: (B,Q,H,hd); k_pages/v_pages: (P,page,K,hd); block_table: (B,NPG)
    int32 — entries beyond each sequence's live page count are never read
    and may be garbage; seq_lens: (B,) TOTAL valid tokens including the Q
    candidates, >= Q. Returns (B,Q,H,hd)."""
    B, Q, H, hd = q.shape
    Ptot, page, K, _ = k_pages.shape
    npg = block_table.shape[1]
    assert H % K == 0

    def _kv_index(b, j, bt, ln):
        return (bt[b, jnp.minimum(j, _last_page(ln[b], page))], 0, 0, 0)

    kernel = functools.partial(_kernel, page=page, Q=Q, scale=hd ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # block_table, seq_lens
        grid=(B, npg),
        in_specs=[
            pl.BlockSpec((1, Q, H, hd), lambda b, j, bt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, K, hd), _kv_index),
            pl.BlockSpec((1, page, K, hd), _kv_index),
        ],
        out_specs=pl.BlockSpec((1, Q, H, hd), lambda b, j, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H * Q, 1), jnp.float32),     # running max
            pltpu.VMEM((H * Q, 1), jnp.float32),     # running denom
            pltpu.VMEM((H * Q, hd), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Q, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, seq_lens, q, k_pages, v_pages)
