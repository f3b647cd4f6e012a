"""Paged decode attention as a Pallas TPU kernel — the KV-cache hot spot
whose locality SkyLB's prefix-aware routing protects.

TPU adaptation of GPU paged attention (DESIGN §3): instead of per-warp
gathers, the grid walks (batch, kv-page) with the page axis innermost and
sequential; the *scalar-prefetched* block table drives the BlockSpec index
map, so each step DMAs exactly one (page_size, K, hd) KV tile HBM->VMEM.
An online softmax over all query heads for that sequence accumulates in
VMEM scratch.

The grid is RAGGED per sequence: the scalar-prefetched `seq_lens` clamp the
BlockSpec index map to the sequence's last live page, so grid steps past a
sequence's real page count re-reference the tile already resident in VMEM
(Pallas elides the DMA when consecutive block indices coincide) and run no
compute; the output is written at the sequence's last live page, not at the
grid edge. Consequence for callers: block-table entries at or beyond a
sequence's page count `ceil(seq_len / page)` are NEVER dereferenced and may
hold arbitrary int32 garbage (the jnp oracle `ref.paged_decode_ref`
implements the same contract). `seq_lens` must be >= 1.
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
            m_ref, l_ref, acc_ref, *, page: int, npg: int, scale: float):
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
        q = q_ref[0].astype(jnp.float32)                     # (H, hd)
        k = k_ref[0].astype(jnp.float32)                     # (page, K, hd)
        v = v_ref[0].astype(jnp.float32)
        H, hd = q.shape
        K = k.shape[1]
        G = H // K
        qg = q.reshape(K, G, hd)
        # scores: (K, G, page)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)              # (K, G, page)
        s = s * scale
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (K, G, page), 2)
        s = jnp.where(pos < seq_len, s, NEG_INF)
        s = s.reshape(H, page)
        m_prev = m_ref[...]                                  # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                               # (H, page)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pg = p.reshape(K, G, page)
        pv = jax.lax.dot_general(
            pg, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)              # (K, G, hd)
        acc_ref[...] = acc_ref[...] * alpha + pv.reshape(H, hd)
        m_ref[...] = m_new

    # ragged early-out: the result is complete once this sequence's last
    # live page has been accumulated; later grid steps are no-ops
    @pl.when(j == _last_page(seq_len, page))
    def _out():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode(q, k_pages, v_pages, block_table, seq_lens, *,
                 interpret: bool = False) -> jax.Array:
    """q: (B,H,hd); k_pages/v_pages: (P,page,K,hd); block_table: (B,NPG)
    int32 — entries beyond each sequence's live page count are never read
    and may be garbage; seq_lens: (B,), >= 1. Returns (B,H,hd)."""
    B, H, hd = q.shape
    Ptot, page, K, _ = k_pages.shape
    npg = block_table.shape[1]
    assert H % K == 0

    def _kv_index(b, j, bt, ln):
        # clamp to the last live page: steps past the ragged edge re-issue
        # the previous index, so no fresh DMA lands and garbage table
        # entries are never dereferenced
        return (bt[b, jnp.minimum(j, _last_page(ln[b], page))], 0, 0, 0)

    kernel = functools.partial(_kernel, page=page, npg=npg, scale=hd ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # block_table, seq_lens
        grid=(B, npg),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, j, bt, ln: (b, 0, 0)),
            pl.BlockSpec((1, page, K, hd), _kv_index),
            pl.BlockSpec((1, page, K, hd), _kv_index),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, j, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),         # running max
            pltpu.VMEM((H, 1), jnp.float32),         # running denom
            pltpu.VMEM((H, hd), jnp.float32),        # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, seq_lens, q, k_pages, v_pages)
