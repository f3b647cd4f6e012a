"""Paged decode attention as a Pallas TPU kernel — the KV-cache hot spot
whose locality SkyLB's prefix-aware routing protects.

TPU adaptation of GPU paged attention (DESIGN §3): instead of per-warp
gathers, the grid walks (batch, kv-block) with the block axis innermost
and sequential. A block is `ppb` pages (`pages_per_block`, chosen from the
page's bytes). The K and V pools stay in HBM (`memory_space=pl.ANY`); the
kernel copies each live page of a block into a double-buffered VMEM slot
with its own async DMA, driven by the scalar-prefetched block table, and
starts the next live block's copies (the next row's first block at a
row's last) before it computes the current one. An online softmax over
all query heads for that sequence accumulates in f32 VMEM scratch. Pages
whose (K, hd) do not fill whole VMEM tiles are zero-padded to them first.

The grid is RAGGED per sequence: blocks wholly past a sequence's live edge
start no copy and run no compute, and inside the last live block only the
live pages are copied; the output is written at the sequence's last live
block, not at the grid edge. Consequence for callers: block-table entries
at or beyond a sequence's page count `ceil(seq_len / page)` are NEVER
dereferenced and may hold arbitrary int32 garbage (the jnp oracle
`ref.paged_decode_ref` implements the same contract). `seq_lens` must be
>= 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38

# Bytes of K (and as many of V) in one block. The double-buffered K and V
# slots take four times this, inside the 16 MiB of VMEM a v5e kernel is
# given by default with room for the body's f32 temporaries. Of blocks
# from 128 KiB to 2 MiB, 1 MiB ran fastest on a TPU v5e at both 32 KiB and
# 128 KiB pages (PERF.md, section 6).
BLOCK_BYTES = 1024 * 1024


def _tiled(K: int, hd: int, itemsize: int) -> tuple:
    """(K, hd) as a page is copied, in whole (sublane, lane) tiles: hd up
    to 128 lanes, K up to the rows one 32-bit sublane packs."""
    rows = max(1, 4 // itemsize)
    return -(-K // rows) * rows, -(-hd // 128) * 128


def page_bytes(page: int, K: int, hd: int, itemsize: int) -> int:
    """Bytes of one page of K (or V) as the kernel copies it."""
    Kt, hdt = _tiled(K, hd, itemsize)
    return page * Kt * hdt * itemsize


def pages_per_block(page: int, K: int, hd: int, itemsize: int,
                    npg: int) -> int:
    """Pages per compute block: as many as fit BLOCK_BYTES, at least one,
    at most the table's `npg`."""
    return max(1, min(npg, BLOCK_BYTES // page_bytes(page, K, hd, itemsize)))


def vmem_bytes(ppb: int, page: int, H: int, K: int, hd: int,
               itemsize: int) -> int:
    """VMEM the kernel asks for: two slots of K and V, the q and output
    tiles (double-buffered by the pipeline) and the f32 softmax state."""
    hdt = _tiled(K, hd, itemsize)[1]
    return (2 * 2 * ppb * page_bytes(page, K, hd, itemsize)
            + 2 * 2 * H * hdt * itemsize + (2 * H + H * hdt) * 4)


def _live_pages(seq_len, page: int):
    """Pages holding a sequence's tokens (seq_len >= 1)."""
    return (seq_len + page - 1) // page


def _kernel(bt_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref,
            *, page: int, ppb: int, group: int, scale: float):
    b, j = pl.program_id(0), pl.program_id(1)
    n_live = _live_pages(lens_ref[b], page)
    last = (n_live - 1) // ppb                    # this row's last live block

    def copies(row, blk, slot, act):
        # one K and one V copy per live page of block `blk` of `row`
        first = blk * ppb
        n = jnp.minimum(ppb, _live_pages(lens_ref[row], page) - first)

        def one(i, carry):
            pid = bt_ref[row, first + i]
            act(pltpu.make_async_copy(k_hbm.at[pid], k_buf.at[slot, i],
                                      sems.at[0, slot]))
            act(pltpu.make_async_copy(v_hbm.at[pid], v_buf.at[slot, i],
                                      sems.at[1, slot]))
            return carry

        jax.lax.fori_loop(0, n, one, 0)

    @pl.when((b == 0) & (j == 0))
    def _first():
        # pages past the edge of a last block are never copied: zeros, not
        # whatever VMEM held, meet their zero softmax weights
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        copies(0, 0, 0, lambda c: c.start())

    @pl.when(j <= last)
    def _live():
        slot = slot_ref[0]
        nrow = jnp.where(j < last, b, b + 1)
        nblk = jnp.where(j < last, j + 1, 0)

        @pl.when(nrow < pl.num_programs(0))
        def _prefetch():
            copies(nrow, nblk, 1 - slot, lambda c: c.start())

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        copies(b, j, slot, lambda c: c.wait())
        _attend(q_ref, k_buf.at[slot], v_buf.at[slot], m_ref, l_ref,
                acc_ref, start=j * ppb * page, seq_len=lens_ref[b],
                group=group, scale=scale)
        slot_ref[0] = 1 - slot

        @pl.when(j == last)
        def _out():
            o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                        ).astype(o_ref.dtype)


def _attend(q_ref, k_blk, v_blk, m_ref, l_ref, acc_ref, *, start, seq_len,
            group, scale):
    """Fold one block of K/V, (ppb, page, K, hd) in VMEM, into the online
    softmax of the row's query heads (`group` per KV head). The scores are
    one matmul of all H query heads against the block's (token, KV head)
    rows, (H, T*K) and lane-dense; entries of another head's rows or of
    tokens at or past `seq_len` are masked out. No relayout of K or V, at
    K times the (small) decode FLOPs."""
    ppb, page, K, hd = k_blk.shape
    T = ppb * page
    H = q_ref.shape[1]
    q = q_ref[0].astype(k_blk.dtype)                          # (H, hd)
    k = k_blk[...].reshape(T * K, hd)
    v = v_blk[...].reshape(T * K, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (H, T*K)
    col = jax.lax.broadcasted_iota(jnp.int32, (H, T * K), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (H, T * K), 0) // group
    live = (col % K == head) & (start + col // K < seq_len)
    s = jnp.where(live, s * scale, NEG_INF)
    m_prev = m_ref[...]                                       # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def paged_decode(q, k_pages, v_pages, block_table, seq_lens, *,
                 interpret: bool = False) -> jax.Array:
    """q: (B,H,hd); k_pages/v_pages: (P,page,K,hd); block_table: (B,NPG)
    int32 — entries beyond each sequence's live page count are never read
    and may be garbage; seq_lens: (B,), >= 1. Returns (B,H,hd)."""
    H, hd = q.shape[1:]
    K = k_pages.shape[2]
    assert H % K == 0
    # a page is copied in whole tiles: zero-pad it to them. Padded KV heads
    # serve no query head; padded lanes add zeros.
    Kt, hdt = _tiled(K, hd, k_pages.dtype.itemsize)
    if (Kt, hdt) != (K, hd):
        pad = ((0, 0), (0, 0), (0, Kt - K), (0, hdt - hd))
        q = jnp.pad(q, ((0, 0), (0, 0), (0, hdt - hd)))
        k_pages, v_pages = jnp.pad(k_pages, pad), jnp.pad(v_pages, pad)
    out = _call(q, k_pages, v_pages, block_table, seq_lens, group=H // K,
                scale=hd ** -0.5, interpret=interpret)
    return out[..., :hd]


def _call(q, k_pages, v_pages, block_table, seq_lens, *, group: int,
          scale: float, interpret: bool) -> jax.Array:
    B, H, hd = q.shape
    _, page, K, _ = k_pages.shape
    npg = block_table.shape[1]
    ppb = pages_per_block(page, K, hd, k_pages.dtype.itemsize, npg)
    kernel = functools.partial(_kernel, page=page, ppb=ppb, group=group,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # block_table, seq_lens
        grid=(B, pl.cdiv(npg, ppb)),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, j, bt, ln: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, j, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, K, hd), k_pages.dtype),  # K slots
            pltpu.VMEM((2, ppb, page, K, hd), v_pages.dtype),  # V slots
            pltpu.SemaphoreType.DMA((2, 2)),         # (K|V, slot)
            pltpu.SMEM((1,), jnp.int32),             # slot being computed
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
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_table, seq_lens, q, k_pages, v_pages)
