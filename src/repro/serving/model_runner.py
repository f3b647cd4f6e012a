"""Jitted model steps for the serving engine (transformer family: dense /
MoE / early-fusion VLM).

Differs from repro.models.transformer's dense-cache path: the KV cache here
is a PAGED pool shared by all sequences —

    k_pages / v_pages: (L, P, page_size, K, hd)

with per-sequence block tables (vLLM layout: one page id list per sequence,
shared across layers; the L axis of the pool is carried by the layer scan).

The hot path is SHAPE-STABLE and single-dispatch-per-step:

  `decode_step`  consumes the backend's persistent device-resident batch
      state (block table, seq lens, last tokens, per-row sampling params)
      at its FULL capacity shape and slices the active `(nb, npgb)` bucket
      inside the jit, so the traced input shapes never change — the only
      compile keys are the static bucket dims, a small fixed set. It
      writes the new K/V, runs paged attention (Pallas on TPU, jnp oracle
      elsewhere), samples ON DEVICE with per-row temperature/top-k arrays,
      and folds the `lens += 1` / `toks = sampled` state advance into the
      same dispatch: one jitted call per engine iteration, with the
      sampled tokens staying resident for the next step's embedding
      lookup (the host only ever downloads them for bookkeeping).

  `prefill_pack_step`  admits SEVERAL sequences in one dispatch: their
      uncached suffixes are ragged-packed back-to-back along one token
      axis (SGLang-style) with per-token segment ids / positions / page
      destinations, each segment attending to its own radix-cached prefix
      gathered from a packed past-page list. New K/V rows scatter DIRECTLY
      into the pool (no gather->reshape->scatter round trip) and the
      boundary next token of every segment is sampled in the same
      dispatch.

  `prefill_step`  the one-request-at-a-time fallback (kept for parity
      tests and `packed_prefill=False`), with the same direct-scatter
      page write.

Sampling is batch-shape-invariant: each row draws from a PRNG key derived
from (the request's sampling seed, token position), never from the row's
position in the batch or the padded batch size — so bucketing cannot
change sampled tokens and reruns reproduce.

All functions are pure and jitted with donated pools; the backend holds the
pools and threads them through.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.layers import apply_mlp, embed_tokens, lm_logits, rms_norm
from repro.kernels import ops as kops
from repro.serving.sampling import (fold_key, kth_largest,
                                   sample_rows_impl as _sample_rows)


def kv_pool_spec(cfg: ModelConfig, n_pages: int, page_size: int,
                 dtype=jnp.bfloat16):
    shp = (cfg.n_layers, n_pages, page_size, cfg.n_kv_heads, cfg.hd)
    return (jax.ShapeDtypeStruct(shp, dtype),
            jax.ShapeDtypeStruct(shp, dtype))


def init_kv_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                 dtype=jnp.bfloat16, device=None):
    ks, vs = kv_pool_spec(cfg, n_pages, page_size, dtype)
    return (jnp.zeros(ks.shape, ks.dtype, device=device),
            jnp.zeros(vs.shape, vs.dtype, device=device))


def _ffn(lp, h, cfg: ModelConfig):
    if cfg.is_moe:
        y, _ = moe_mod.apply_moe(lp["moe"], h, cfg)
        return y
    return apply_mlp(lp["mlp"], h, cfg)


# ---------------------------------------------------------------- sampling
# The per-row implementation `_sample_rows` and the seed+position keying
# contract live in repro.serving.sampling (one source of truth shared with
# the speculative verify path); this module re-exports the jitted entries.

@jax.jit
def sample_rows(logits, base_key, seeds, pos, temps, top_ks):
    """Standalone jitted `sampling.sample_rows_impl` (sequential prefill)."""
    return _sample_rows(logits, base_key, seeds, pos, temps, top_ks)


@jax.jit
def sample(logits: jax.Array, key: jax.Array, *, temperature=0.0,
           top_k=0, seed=0, pos=0) -> jax.Array:
    """Fallback batch sampler, logits: (B, V) -> (B,) int32.

    `temperature` / `top_k` / `seed` / `pos` are TRACED scalars (one
    compiled program for every sampling config). The draw key derives from
    `sampling.fold_key(key, seed, pos)` — the same seed+position contract
    as the fused decode/verify paths, so a caller that passes the engine
    base key plus the request seed and token position reproduces exactly
    the hot path's draw.
    """
    lg = logits.astype(jnp.float32)
    V = lg.shape[-1]
    t = jnp.asarray(temperature, jnp.float32)
    k = jnp.asarray(top_k, jnp.int32)
    draw_key = fold_key(key, jnp.asarray(seed, jnp.int32),
                        jnp.asarray(pos, jnp.int32))
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)

    def topk_mask():
        ks = jnp.full(lg.shape[:1], jnp.clip(k, 1, V), jnp.int32)
        kth = kth_largest(lg, ks)[:, None]
        return jnp.where((k > 0) & (lg < kth), -jnp.inf, lg)

    def stochastic():
        masked = jax.lax.cond(k > 0, topk_mask, lambda: lg)
        scaled = masked / jnp.maximum(t, 1e-6)
        return jax.random.categorical(draw_key, scaled,
                                      axis=-1).astype(jnp.int32)

    return jax.lax.cond(t > 0.0, stochastic, lambda: greedy)


# ----------------------------------------------------------------- prefill

@functools.partial(jax.jit, static_argnames=("cfg", "page_size"),
                   donate_argnums=(3, 4))
def prefill_step(params: Any, tokens: jax.Array, new_pages: jax.Array,
                 k_pages: jax.Array, v_pages: jax.Array,
                 past_pages: jax.Array, past_len: jax.Array,
                 new_len: jax.Array, *, cfg: ModelConfig, page_size: int):
    """One-request prefill over the uncached suffix (sequential fallback).

    tokens:     (1, S_pad)   uncached suffix, right-padded
    new_pages:  (NP,) int32  page ids to write the suffix K/V into (padded
                             with a scratch page id; suffix starts at slot 0
                             of new_pages[0] — the engine never splits a
                             cached prefix mid-page)
    past_pages: (CP,) int32  radix-cached prefix pages (padded w/ scratch)
    past_len:   ()   int32   cached prefix token count
    new_len:    ()   int32   real suffix length (<= S_pad)
    Returns (logits_last (1, vocab), k_pages, v_pages).
    """
    S = tokens.shape[1]
    h = embed_tokens(params, tokens, cfg)          # compute in param dtype
    positions = past_len + jnp.arange(S, dtype=jnp.int32)[None, :]   # (1,S)
    # row i of the suffix scatters straight into page new_pages[i // ps],
    # slot i % ps (no gather->reshape->scatter round trip on the pool)
    rows = jnp.arange(S, dtype=jnp.int32)
    dest_page = new_pages[rows // page_size]
    dest_slot = rows % page_size

    def blk(carry, xs):
        h, kp, vp = carry
        lp, li = xs
        x = rms_norm(h, lp["ln1"], cfg.norm_eps)
        q = attn._project_q(lp["attn"], x, cfg, positions, rope=True)
        k_new, v_new = attn._project_kv(lp["attn"], x, cfg, positions, rope=True)
        k_new = k_new.astype(kp.dtype)
        v_new = v_new.astype(vp.dtype)
        # past K/V gathered from the radix-cached pages
        k_past = kp[li][past_pages].reshape(1, -1, cfg.n_kv_heads, cfg.hd)
        v_past = vp[li][past_pages].reshape(1, -1, cfg.n_kv_heads, cfg.hd)
        T_past = k_past.shape[1]
        k_all = jnp.concatenate([k_past, k_new], axis=1)
        v_all = jnp.concatenate([v_past, v_new], axis=1)
        # mask: past cols < past_len valid for all rows; new cols causal & < new_len
        qpos = jnp.arange(S, dtype=jnp.int32)
        past_cols = jnp.arange(T_past, dtype=jnp.int32)
        m_past = jnp.broadcast_to((past_cols < past_len)[None, :], (S, T_past))
        new_cols = jnp.arange(S, dtype=jnp.int32)
        m_new = (new_cols[None, :] <= qpos[:, None]) & (new_cols < new_len)[None, :]
        mask = jnp.concatenate([m_past, m_new], axis=1)[None, None]   # (1,1,S,T)
        o = attn._sdpa(q, k_all, v_all, mask, cfg)
        y = jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
        h = h + y
        h = h + _ffn(lp, rms_norm(h, lp["ln2"], cfg.norm_eps), cfg)
        kp = kp.at[li, dest_page, dest_slot].set(k_new[0])
        vp = vp.at[li, dest_page, dest_slot].set(v_new[0])
        return (h, kp, vp), None

    L = cfg.n_layers
    (h, k_pages, v_pages), _ = jax.lax.scan(
        blk, (h, k_pages, v_pages),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    last = jnp.clip(new_len - 1, 0, S - 1)
    logits = lm_logits(params, h[:, last][:, None], cfg)[:, 0]
    return logits, k_pages, v_pages


@functools.partial(jax.jit, static_argnames=("cfg", "page_size"),
                   donate_argnums=(6, 7))
def prefill_pack_step(params: Any, tokens: jax.Array, seg_ids: jax.Array,
                      positions: jax.Array, dest_page: jax.Array,
                      dest_slot: jax.Array, k_pages: jax.Array,
                      v_pages: jax.Array, past_pages: jax.Array,
                      past_start: jax.Array, past_len: jax.Array,
                      last_idx: jax.Array, temps: jax.Array,
                      top_ks: jax.Array, seeds: jax.Array,
                      sample_pos: jax.Array, base_key: jax.Array, *,
                      cfg: ModelConfig, page_size: int):
    """Packed ragged prefill: several sequences' uncached suffixes in ONE
    dispatch, each attending to its own cached prefix; the boundary next
    token of every segment is sampled on device in the same dispatch.

    Packed token axis (S = bucketed total, padding tokens have seg -1):
      tokens:     (S,) int32  suffix tokens, segments back-to-back
      seg_ids:    (S,) int32  segment index per token (-1 = padding)
      positions:  (S,) int32  absolute position (past_len[seg] + offset)
      dest_page:  (S,) int32  pool page the token's K/V scatters into
      dest_slot:  (S,) int32  slot within that page (padding -> scratch)
    Packed past-page axis (CP = bucketed total, padded with scratch):
      past_pages: (CP,) int32  all segments' cached-prefix pages, packed
    Per segment (NSEG = bucketed count):
      past_start: (NSEG,) int32  first past COLUMN (page offset * ps)
      past_len:   (NSEG,) int32  cached token count
      last_idx:   (NSEG,) int32  packed index of the segment's last token
      temps/top_ks/seeds/sample_pos: per-segment sampling rows
    Returns (tokens (NSEG,) int32, k_pages, v_pages).
    """
    S = tokens.shape[0]
    nseg = past_start.shape[0]
    h = embed_tokens(params, tokens[None, :], cfg)                 # (1,S,d)
    pos2 = positions[None, :]
    tseg = jnp.clip(seg_ids, 0, nseg - 1)
    tstart = past_start[tseg]                                      # (S,)
    tplen = past_len[tseg]

    tok_idx = jnp.arange(S, dtype=jnp.int32)
    # past col c valid for token t iff it falls in t's segment's window
    # (computed once; identical for every layer)
    CP = past_pages.shape[0]
    past_cols = jnp.arange(CP * page_size, dtype=jnp.int32)
    m_past = ((past_cols[None, :] >= tstart[:, None]) &
              (past_cols[None, :] < (tstart + tplen)[:, None]))    # (S,Tp)
    # new col u valid for token t iff same segment and causal; note this
    # includes every token's own diagonal (padding rows share seg -1), so
    # no row's softmax is ever all-masked
    m_new = ((seg_ids[None, :] == seg_ids[:, None]) &
             (tok_idx[None, :] <= tok_idx[:, None]))
    mask = jnp.concatenate([m_past, m_new], axis=1)[None, None]    # (1,1,S,T)

    def blk(carry, xs):
        h, kp, vp = carry
        lp, li = xs
        x = rms_norm(h, lp["ln1"], cfg.norm_eps)
        q = attn._project_q(lp["attn"], x, cfg, pos2, rope=True)
        k_new, v_new = attn._project_kv(lp["attn"], x, cfg, pos2, rope=True)
        k_new = k_new.astype(kp.dtype)
        v_new = v_new.astype(vp.dtype)
        k_past = kp[li][past_pages].reshape(1, -1, cfg.n_kv_heads, cfg.hd)
        v_past = vp[li][past_pages].reshape(1, -1, cfg.n_kv_heads, cfg.hd)
        k_all = jnp.concatenate([k_past, k_new], axis=1)
        v_all = jnp.concatenate([v_past, v_new], axis=1)
        o = attn._sdpa(q, k_all, v_all, mask, cfg)
        y = jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])
        h = h + y
        h = h + _ffn(lp, rms_norm(h, lp["ln2"], cfg.norm_eps), cfg)
        kp = kp.at[li, dest_page, dest_slot].set(k_new[0])
        vp = vp.at[li, dest_page, dest_slot].set(v_new[0])
        return (h, kp, vp), None

    L = cfg.n_layers
    (h, k_pages, v_pages), _ = jax.lax.scan(
        blk, (h, k_pages, v_pages),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(params, h[:, last_idx], cfg)[0]             # (NSEG,V)
    toks = _sample_rows(logits, base_key, seeds, sample_pos, temps, top_ks)
    return toks, k_pages, v_pages


# ------------------------------------------------------------------ decode

def _token_fwd(params, toks, positions, atn_lens, bt, page_ids, offsets,
               k_pages, v_pages, *, cfg: ModelConfig):
    """One single-token forward for a batch — the body shared by the fused
    decode step and the drafter's proposal steps: embed + per-layer KV
    write at (page_ids, offsets) + ragged paged attention over `atn_lens`
    tokens. Returns (logits (B, V), k_pages, v_pages)."""
    h = embed_tokens(params, toks[:, None], cfg)   # compute in param dtype

    def blk(carry, xs):
        h, kp, vp = carry
        lp, li = xs
        x = rms_norm(h, lp["ln1"], cfg.norm_eps)
        q = attn._project_q(lp["attn"], x, cfg, positions[:, None], rope=True)
        k_new, v_new = attn._project_kv(lp["attn"], x, cfg,
                                        positions[:, None], rope=True)
        kp = kp.at[li, page_ids, offsets].set(k_new[:, 0].astype(kp.dtype))
        vp = vp.at[li, page_ids, offsets].set(v_new[:, 0].astype(vp.dtype))
        o = kops.paged_decode(q[:, 0], kp[li], vp[li], bt, atn_lens)
        y = jnp.einsum("bhk,hkd->bd", o, lp["attn"]["wo"])[:, None]
        h = h + y
        h = h + _ffn(lp, rms_norm(h, lp["ln2"], cfg.norm_eps), cfg)
        return (h, kp, vp), None

    L = cfg.n_layers
    (h, k_pages, v_pages), _ = jax.lax.scan(
        blk, (h, k_pages, v_pages),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return lm_logits(params, h, cfg)[:, 0], k_pages, v_pages


@functools.partial(jax.jit,
                   static_argnames=("cfg", "page_size", "nb", "npgb"),
                   donate_argnums=(1, 2, 3))
def decode_step(params: Any, state: dict, k_pages: jax.Array,
                v_pages: jax.Array, base_key: jax.Array, *,
                cfg: ModelConfig, page_size: int, nb: int, npgb: int):
    """Fused continuous-batch decode: embed + forward + KV write + paged
    attention + per-row sampling + state advance, ONE dispatch.

    `state` is the backend's persistent device-resident batch state at
    full capacity shape (Bcap, NPGcap); the active bucket `(nb, npgb)` is
    sliced INSIDE the jit so the traced input shapes never vary — the only
    compile keys are the static bucket dims:

      bt:    (Bcap, NPGcap) int32  block tables (scratch-padded)
      lens:  (Bcap,) int32   tokens already in cache per row (0 = inactive
                             padding row; real rows always have lens >= 1)
      toks:  (Bcap,) int32   last sampled token per row (device-resident —
                             the host never uploads tokens on this path)
      temps/top_ks/seeds: (Bcap,) per-row sampling params / RNG ids

    Rows [nb:] are untouched; inactive rows inside the bucket keep lens=0,
    write only to their scratch page, and sample garbage that is ignored.
    Returns (tokens (nb,) int32, state, k_pages, v_pages).
    """
    bt = jax.lax.slice(state["bt"], (0, 0), (nb, npgb))
    lens = jax.lax.slice(state["lens"], (0,), (nb,))
    toks = jax.lax.slice(state["toks"], (0,), (nb,))
    temps = jax.lax.slice(state["temps"], (0,), (nb,))
    top_ks = jax.lax.slice(state["top_ks"], (0,), (nb,))
    seeds = jax.lax.slice(state["seeds"], (0,), (nb,))

    page_ids = bt[jnp.arange(nb), lens // page_size]
    offsets = lens % page_size
    logits, k_pages, v_pages = _token_fwd(
        params, toks, lens, lens + 1, bt, page_ids, offsets,
        k_pages, v_pages, cfg=cfg)                             # (nb, V)

    new_toks = _sample_rows(logits, base_key, seeds, lens + 1, temps, top_ks)
    active = lens > 0
    state = dict(state,
                 lens=state["lens"].at[:nb].set(
                     jnp.where(active, lens + 1, lens)),
                 toks=state["toks"].at[:nb].set(
                     jnp.where(active, new_toks, toks)))
    return new_toks, state, k_pages, v_pages


# ------------------------------------------------------- speculative decode

def _verify_fwd(params, qtoks, qpos, bt, dest_page, dest_slot, total,
                k_pages, v_pages, *, cfg: ModelConfig):
    """Multi-query target forward over the Q = k_spec+1 candidate
    positions: embed + per-layer KV write of ALL candidates + ragged
    multi-query paged attention (`kops.paged_verify`). Returns
    (logits (B, Q, V), k_pages, v_pages)."""
    h = embed_tokens(params, qtoks, cfg)                       # (B, Q, d)

    def blk(carry, xs):
        h, kp, vp = carry
        lp, li = xs
        x = rms_norm(h, lp["ln1"], cfg.norm_eps)
        q = attn._project_q(lp["attn"], x, cfg, qpos, rope=True)
        k_new, v_new = attn._project_kv(lp["attn"], x, cfg, qpos, rope=True)
        kp = kp.at[li, dest_page, dest_slot].set(k_new.astype(kp.dtype))
        vp = vp.at[li, dest_page, dest_slot].set(v_new.astype(vp.dtype))
        o = kops.paged_verify(q, kp[li], vp[li], bt, total)    # (B,Q,H,hd)
        y = jnp.einsum("bqhk,hkd->bqd", o, lp["attn"]["wo"])
        h = h + y
        h = h + _ffn(lp, rms_norm(h, lp["ln2"], cfg.norm_eps), cfg)
        return (h, kp, vp), None

    L = cfg.n_layers
    (h, k_pages, v_pages), _ = jax.lax.scan(
        blk, (h, k_pages, v_pages),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return lm_logits(params, h, cfg), k_pages, v_pages


@functools.partial(jax.jit,
                   static_argnames=("cfg", "dcfg", "page_size", "nb", "npgb",
                                    "k_spec", "synth_rate"),
                   donate_argnums=(2, 3, 4, 5, 6))
def spec_decode_step(params: Any, dparams: Any, state: dict,
                     k_pages: jax.Array, v_pages: jax.Array,
                     dk_pages: jax.Array, dv_pages: jax.Array,
                     base_key: jax.Array, scratch: jax.Array, *,
                     cfg: ModelConfig, dcfg: ModelConfig, page_size: int,
                     nb: int, npgb: int, k_spec: int,
                     synth_rate=None):
    """Fused draft-k/verify-1 speculative decode: k_spec+1 drafter
    single-token forwards propose candidates, then the target verifies all
    k_spec+1 positions in ONE multi-query dispatch — one jitted call per
    engine iteration, same bucketed batch-state contract as `decode_step`.

    The drafter shares the target's block tables / page ids / lens (its
    own pools `dk_pages`/`dv_pages` mirror the target pool's page
    geometry), so the scheduler manages ONE set of pages. Acceptance is
    exact-match: the target samples T_j at every verified position with
    the seed+position keys sequential decode would use, and draft d_j is
    accepted iff it equals T_{j-1}; the step therefore always emits
    n_acc+1 >= 1 TARGET-sampled tokens, which makes the emitted stream
    bit-identical to the non-speculative engine no matter how bad the
    drafter is. Rejected positions' KV writes are rolled back logically:
    `lens` advances only past accepted tokens, so the stale slots sit
    beyond every row's ragged edge (masked by seq_lens, overwritten by the
    next step's writes). Writes that would land past the bucket's
    `npgb * page_size` horizon are redirected to the scratch page.

    With `synth_rate` set (a float in [0,1], static), the accept/reject
    decision per draft position is replaced by a deterministic synthetic
    coin (keyed on the same seed+position PRNG, decorrelated by a tag) —
    the benchmark knob that measures speculation mechanics at a fixed
    acceptance rate; emitted tokens are then NOT baseline-exact.

    Returns (T (nb, k_spec+1) all target samples, n_acc (nb,) accepted
    draft counts, state, k_pages, v_pages, dk_pages, dv_pages).
    """
    Q = k_spec + 1
    bt = jax.lax.slice(state["bt"], (0, 0), (nb, npgb))
    lens = jax.lax.slice(state["lens"], (0,), (nb,))
    toks = jax.lax.slice(state["toks"], (0,), (nb,))
    temps = jax.lax.slice(state["temps"], (0,), (nb,))
    top_ks = jax.lax.slice(state["top_ks"], (0,), (nb,))
    seeds = jax.lax.slice(state["seeds"], (0,), (nb,))
    rows = jnp.arange(nb)
    cap = npgb * page_size

    def dests(positions):
        # a position past the bucket horizon must not clamp onto a REAL
        # page (the wrapped slot would corrupt committed KV): redirect it
        # to the scratch page, whose contents are never read back
        ok = positions < cap
        pids = bt[rows, jnp.minimum(positions // page_size, npgb - 1)]
        return jnp.where(ok, pids, scratch), positions % page_size

    # ---- draft phase: k_spec proposal forwards + 1 write-only forward
    # (the last candidate's KV must be resident for the all-accepted case:
    # next step's drafter attends position lens+k_spec)
    x = toks
    drafts = []
    for i in range(k_spec + 1):
        p = lens + i
        pids, offs = dests(p)
        d_logits, dk_pages, dv_pages = _token_fwd(
            dparams, x, p, p + 1, bt, pids, offs, dk_pages, dv_pages,
            cfg=dcfg)
        if i < k_spec:
            # drafts draw through the SAME seed+position keying as the
            # target's verify draws: an identical drafter reproduces the
            # target's samples exactly (acceptance 1.0 by construction)
            d = _sample_rows(d_logits, base_key, seeds, p + 1, temps, top_ks)
            drafts.append(d)
            x = d

    # ---- verify phase: ONE fused multi-query target dispatch
    if k_spec:
        D = jnp.stack(drafts, axis=1)                          # (nb, k)
        qtoks = jnp.concatenate([toks[:, None], D], axis=1)    # (nb, Q)
    else:
        D = jnp.zeros((nb, 0), jnp.int32)
        qtoks = toks[:, None]
    qpos = lens[:, None] + jnp.arange(Q, dtype=jnp.int32)[None, :]
    dok = qpos < cap
    dp = jnp.take_along_axis(bt, jnp.minimum(qpos // page_size, npgb - 1),
                             axis=1)
    dp = jnp.where(dok, dp, scratch)
    dsl = qpos % page_size
    # seq_lens for the verify kernel count ALL Q candidates; inactive
    # padding rows (lens=0, scratch block table) pass the minimum Q
    total = jnp.where(lens > 0, lens + Q, Q)
    logits, k_pages, v_pages = _verify_fwd(
        params, qtoks, qpos, bt, dp, dsl, total, k_pages, v_pages,
        cfg=cfg)                                               # (nb, Q, V)

    # target samples at every verified position with the sequential keys
    T = jnp.stack(
        [_sample_rows(logits[:, j], base_key, seeds, lens + 1 + j,
                      temps, top_ks) for j in range(Q)], axis=1)

    # exact-match acceptance: accept the longest draft prefix that equals
    # the target's own draws (leading matches only)
    if k_spec:
        if synth_rate is None:
            m = (D == T[:, :k_spec]).astype(jnp.int32)
        else:
            def urow(seed, ps_):
                def u1(p):
                    return jax.random.uniform(
                        jax.random.fold_in(fold_key(base_key, seed, p), 7))
                return jax.vmap(u1)(ps_)
            u = jax.vmap(urow)(seeds, qpos[:, 1:])
            m = (u < jnp.float32(synth_rate)).astype(jnp.int32)
        n_acc = jnp.sum(jnp.cumprod(m, axis=1), axis=1)        # (nb,)
    else:
        n_acc = jnp.zeros((nb,), jnp.int32)

    emitted = n_acc + 1
    new_toks = T[rows, n_acc]
    active = lens > 0
    state = dict(state,
                 lens=state["lens"].at[:nb].set(
                     jnp.where(active, lens + emitted, lens)),
                 toks=state["toks"].at[:nb].set(
                     jnp.where(active, new_toks, toks)))
    return T, n_acc, state, k_pages, v_pages, dk_pages, dv_pages


# ---------------------------------------------------------- instrumentation

def compile_counts() -> dict:
    """Live jit-cache entry counts for the hot-path programs (the
    recompile-churn metric serving_bench gates; process-global)."""
    def n(f):
        try:
            return int(f._cache_size())
        except Exception:                                    # noqa: BLE001
            return -1
    return {"decode_step": n(decode_step),
            "spec_decode_step": n(spec_decode_step),
            "prefill_pack_step": n(prefill_pack_step),
            "prefill_step": n(prefill_step),
            "sample": n(sample),
            "sample_rows": n(sample_rows)}
