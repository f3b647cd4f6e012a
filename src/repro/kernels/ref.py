"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth
swept in tests/test_kernels_*.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True) -> jax.Array:
    """q: (B,H,S,hd); k/v: (B,K,T,hd); GQA by head grouping. fp32 softmax."""
    B, H, S, hd = q.shape
    K = k.shape[1]
    G = H // K
    qg = q.reshape(B, K, G, S, hd)
    logits = jnp.einsum("bkgsh,bkth->bkgst", qg, k).astype(jnp.float32)
    logits *= hd ** -0.5
    if causal:
        T = k.shape[2]
        mask = jnp.arange(S)[:, None] >= jnp.arange(T)[None, :]
        logits = jnp.where(mask[None, None, None], logits, -2.0e38)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,bkth->bkgsh", probs, v)
    return out.reshape(B, H, S, hd)


def paged_decode_ref(q, k_pages, v_pages, block_table, seq_lens) -> jax.Array:
    """Decode attention over a paged KV cache.
    q: (B,H,hd); k_pages/v_pages: (P,page,K,hd); block_table: (B,npages)
    int32 — entries at or beyond a sequence's live page count
    ceil(seq_len / page) are NEVER dereferenced and may hold arbitrary
    garbage (the ragged Pallas kernel copies live pages only);
    seq_lens: (B,) valid token counts, >= 1. fp32 softmax."""
    B, H, hd = q.shape
    Ptot, page, K, _ = k_pages.shape
    npages = block_table.shape[1]
    G = H // K

    def one(qb, bt, ln):
        # entries past the ragged edge may be garbage: squash them onto
        # page 0 before the gather (their columns are masked anyway)
        live = jnp.arange(npages, dtype=jnp.int32) * page < ln
        bt = jnp.where(live, bt, 0)
        k = k_pages[bt]                                   # (npages,page,K,hd)
        v = v_pages[bt]
        T = npages * page
        k = k.reshape(T, K, hd)
        v = v.reshape(T, K, hd)
        qg = qb.reshape(K, G, hd)
        logits = jnp.einsum("kgh,tkh->kgt", qg, k).astype(jnp.float32)
        logits *= hd ** -0.5
        valid = jnp.arange(T) < ln
        logits = jnp.where(valid[None, None], logits, -2.0e38)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("kgt,tkh->kgh", probs, v)
        return out.reshape(H, hd)

    return jax.vmap(one)(q, block_table, seq_lens)


def paged_verify_ref(q, k_pages, v_pages, block_table, seq_lens) -> jax.Array:
    """Multi-query verify attention over a paged KV cache (speculative
    decoding's target-model half).
    q: (B,Q,H,hd) — Q candidate positions per sequence, whose K/V the
    caller has already written into the pool; seq_lens: (B,) TOTAL valid
    token counts INCLUDING the Q candidates (>= Q). With
    base = seq_len - Q committed tokens, query qi attends positions
    < base + qi + 1 (its own position and everything before it, none of
    the later candidates). Same garbage-past-ragged-edge block-table
    contract as `paged_decode_ref`; reduces to its math at Q=1."""
    B, Q, H, hd = q.shape
    Ptot, page, K, _ = k_pages.shape
    npages = block_table.shape[1]
    G = H // K

    def one(qb, bt, ln):
        live = jnp.arange(npages, dtype=jnp.int32) * page < ln
        bt = jnp.where(live, bt, 0)
        k = k_pages[bt]                                   # (npages,page,K,hd)
        v = v_pages[bt]
        T = npages * page
        k = k.reshape(T, K, hd)
        v = v.reshape(T, K, hd)
        # fold the query axis into the grouped-query axis: row g*Q + qi
        qg = qb.transpose(1, 0, 2).reshape(K, G * Q, hd)
        logits = jnp.einsum("kgh,tkh->kgt", qg, k).astype(jnp.float32)
        logits *= hd ** -0.5
        qi = jnp.arange(G * Q, dtype=jnp.int32) % Q
        limit = ln - Q + qi + 1                           # (G*Q,)
        valid = jnp.arange(T, dtype=jnp.int32)[None, :] < limit[:, None]
        logits = jnp.where(valid[None], logits, -2.0e38)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("kgt,tkh->kgh", probs, v)
        return out.reshape(K, G, Q, hd).transpose(2, 0, 1, 3).reshape(Q, H, hd)

    return jax.vmap(one)(q, block_table, seq_lens)


def ssd_scan_ref(x, dt, a, B_, C_, *, chunk: int) -> jax.Array:
    """Chunked SSD oracle (zero initial state).
    x: (B,H,S,P) f32; dt: (B,H,S) f32 post-softplus; a: (H,) f32 (<0);
    B_/C_: (B,G,S,N) f32 with groups broadcast over H//G heads.
    Returns y: (B,H,S,P) f32."""
    Bb, H, S, P = x.shape
    G, N = B_.shape[1], B_.shape[3]
    hpg = H // G
    Bh = jnp.repeat(B_, hpg, axis=1)                      # (B,H,S,N)
    Ch = jnp.repeat(C_, hpg, axis=1)

    def step(h, inp):
        xt, dtt, bt, ct = inp                             # (B,H,P),(B,H),(B,H,N)
        decay = jnp.exp(dtt * a[None, :])
        h = h * decay[..., None, None] + jnp.einsum("bh,bhp,bhn->bhpn",
                                                    dtt, xt, bt)
        y = jnp.einsum("bhpn,bhn->bhp", h, ct)
        return h, y

    h0 = jnp.zeros((Bb, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(
        step, h0, (jnp.moveaxis(x, 2, 0), jnp.moveaxis(dt, 2, 0),
                   jnp.moveaxis(Bh, 2, 0), jnp.moveaxis(Ch, 2, 0)))
    return jnp.moveaxis(ys, 0, 2)                         # (B,H,S,P)


def page_gather_ref(pool, ids) -> jax.Array:
    """pool: (L, P, page, K, hd); ids: (N,) int32 unique page slots.
    Returns the dense page stack (N, L, page, K, hd)."""
    return jnp.swapaxes(pool[:, ids], 0, 1)


def page_scatter_ref(pool, staged, ids) -> jax.Array:
    """Inverse of `page_gather_ref`: write `staged` (N, L, page, K, hd)
    into the pool at page slots `ids` (unique). Returns the updated pool."""
    return pool.at[:, ids].set(jnp.swapaxes(staged, 0, 1))
