"""Pallas kernel sweeps: every kernel runs in interpret mode (kernel body
executed on CPU) and must match its pure-jnp oracle across shapes/dtypes."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels import paged_decode as pd
from repro.kernels.paged_decode import paged_decode
from repro.kernels.paged_verify import paged_verify
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------- flash

@pytest.mark.parametrize("B,H,K,S,hd", [
    (1, 4, 4, 128, 32),          # MHA
    (2, 8, 2, 256, 32),          # GQA 4:1
    (1, 4, 1, 128, 64),          # MQA
    (1, 2, 2, 384, 16),          # non-pow2 seq (3 blocks of 128)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, K, S, hd, dtype, causal):
    q = _rand((B, H, S, hd), dtype)
    k = _rand((B, K, S, hd), dtype)
    v = _rand((B, K, S, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=128, bk=128,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_cross_lengths():
    """S != T (prefill extending a cached prefix)."""
    q = _rand((1, 4, 128, 32), jnp.float32)
    k = _rand((1, 4, 256, 32), jnp.float32)
    v = _rand((1, 4, 256, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------- paged

@pytest.mark.parametrize("B,H,K,hd,page,Ptot,npg", [
    (2, 4, 4, 32, 8, 16, 4),
    (3, 8, 2, 64, 16, 32, 8),    # GQA
    (1, 4, 1, 32, 8, 8, 2),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_sweep(B, H, K, hd, page, Ptot, npg, dtype):
    q = _rand((B, H, hd), dtype)
    kp = _rand((Ptot, page, K, hd), dtype)
    vp = _rand((Ptot, page, K, hd), dtype)
    bt = jnp.asarray(RNG.integers(0, Ptot, size=(B, npg)), jnp.int32)
    lens = jnp.asarray(RNG.integers(1, npg * page + 1, size=(B,)), jnp.int32)
    out = paged_decode(q, kp, vp, bt, lens, interpret=True)
    want = ref.paged_decode_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_paged_decode_length_edges():
    """len=1 (only first slot valid) and len=full (every page used)."""
    B, H, K, hd, page, Ptot, npg = 2, 4, 2, 32, 8, 16, 4
    q = _rand((B, H, hd), jnp.float32)
    kp = _rand((Ptot, page, K, hd), jnp.float32)
    vp = _rand((Ptot, page, K, hd), jnp.float32)
    bt = jnp.asarray(RNG.integers(0, Ptot, size=(B, npg)), jnp.int32)
    lens = jnp.asarray([1, npg * page], jnp.int32)
    out = paged_decode(q, kp, vp, bt, lens, interpret=True)
    want = ref.paged_decode_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_paged_decode_ignores_garbage_pages():
    """Entries past the ragged edge are never dereferenced: the kernel
    copies live pages only, so they may hold ARBITRARY int32 (even
    out-of-range page ids) — results must not change, and nothing may
    crash."""
    B, H, K, hd, page, Ptot, npg = 1, 4, 2, 32, 8, 16, 4
    q = _rand((B, H, hd), jnp.float32)
    kp = _rand((Ptot, page, K, hd), jnp.float32)
    vp = _rand((Ptot, page, K, hd), jnp.float32)
    bt1 = jnp.asarray([[3, 5, 0, 0]], jnp.int32)
    bt2 = jnp.asarray([[3, 5, 999, -7]], jnp.int32)  # garbage beyond len
    lens = jnp.asarray([12], jnp.int32)              # only pages 0-1 valid
    o1 = paged_decode(q, kp, vp, bt1, lens, interpret=True)
    o2 = paged_decode(q, kp, vp, bt2, lens, interpret=True)
    want = ref.paged_decode_ref(q, kp, vp, bt2, lens)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(want), atol=2e-5)


def test_paged_decode_ragged_sweep():
    """Very ragged batch — per-sequence lengths spanning 1 token to the
    full table, with out-of-range garbage seeded past every ragged edge —
    must match the oracle exactly (the interpret-mode acceptance sweep for
    the ragged grid)."""
    B, H, K, hd, page, Ptot, npg = 6, 8, 2, 32, 8, 24, 6
    q = _rand((B, H, hd), jnp.float32)
    kp = _rand((Ptot, page, K, hd), jnp.float32)
    vp = _rand((Ptot, page, K, hd), jnp.float32)
    bt = RNG.integers(0, Ptot, size=(B, npg)).astype(np.int32)
    lens = np.asarray([1, page, page + 1, 2 * page + 3, npg * page - 1,
                       npg * page], np.int32)
    for i in range(B):                     # poison everything past the edge
        bt[i, (int(lens[i]) + page - 1) // page:] = RNG.integers(
            -(2 ** 31), 2 ** 31 - 1)
    bt, lens = jnp.asarray(bt), jnp.asarray(lens)
    out = paged_decode(q, kp, vp, bt, lens, interpret=True)
    want = ref.paged_decode_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def _poison_past_edge(bt, lens, page):
    """Out-of-range int32 garbage in every table entry past a row's live
    page count, which the kernel must never dereference."""
    for i, n in enumerate(lens):
        bt[i, (int(n) + page - 1) // page:] = RNG.integers(
            -(2 ** 31), 2 ** 31 - 1)
    return bt


@pytest.mark.parametrize("H,K,hd,page,Ptot,npg,ppb,lens", [
    # npg spans two blocks and is not a multiple of ppb
    (8, 2, 32, 8, 24, 6, 4, [3, 20, 33, 47, 48]),
    # live edge in the middle of a block, garbage in the rest of it
    (4, 2, 32, 8, 24, 6, 4, [9, 26, 41]),
    # seq_len 1 and full, over three blocks of two
    (4, 2, 32, 8, 24, 6, 2, [1, 48]),
    # MHA with 32 KV heads (deepseek's shape at a small head size)
    (32, 32, 32, 8, 16, 6, 4, [1, 17, 40, 48]),
    # 16-token pages, GQA 2:1 (qwen3's page and grouping)
    (16, 8, 32, 16, 24, 6, 4, [5, 16, 17, 70, 96]),
    # qwen3's whole page: 128 lanes, nothing padded
    (16, 8, 128, 16, 24, 6, 4, [1, 33, 64, 96]),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_multi_page_blocks(monkeypatch, H, K, hd, page, Ptot,
                                        npg, ppb, lens, dtype):
    """Blocks of several pages: copies, double buffering across blocks and
    rows, and a last block cut at the live edge all match the oracle."""
    itemsize = jnp.dtype(dtype).itemsize
    monkeypatch.setattr(pd, "BLOCK_BYTES",
                        ppb * pd.page_bytes(page, K, hd, itemsize))
    assert pd.pages_per_block(page, K, hd, itemsize, npg) == ppb
    B = len(lens)
    q = _rand((B, H, hd), dtype)
    kp = _rand((Ptot, page, K, hd), dtype)
    vp = _rand((Ptot, page, K, hd), dtype)
    bt = _poison_past_edge(RNG.integers(0, Ptot, size=(B, npg)).astype(
        np.int32), lens, page)
    bt, lens = jnp.asarray(bt), jnp.asarray(lens, jnp.int32)
    out = paged_decode(q, kp, vp, bt, lens, interpret=True)
    want = ref.paged_decode_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("H,K,ppb", [
    (16, 8, 32),       # qwen3-0.6b: 32 KiB pages
    (32, 32, 8),       # deepseek-llm-7b: 128 KiB pages
])
def test_pages_per_block_rule(H, K, ppb):
    """The rule gives more pages to narrower pages, caps at the table's
    width, and keeps the kernel's buffers inside the scoped VMEM default."""
    page, hd = 16, 128
    assert pd.pages_per_block(page, K, hd, 2, 256) == ppb
    assert pd.pages_per_block(page, K, hd, 2, 1) == 1
    assert pd.pages_per_block(page, K, hd, 2, ppb - 1) == ppb - 1
    assert pd.vmem_bytes(ppb, page, H, K, hd, 2) < 8 * 2 ** 20


# ---------------------------------------------------------------- verify

@pytest.mark.parametrize("B,H,K,hd,page,Ptot,npg,Q", [
    (2, 4, 4, 32, 8, 16, 4, 2),
    (3, 8, 2, 64, 16, 32, 8, 4),   # GQA, k_spec=3
    (1, 4, 1, 32, 8, 8, 2, 3),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_verify_sweep(B, H, K, hd, page, Ptot, npg, Q, dtype):
    q = _rand((B, Q, H, hd), dtype)
    kp = _rand((Ptot, page, K, hd), dtype)
    vp = _rand((Ptot, page, K, hd), dtype)
    bt = jnp.asarray(RNG.integers(0, Ptot, size=(B, npg)), jnp.int32)
    # lens count ALL valid tokens INCLUDING the Q candidates (>= Q)
    lens = jnp.asarray(RNG.integers(Q, npg * page + 1, size=(B,)), jnp.int32)
    out = paged_verify(q, kp, vp, bt, lens, interpret=True)
    want = ref.paged_verify_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_paged_verify_q1_matches_paged_decode():
    """Q=1 degenerates to plain paged decode (same mask, same numbers)."""
    B, H, K, hd, page, Ptot, npg = 2, 4, 2, 32, 8, 16, 4
    q = _rand((B, H, hd), jnp.float32)
    kp = _rand((Ptot, page, K, hd), jnp.float32)
    vp = _rand((Ptot, page, K, hd), jnp.float32)
    bt = jnp.asarray(RNG.integers(0, Ptot, size=(B, npg)), jnp.int32)
    lens = jnp.asarray([5, 27], jnp.int32)
    out = paged_verify(q[:, None], kp, vp, bt, lens, interpret=True)
    want = paged_decode(q, kp, vp, bt, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(want),
                               atol=1e-6)


def test_paged_verify_causal_within_candidates():
    """Candidate j must not see candidates j+1..Q-1: truncating the batch
    to the first j+1 candidates cannot change query j's output."""
    B, H, K, hd, page, Ptot, npg, Q = 1, 4, 2, 32, 8, 16, 4, 4
    q = _rand((B, Q, H, hd), jnp.float32)
    kp = _rand((Ptot, page, K, hd), jnp.float32)
    vp = _rand((Ptot, page, K, hd), jnp.float32)
    bt = jnp.asarray(RNG.integers(0, Ptot, size=(B, npg)), jnp.int32)
    lens = jnp.asarray([20], jnp.int32)
    full = np.asarray(paged_verify(q, kp, vp, bt, lens, interpret=True))
    for j in range(Q):
        part = np.asarray(paged_verify(
            q[:, :j + 1], kp, vp, bt, lens - (Q - j - 1), interpret=True))
        np.testing.assert_allclose(part[:, j], full[:, j], atol=2e-5)


def test_paged_verify_ignores_garbage_pages():
    """Block-table entries past the ragged edge may hold arbitrary int32
    (the rollback contract: rejected-draft KV sits beyond the edge)."""
    B, H, K, hd, page, Ptot, npg, Q = 2, 4, 2, 32, 8, 16, 4, 3
    q = _rand((B, Q, H, hd), jnp.float32)
    kp = _rand((Ptot, page, K, hd), jnp.float32)
    vp = _rand((Ptot, page, K, hd), jnp.float32)
    bt = np.asarray(RNG.integers(0, Ptot, size=(B, npg)), np.int32)
    lens = np.asarray([12, Q], np.int32)
    clean = jnp.asarray(bt.copy())
    for i in range(B):
        bt[i, (int(lens[i]) + page - 1) // page:] = RNG.integers(
            -(2 ** 31), 2 ** 31 - 1)
    bt, lens = jnp.asarray(bt), jnp.asarray(lens)
    o1 = paged_verify(q, kp, vp, clean, lens, interpret=True)
    o2 = paged_verify(q, kp, vp, bt, lens, interpret=True)
    want = ref.paged_verify_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------- ssd

@pytest.mark.parametrize("B,H,S,P,G,N,chunk", [
    (1, 2, 64, 16, 1, 16, 16),
    (2, 4, 128, 16, 2, 24, 32),
    (1, 8, 96, 8, 4, 16, 48),      # 2 chunks of 48
])
def test_ssd_scan_sweep(B, H, S, P, G, N, chunk):
    x = _rand((B, H, S, P), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, H, S)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 4.0, size=(H,)), jnp.float32)
    B_ = _rand((B, G, S, N), jnp.float32)
    C_ = _rand((B, G, S, N), jnp.float32)
    out = ssd_scan(x, dt, a, B_, C_, chunk=chunk, interpret=True)
    want = ref.ssd_scan_ref(x, dt, a, B_, C_, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-5, rtol=5e-4)


def test_ssd_scan_chunk_invariance():
    """The chunked algorithm must give the same answer for any chunk size."""
    B, H, S, P, G, N = 1, 2, 96, 8, 1, 16
    x = _rand((B, H, S, P), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, H, S)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    B_ = _rand((B, G, S, N), jnp.float32)
    C_ = _rand((B, G, S, N), jnp.float32)
    outs = [np.asarray(ssd_scan(x, dt, a, B_, C_, chunk=c, interpret=True))
            for c in (16, 32, 48, 96)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=5e-5, rtol=5e-4)


# ---------------------------------------------------------------- dispatch

def test_ops_dispatch_cpu_uses_ref(monkeypatch):
    from repro.kernels import ops
    monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    q = _rand((1, 2, 16, 8), jnp.float32)
    k = _rand((1, 2, 16, 8), jnp.float32)
    out = ops.flash_attention(q, k, k)
    want = ref.flash_attention_ref(q, k, k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


def test_ops_does_not_hide_a_backend_failure(monkeypatch):
    """A backend that fails to start must surface, not turn into a quiet
    oracle run that looks like the device path."""
    from repro.kernels import ops

    def broken():
        raise RuntimeError("backend failed to initialize")
    monkeypatch.setattr(jax, "default_backend", broken)
    q = _rand((1, 2, 16, 8), jnp.float32)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        ops.flash_attention(q, q, q)


def test_ops_force_interpret(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    q = _rand((1, 2, 128, 32), jnp.float32)
    k = _rand((1, 2, 128, 32), jnp.float32)
    out = ops.flash_attention(q, k, k)
    want = ref.flash_attention_ref(q, k, k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_ops_paged_verify_dispatch(monkeypatch):
    """ops.paged_verify: CPU default hits the jnp oracle; with
    REPRO_FORCE_INTERPRET=1 it runs the Pallas body in interpret mode —
    both must agree with the reference."""
    from repro.kernels import ops
    B, H, K, hd, page, Ptot, npg, Q = 2, 4, 2, 32, 8, 16, 4, 3
    q = _rand((B, Q, H, hd), jnp.float32)
    kp = _rand((Ptot, page, K, hd), jnp.float32)
    vp = _rand((Ptot, page, K, hd), jnp.float32)
    bt = jnp.asarray(RNG.integers(0, Ptot, size=(B, npg)), jnp.int32)
    lens = jnp.asarray([17, Q], jnp.int32)
    want = ref.paged_verify_ref(q, kp, vp, bt, lens)
    monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    out = ops.paged_verify(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    out = ops.paged_verify(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
