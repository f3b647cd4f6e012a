"""Kernel tile-shape sweep (no corresponding paper table — the paper's
contribution is LB-level; these are the TPU-target hot-spot kernels the
engine calls, DESIGN §3).

For each kernel x tile configuration we report the STRUCTURAL metrics the
dry-run perf loop reasons from: per-step VMEM working set, MXU lane
alignment, grid size. Kernel times come from the chip's trace
(benchmarks/chip/), never from here.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def _vmem_flash(bq, bk, hd):
    # q + k + v tiles + scratch (m, l, acc) fp32
    return (bq * hd + 2 * bk * hd) * 2 + (bq * 1 * 2 + bq * hd) * 4


def _vmem_verify(page, H, Q, K, hd):
    # q tile folded to (K, G*Q, hd) + k/v page tiles + scratch (m, l, acc)
    return (H * Q * hd + 2 * page * K * hd) * 2 + (2 * H * Q + H * Q * hd) * 4


def _vmem_ssd(Q, P, N):
    return (Q * P + Q + 2 * Q * N) * 4 + (P * N) * 4 + (Q * Q) * 4


def run() -> list[dict]:
    rows = []
    rng = np.random.default_rng(0)

    # flash attention tiles
    S = 512
    for bq, bk in ((128, 128), (256, 128), (128, 256), (512, 128)):
        rows.append({
            "kernel": "flash_attention", "tile": f"bq{bq}xbk{bk}",
            "vmem_kb": round(_vmem_flash(bq, bk, 128) / 1024, 1),
            "lane_aligned": bk % 128 == 0 and 128 % 128 == 0,
            "grid": f"(B,H,{S//min(bq,S)},{S//min(bk,S)})"})

    # paged decode: the pages per block and VMEM the kernel's own rule
    # picks at qwen3-0.6b's and deepseek-llm-7b's widths, 16-token bf16
    # pages, for a short and the longest decode bucket
    from repro.kernels import paged_decode as pd
    hd, page = 128, 16
    for Hq, Kv in ((16, 8), (32, 32)):
        for npg in (16, 256):
            ppb = pd.pages_per_block(page, Kv, hd, 2, npg)
            rows.append({
                "kernel": "paged_decode",
                "tile": f"K{Kv}.npg{npg}.ppb{ppb}",
                "vmem_kb": round(
                    pd.vmem_bytes(ppb, page, Hq, Kv, hd, 2) / 1024, 1),
                "lane_aligned": hd % 128 == 0,
                "grid": f"(B,{-(-npg // ppb)})"})
    B, H, K, hd, page, Ptot, npg = 8, 16, 8, 128, 16, 64, 16

    # paged verify (speculative decoding: Q = k_spec + 1 queries per seq)
    from repro.kernels.paged_verify import paged_verify
    from repro.kernels.ref import paged_verify_ref
    kp = jnp.asarray(rng.normal(size=(Ptot, page, K, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(Ptot, page, K, hd)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, Ptot, size=(B, npg)), jnp.int32)
    for Q in (2, 4):
        qv = jnp.asarray(rng.normal(size=(B, Q, H, hd)), jnp.float32)
        # ragged lens INCLUDING the Q candidate positions
        lnv = jnp.asarray(rng.integers(Q, npg * page + 1, size=(B,)),
                          jnp.int32)
        oracle = paged_verify_ref(qv, kp, vp, bt, lnv)
        got = paged_verify(qv, kp, vp, bt, lnv, interpret=True)
        err = float(jnp.max(jnp.abs(got - oracle)))
        rows.append({
            "kernel": "paged_verify", "tile": f"q{Q}xpage{page}",
            "vmem_kb": round(_vmem_verify(page, H, Q, K, hd) / 1024, 1),
            "lane_aligned": hd % 128 == 0,
            "grid": f"(B,{npg})",
            # CI-gated: interpret-mode Pallas vs jnp oracle agreement
            "verify_ok": 1.0 if err < 2e-5 else 0.0})

    # ssd chunks
    S2, P, N = 512, 64, 128
    for Q in (64, 128, 256):
        rows.append({
            "kernel": "ssd_scan", "tile": f"chunk{Q}",
            "vmem_kb": round(_vmem_ssd(Q, P, N) / 1024, 1),
            "lane_aligned": N % 128 == 0,
            "grid": f"(B,H,{S2//Q})"})
    return rows


def main(smoke: bool = False) -> list[dict]:   # fast either way
    rows = run()
    print(f"[kern] {'kernel':16s} {'tile':>12s} {'vmem_kb':>8s} "
          f"{'aligned':>8s} {'grid':>14s}")
    for r in rows:
        print(f"[kern] {r['kernel']:16s} {r['tile']:>12s} {r['vmem_kb']:8.1f} "
              f"{str(r['lane_aligned']):>8s} {r['grid']:>14s}")
    return rows


if __name__ == "__main__":
    main()
