"""Operations and bytes the algorithm needs, from shapes alone, and the
chip's peaks. Kept with the benchmark so that every change is measured
with the same arithmetic.

Conventions: a (m, k) x (k, n) matrix product costs 2mkn operations; one
decoded token attending to `ctx` cached tokens costs 4 * ctx * heads *
head_dim (scores and the weighted sum).
"""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.chip.cell import head_dim

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The peaks of one chip; an unknown device is an error."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _dims(config: dict) -> tuple:
    d, f = config["hidden_size"], config["intermediate_size"]
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    return d, f, H, K, head_dim(config), config["num_hidden_layers"], \
        config["vocab_size"]


def dense_token_flops(config: dict) -> float:
    """Matrix-product operations of one token through every layer and
    the output head (the dense-layer arithmetic of repro.analysis.flops:
    q, k, v and output projections, gated MLP)."""
    d, f, H, K, hd, L, V = _dims(config)
    proj = 2.0 * d * (2 * H * hd + 2 * K * hd)
    mlp = 3 * 2.0 * d * f
    return L * (proj + mlp) + 2.0 * d * V


def decode_row_flops(config: dict, ctx: int) -> float:
    """One decoded token with `ctx` tokens in its cache (itself included)."""
    d, f, H, K, hd, L, V = _dims(config)
    return dense_token_flops(config) + L * 4.0 * ctx * H * hd


def paged_decode_cost(config: dict, ctxs: list, kv_bytes: int = 2) -> tuple:
    """(operations, bytes) of one paged-attention kernel call (one layer)
    over the live rows: K and V of every live token, plus q in and the
    output out."""
    d, f, H, K, hd, L, V = _dims(config)
    toks = sum(ctxs)
    flops = 4.0 * toks * H * hd
    nbytes = (2 * toks * K * hd + 2 * len(ctxs) * H * hd) * kv_bytes
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take."""
    return max(flops / peak["bf16_flop_s"], nbytes / peak["hbm_bytes_s"])
