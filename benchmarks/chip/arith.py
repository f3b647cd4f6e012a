"""Operations and bytes the algorithm needs, from shapes alone, and the
chip's peaks. Kept with the benchmark so that every change is measured
with the same arithmetic. What depends on an architecture's layout (which
layers run which kernel over which contexts, the operations of a token)
is in its module under arch/, which calls these.

Conventions: a (m, k) x (k, n) matrix product costs 2mkn operations; one
decoded token attending to `ctx` cached tokens costs 4 * ctx * heads *
head size (scores and the weighted sum).
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The peaks of one chip; an unknown device is an error."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def paged_decode_cost(ctxs: list, heads: int, kv_heads: int, hd: int,
                      kv_bytes: int = 2) -> tuple:
    """(operations, bytes) of one paged-attention kernel call (one layer)
    over the live rows with these contexts: K and V of every live token,
    plus q in and the output out."""
    toks = sum(ctxs)
    flops = 4.0 * toks * heads * hd
    nbytes = (2 * toks * kv_heads * hd + 2 * len(ctxs) * heads * hd) * kv_bytes
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take."""
    return max(flops / peak["bf16_flop_s"], nbytes / peak["hbm_bytes_s"])
