"""Whole decode step: model operations of the real rows of every
decode_step execution (as the configuration's architecture module counts
them) over that program's device time times the chip's peak, in the
traced part of the window."""
from benchmarks.chip import arch


def read(rec):
    cfg, peak = rec["config"], rec["peak"]
    runs = [c["programs"].get("decode_step") for c in rec["trace"]["chips"]]
    runs = [p for p in runs if p and p["calls"]]
    calls = rec["decode"]
    if not runs or not calls or sum(p["calls"] for p in runs) != len(calls):
        return None
    row_flops = arch.load(cfg).decode_row_flops
    flops = sum(row_flops(cfg, ctx) for c in calls for ctx in c[2])
    dev_s = sum(p["time_s"] for p in runs)
    return 100.0 * flops / (dev_s * peak["bf16_flop_s"])
