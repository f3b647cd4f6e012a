"""Kernels: the paged-attention kernel's share of its roofline. The least
time of every call (one per layer per decode step, over the live rows'
K and V, q and the output) over the kernel's device time, in the traced
part of the window. Left out when the trace's kernel calls do not match
the decode steps recorded on the host."""
from benchmarks.chip import arith


def read(rec):
    cfg, peak = rec["config"], rec["peak"]
    L = cfg["num_hidden_layers"]
    kern_s = sum(c["paged_decode"][0] for c in rec["trace"]["chips"])
    kern_n = sum(c["paged_decode"][1] for c in rec["trace"]["chips"])
    calls = rec["decode"]
    if not calls or kern_s <= 0 or kern_n != L * len(calls):
        return None
    least = sum(L * arith.roofline_s(*arith.paged_decode_cost(cfg, c[2]),
                                     peak) for c in calls)
    return 100.0 * least / kern_s
