"""Kernels: the paged-attention kernel's share of its roofline. The least
time of every call that the configuration's architecture module counts
(one per paged-attention layer per decode step, over the live rows' K and
V, q and the output) over the device time of that kernel's calls inside
decode_step, in the traced part of the window. Left out when the trace's
calls of the kernel do not match the decode steps recorded on the host."""
from benchmarks.chip import arch, arith

# The kernel's own name once its pallas_call is given one; until then its
# op takes the name of the call around it in decode_step's layer loop.
KERNELS = ("paged_decode", "closed_call")


def read(rec):
    cfg, peak = rec["config"], rec["peak"]
    tables = [c["kernels"].get("decode_step", {})
              for c in rec["trace"]["chips"]]
    name = next((k for k in KERNELS if any(k in t for t in tables)), None)
    got = [t[name] for t in tables if name in t]
    kern_s, kern_n = sum(g[0] for g in got), sum(g[1] for g in got)
    costs = arch.load(cfg).paged_decode_costs
    steps = [costs(cfg, c[2]) for c in rec["decode"]]
    if not steps or kern_s <= 0 or kern_n != sum(map(len, steps)):
        return None
    least = sum(arith.roofline_s(f, b, peak) for s in steps for f, b in s)
    return 100.0 * least / kern_s
