"""The program's own spans in a traced run.

The program opens `jax.profiler.TraceAnnotation` spans at its layer
boundaries (`src/repro/serving/spans.py`); they sit on the host plane of
the trace, on the device's clock, inside the spans the harness opens
(`Engine.step:<i>` around each engine's step). `run.py` reduces the trace
before the readers run and keeps none of these spans, so this module reads
the trace file the run left in `out/trace/` (emptied before each traced
run), once per process. In a trace without them (a program that opens
none) every number here is missing and its reader returns None.

A program span belongs to the engine whose `Engine.step:<i>` encloses it,
and engine i steps on chip i % chips. Its self time is its length less the
part its program-span children cover; its chip-idle self time is the part
of its self time in which that chip ran no operation.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
from pathlib import Path

from benchmarks.chip import trace

# where run.run has the profiler write a traced run's window, spelled as
# run.py spells it (a local there, so it cannot be imported)
ROOT = Path(__file__).resolve().parents[2]
TRACE_DIR = ROOT / "benchmarks" / "chip" / "out" / "trace"

# as the program nests them, outer to inner
NAMES = ("router.tick", "router.route", "engine.step", "replica.admit",
         "backend.prefill", "replica.finish", "backend.decode",
         "backend.upload", "backend.sync", "engine.stream", "host.gc")


def nest(events: list, lo: int, hi: int) -> list:
    """The program spans inside [lo, hi) as (name, start, end, parent),
    parent the index of the innermost program span that encloses it, or
    -1; every parent comes before its children."""
    rank = {n: i for i, n in enumerate(NAMES)}
    spans = sorted(((s, -e, rank[n], n) for n in NAMES
                    for s, e in trace.spans(events, n)
                    if s >= lo and e <= hi))
    out, stack = [], []
    for s, neg_e, _, name in spans:
        e = -neg_e
        while stack and (out[stack[-1]][2] <= s or out[stack[-1]][2] < e):
            stack.pop()
        out.append((name, s, e, stack[-1] if stack else -1))
        stack.append(len(out) - 1)
    return out


def reduce(events: list, n_chips: int, n_engines: int):
    """{"engine_steps", "spans": {name: {"count", "total_s",
    "idle_self_s"}}} over the program spans of the traced window that lie
    inside an engine's step; None without a device or an `engine.step`."""
    lo, hi = trace.window(events, "bench.window")
    planes = trace.device_planes(events)[:n_chips]
    if not planes:
        return None
    steps = sorted((s, e, i) for i in range(n_engines)
                   for s, e in trace.spans(events, f"Engine.step:{i}"))
    starts = [s for s, _, _ in steps]
    busy = [trace.busy(events, p) for p in planes]
    busy_starts = [[s for s, _ in b] for b in busy]
    spans = nest(events, lo, hi)
    kids: list = [[] for _ in spans]
    for _, s, e, parent in spans:
        if parent >= 0:
            kids[parent].append((s, e))
    out: dict = {}
    for j, (name, s, e, _) in enumerate(spans):
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or e > steps[k][1]:
            continue                    # outside every engine's step
        chip = steps[k][2] % len(planes)
        merged, starts_c = busy[chip], busy_starts[chip]
        self_ivs, at = [], s
        for c0, c1 in trace.union(kids[j]):
            if c0 > at:
                self_ivs.append((at, c0))
            at = max(at, c1)
        if e > at:
            self_ivs.append((at, e))
        idle = 0
        for a, b in self_ivs:           # only the busy intervals near [a, b)
            i0 = max(0, bisect.bisect_right(starts_c, a) - 1)
            i1 = bisect.bisect_left(starts_c, b)
            idle += (b - a) - trace.covered(merged[i0:i1], a, b)
        agg = out.setdefault(name, {"count": 0, "total_s": 0.0,
                                    "idle_self_s": 0.0})
        agg["count"] += 1
        agg["total_s"] += (e - s) * 1e-9
        agg["idle_self_s"] += idle * 1e-9
    if "engine.step" not in out:
        return None
    return {"engine_steps": out["engine.step"]["count"], "spans": out}


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime: float, n_chips: int, n_engines: int):
    return reduce(trace.load(path), n_chips, n_engines)


def program(rec: dict):
    """What `reduce` gives for the run a reader is handed, or None."""
    paths = glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"), recursive=True)
    chips = rec["trace"]["chips"]
    if not paths or not chips:
        return None
    n_eng = sum(1 for k in rec["spans"] if k.startswith("Engine.step:"))
    return _reduced(paths[0], os.path.getmtime(paths[0]), len(chips), n_eng)
