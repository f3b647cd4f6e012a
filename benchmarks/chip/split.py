#!/usr/bin/env python3
"""Run one cell exactly as run.py does, and write down what its result
line cannot carry yet: the TTFT split by the request stamps, the backend's
counters over the whole window, and in traced runs the program's spans and
the longest idle gaps on the chip named by them.

    python3 benchmarks/chip/split.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1> [--out DIR]

run.py's line goes to stdout unchanged; the rest goes to
DIR/<workload>.<seed>.t<trace>.json (DIR defaults to out/split/ here):

  stamps     over the requests due in the window that got a token: median,
             mean and max in ms of each gap between consecutive stamps,
             due -> submitted (submit_late), arrival_s -> dispatched_s
             (router_queue), -> admitted_s (pending_wait), -> first_token_s
             (admit_to_token), -> the client's first token
             (first_token_hold), and of the TTFT (due -> first token).
             The means of the five gaps add up to the TTFT mean, less the
             submit -> arrival step (microseconds on one host). `n`, the
             requests counted; `backwards`, those whose chain due <=
             submitted <= arrival <= dispatched <= admitted <= first token
             <= first client token runs backwards.
  counters   JaxPagedBackend's `decode_calls` and `state_uploads`, summed
             over the engines, at the window's start and end (where
             run.py reads its own counters), and `upload_share`, the
             uploads over the decodes between the two, in %.
  program_spans   (--trace 1) what spans.program gives the readers.
  gaps       (--trace 1) the ten longest gaps between ops on chip 0 in the
             traced window: length, start, the step programs just before
             and after, the share of it each span covers, and the
             innermost span covering most of it (trace.idle_gaps's rule,
             over the harness's and the program's span names).

A program without a stamp, counter or span (one from before they were
added) leaves it out. The hooks add a clock read per finished request and
nothing inside a step; run.py's own numbers are measured as without them.
"""
from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import run, serve, spans, stats, trace  # noqa: E402

# outer to inner, as trace.idle_gaps wants them
GAP_NAMES = ["Client.poll", "InProcessRouter.step", "router.tick",
             "router.route", "Engine.step:0", "engine.step",
             "replica.admit", "backend.prefill", "replica.finish",
             "backend.decode", "backend.upload", "backend.sync",
             "engine.stream", "host.gc"]
TERMS = ("submit_late", "router_queue", "pending_wait", "admit_to_token",
         "first_token_hold")


def ttft_split(chains: list) -> dict:
    """chains: per request (due, submitted, arrival, dispatched, admitted,
    first_token_s, first client token), seconds on one clock."""
    cols: dict = {k: [] for k in TERMS + ("ttft",)}
    back = 0
    for c in chains:
        due, sub, arr, disp, adm, ft, first = c
        if list(c) != sorted(c):
            back += 1
        for k, (a, b) in zip(TERMS, ((due, sub), (arr, disp), (disp, adm),
                                     (adm, ft), (ft, first))):
            cols[k].append(1e3 * (b - a))
        cols["ttft"].append(1e3 * (first - due))
    out: dict = {"n": len(chains), "backwards": back}
    for k, v in cols.items():
        if v:
            out[k] = {"median": statistics.median(v),
                      "mean": statistics.fmean(v), "max": max(v)}
    return out


def gaps(events: list, plane: str, lo: int, hi: int, names: list,
         n: int = 10) -> list:
    """The n longest gaps between ops on `plane` in [lo, hi), longest
    first, each with what covers it."""
    merged = [iv for iv in trace.busy(events, plane)
              if iv[1] > lo and iv[0] < hi]
    edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
    found = sorted(((max(lo, edges[i]), min(hi, edges[i + 1]))
                    for i in range(0, len(edges) - 1, 2)),
                   key=lambda g: g[0] - g[1])
    found = [g for g in found if g[1] > g[0]][:n]
    mods = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"],
                   trace.program_name(e["name"])) for e in events
                  if e["plane"] == plane and e["line"] == trace.MODULES_LINE)
    host = [(name, trace.union(trace.spans(events, name))) for name in names]
    out = []
    for g0, g1 in found:
        cover, label = {}, "none"
        for name, ivs in host:              # outermost first, keep inner
            c = trace.covered(ivs, g0, g1)
            if c:
                cover[name] = c / (g1 - g0)
            if c * 2 > g1 - g0:
                label = name
        out.append({
            "ms": (g1 - g0) * 1e-6, "at_ms": (g0 - lo) * 1e-6,
            "label": label,
            "before": [m[2] for m in mods if m[1] <= g0][-1:],
            "after": [m[2] for m in mods if m[0] >= g1][:1],
            "cover": cover})
    return out


class Hooks:
    """What the run hands on, caught at the harness's own call sites."""

    def __init__(self):
        self.chains: dict = {}          # id(Record) -> stamp chain
        self.counts: list = []          # per serve.counters call
        self.extra: dict = {}

    def install(self) -> None:
        done, fin = serve.OpenLoop._done, serve.OpenLoop.finalize
        counters, layers = serve.counters, run.read_layers
        hooks = self

        def _done(loop, h, rec):
            done(loop, h, rec)
            hooks.stamp(loop, h, rec)

        def finalize(loop):
            fin(loop)
            for h, rec in loop._handles:
                hooks.stamp(loop, h, rec)
            timed = stats.in_window(loop.records, loop.seconds)
            hooks.extra["stamps"] = ttft_split(
                [hooks.chains[id(r)] for r in timed if id(r) in hooks.chains])

        def count(router, engines):
            got = [tuple(getattr(e.backend, k, None)
                         for k in ("decode_calls", "state_uploads"))
                   for e in engines]
            hooks.counts.append(None if any(None in g for g in got) else
                                [sum(g[0] for g in got),
                                 sum(g[1] for g in got)])
            return counters(router, engines)

        def read_layers(cell, rec):
            hooks.traced(rec)
            return layers(cell, rec)

        serve.OpenLoop._done, serve.OpenLoop.finalize = _done, finalize
        serve.counters, run.read_layers = count, read_layers

    def stamp(self, loop, h, rec) -> None:
        """The chain of a request that got a token, relative to the
        window's start; first call wins."""
        r = h.request
        st = [getattr(r, k, None) for k in
              ("arrival_s", "dispatched_s", "admitted_s", "first_token_s")]
        if id(rec) in self.chains or None in st or not rec.times:
            return
        self.chains[id(rec)] = ((rec.due, rec.submitted)
                                + tuple(x - loop.t0 for x in st)
                                + (rec.times[0],))

    def traced(self, rec: dict) -> None:
        p = spans.program(rec)
        if p is not None:
            self.extra["program_spans"] = p
        paths = sorted(spans.TRACE_DIR.glob("**/*.xplane.pb"))
        if not paths:
            return
        events = trace.load(str(paths[0]))
        planes = trace.device_planes(events)
        if planes:
            lo, hi = trace.window(events, "bench.window")
            self.extra["gaps"] = gaps(events, planes[0], lo, hi, GAP_NAMES)

    def counters(self) -> dict | None:
        """Start and end of the window: run.run's first two reads."""
        if len(self.counts) < 2 or None in self.counts[:2]:
            return None
        (d0, u0), (d1, u1) = self.counts[:2]
        return {"decode_calls": [d0, d1], "state_uploads": [u0, u1],
                "upload_share": (100.0 * (u1 - u0) / (d1 - d0)
                                 if d1 > d0 else None)}


def main(argv: list) -> int:
    out = ROOT / "benchmarks" / "chip" / "out" / "split"
    if "--out" in argv:
        i = argv.index("--out")
        out = Path(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    args = dict(zip(argv[::2], argv[1::2]))
    hooks = Hooks()
    hooks.install()
    rc = run.main(argv)
    c = hooks.counters()
    if c is not None:
        hooks.extra["counters"] = c
    out.mkdir(parents=True, exist_ok=True)
    name = f"{args['--workload']}.{args['--seed']}.t{args.get('--trace', '0')}"
    (out / f"{name}.json").write_text(json.dumps(hooks.extra, indent=1))
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
