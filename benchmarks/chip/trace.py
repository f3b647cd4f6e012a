"""From a profiler trace to numbers: device busy and idle time, device time
per jitted program, per kernel, and host spans on the same clock.

`load(path)` flattens the `.xplane.pb` that `jax.profiler` writes into
plain event dicts (plane, line, name, start and duration in ns).
Everything after that works on such lists, so it is tested on a small
recorded trace kept beside the tests.

Device planes are named `/device:TPU:<n>`. On each, the `XLA Ops` line
holds one event per operation that ran; the union of those intervals is
the device's busy time. An op's name is its HLO instruction text;
a loop's op spans its body's ops. The `XLA Modules` line holds one event
per execution of a compiled program (`jit_decode_step(...)`); an op
belongs to the program whose execution it falls in. A Pallas kernel is
an op with `custom_call_target="tpu_custom_call"`, named by its HLO
instruction name (`kernel_name`): on a TPU v5 lite the event carries no
other field that tells kernels apart. Host spans that the benchmark opens
with `jax.profiler.TraceAnnotation` sit on the host plane's threads, on
the same clock.
"""
from __future__ import annotations

import bisect
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def load(path: str) -> list:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                ev = {"plane": plane.name, "line": line.name,
                      "name": e.name, "start_ns": int(e.start_ns),
                      "dur_ns": int(e.duration_ns)}
                out.append(ev)
    return out


def device_planes(events: list) -> list:
    return sorted({e["plane"] for e in events
                   if e["plane"].startswith(DEVICE_PREFIX)})


def union(intervals: list) -> list:
    """Merge [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(merged: list, lo: int, hi: int) -> int:
    """Length of [lo, hi) covered by merged intervals."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def busy(events: list, plane: str) -> list:
    """Merged intervals in which some operation ran on `plane`."""
    return union([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                  for e in events
                  if e["plane"] == plane and e["line"] == OPS_LINE])


def program_name(module: str) -> str:
    """`jit_decode_step(123)` or `jit_decode_step.45` -> `decode_step`."""
    name = re.sub(r"^jit_", "", module)
    return re.split(r"[(.]", name, maxsplit=1)[0]


def programs(events: list, plane: str) -> dict:
    """{program: {"time_s", "calls"}} from the module executions."""
    out: dict = {}
    for e in events:
        if e["plane"] == plane and e["line"] == MODULES_LINE:
            p = out.setdefault(program_name(e["name"]),
                               {"time_s": 0.0, "calls": 0})
            p["time_s"] += e["dur_ns"] * 1e-9
            p["calls"] += 1
    return out


def _program_at(events: list, plane: str):
    """A function from a time on `plane` to the program whose execution
    covers it ("?" where none does)."""
    mods = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"],
                   program_name(e["name"])) for e in events
                  if e["plane"] == plane and e["line"] == MODULES_LINE)
    starts = [m[0] for m in mods]

    def at(t: int) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return mods[i][2] if i >= 0 and t < mods[i][1] else "?"
    return at


def kernel_name(op: str) -> str | None:
    """The name of the Pallas kernel that an op runs, None for any other
    op: the op's HLO instruction name without `%` and its `.N` suffixes.
    `pallas_call(..., name=...)` sets it; a call given no name takes the
    name of the scope around it."""
    if 'custom_call_target="tpu_custom_call"' not in op:
        return None
    head = op.split(" = ", 1)[0].split()[-1].lstrip("%")   # past any ROOT
    return re.sub(r"(\.\d+)+$", "", head)


def kernels(events: list, plane: str) -> dict:
    """{program: {kernel: [seconds, calls]}} of the Pallas kernels whose
    ops started inside that program's executions on `plane`."""
    at = _program_at(events, plane)
    out: dict = {}
    for e in events:
        if e["plane"] != plane or e["line"] != OPS_LINE:
            continue
        name = kernel_name(e["name"])
        if name is not None:
            k = out.setdefault(at(e["start_ns"]), {}).setdefault(
                name, [0.0, 0])
            k[0] += e["dur_ns"] * 1e-9
            k[1] += 1
    return out


def spans(events: list, name: str) -> list:
    """[start, end) of every host span called `name`."""
    return [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
            if e["plane"] == HOST_PLANE and e["name"] == name]


def window(events: list, name: str) -> tuple:
    """The one host span that marks the traced window."""
    (w,) = spans(events, name)
    return w


def op_label(name: str) -> str:
    """`%closed_call.8 = bf16[...] custom-call(...), custom_call_target=
    "tpu_custom_call", ...` -> `%closed_call.8 tpu_custom_call`."""
    head = name.split(" = ", 1)[0]
    m = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{head} {m.group(1)}" if m else head


def leaves(events: list, plane: str) -> list:
    """Op events on `plane` that contain no other op (a while loop's
    event spans its body's ops)."""
    ops = sorted((e for e in events
                  if e["plane"] == plane and e["line"] == OPS_LINE),
                 key=lambda e: (e["start_ns"], -e["dur_ns"]))
    out = []
    for i, e in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt["start_ns"] >= e["start_ns"] + e["dur_ns"]:
            out.append(e)
    return out


def top_ops(events: list, plane: str, lo: int, hi: int, n: int = 10) -> list:
    """The n leaf ops, by program and label, that took the most device
    time in [lo, hi)."""
    at = _program_at(events, plane)
    tot: dict = {}
    for e in leaves(events, plane):
        t = covered([[e["start_ns"], e["start_ns"] + e["dur_ns"]]], lo, hi)
        if not t:
            continue
        key = f"{at(e['start_ns'])}/{op_label(e['name'])}"
        tot[key] = tot.get(key, 0) + t
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: list, plane: str, lo: int, hi: int,
              host_names: list, n: int = 10) -> list:
    """The n longest gaps between device ops in [lo, hi), each named by
    the innermost of `host_names` spans that covers most of it."""
    merged = [iv for iv in busy(events, plane) if iv[1] > lo and iv[0] < hi]
    edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
    gaps = [(max(lo, edges[i]), min(hi, edges[i + 1]))
            for i in range(0, len(edges) - 1, 2)]
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:n]
    host = [(name, spans(events, name)) for name in host_names]
    out = []
    for g0, g1 in gaps:
        label = "none"
        for name, ivs in host:              # outermost first, keep inner
            if covered(union(ivs), g0, g1) * 2 > g1 - g0:
                label = name
        out.append([label, (g1 - g0) * 1e-9])
    return out
