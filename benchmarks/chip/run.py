#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (BENCHMARK.json `workloads`) names a configuration and a traffic
mix; the files under this directory give the rest (see cell.py). One run:

  1. checks for the chips the cell asks for; exits 1 without a result if
     JAX sees no TPU or too few;
  2. turns on the persistent compilation cache (JAX_COMPILATION_CACHE_DIR
     where set, else a fixed directory in the checkout);
  3. makes the weights on the device from the seed, in one jitted call;
  4. builds the deployment: an InProcessRouter with its regions and
     engines, one engine per chip;
  5. compiles every step shape the traffic can reach, prefills the
     running sessions' histories, and runs the open loop for the mix's
     ramp; all of this is set-up;
  6. measures for --seconds: the open loop keeps running, each turn due
     at the previous turn's last token plus its think time;
  7. drains the requests due in the window, up to the mix's cap;
  8. reads the peak device memory, frees the deployment, checks a sample
     of the served requests against the float32 reference, and prints one
     JSON line.

With --trace 1 the line carries the per-layer metrics instead of the
end-to-end ones: the host spans (Client.poll, InProcessRouter.step,
Engine.step) are opened around the program's calls, the last seconds of
the window are traced by the profiler, and each metric's reader under
metrics/ computes its number from that record.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse                                               # noqa: E402
import gc                                                     # noqa: E402
import glob                                                   # noqa: E402
import importlib                                              # noqa: E402
import json                                                   # noqa: E402
import shutil                                                 # noqa: E402
import sys                                                    # noqa: E402
from pathlib import Path                                      # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import numpy as np                                            # noqa: E402

from benchmarks.chip import cell as cellmod                   # noqa: E402
from benchmarks.chip import stats                             # noqa: E402


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_cache() -> None:
    """The persistent compilation cache, where the program's entry points
    put it (JAX_COMPILATION_CACHE_DIR, else a fixed directory in the
    checkout), keeping every program however fast it compiled."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    say(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def check_device(chips: int):
    """The devices to run on, or None (with a message) when JAX sees no
    TPU or fewer chips than the cell asks for."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        say(f"FAIL: no accelerator: {e}")
        return None
    if devices[0].platform != "tpu":
        say(f"FAIL: no TPU: JAX runs on {devices[0].platform}")
        return None
    if len(devices) < chips:
        say(f"FAIL: the cell needs {chips} chips, JAX sees {len(devices)}")
        return None
    return devices[:chips]


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling."""

    def __init__(self):
        self.total = 0.0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += duration


# ------------------------------------------------------------ instruments

class Instruments:
    """Host spans and per-call records, installed on the instances only
    in traced runs. Times are monotonic seconds."""

    def __init__(self, router, engines, client):
        import jax
        self.spans: dict = {"Client.poll": [], "InProcessRouter.step": []}
        self.decode: list = []       # (t0, t1, context length of each row)
        self.active = False
        TA = jax.profiler.TraceAnnotation

        def spanned(name, fn):
            def wrapped(*a, **k):
                t0 = time.monotonic()
                with TA(name):
                    out = fn(*a, **k)
                if self.active:
                    self.spans[name].append((t0, time.monotonic()))
                return out
            return wrapped

        client.poll = spanned("Client.poll", client.poll)
        router.step = spanned("InProcessRouter.step", router.step)
        for i, e in enumerate(engines):
            self.spans[f"Engine.step:{i}"] = []
            e.step = spanned(f"Engine.step:{i}", e.step)
            be = e.backend

            def decode(seqs, _f=be.decode):
                t0 = time.monotonic()
                out = _f(seqs)
                if self.active:
                    self.decode.append((t0, time.monotonic(),
                                        [len(s.tokens) for s in seqs]))
                return out

            be.decode = decode


def reduce_trace(tdir: Path, n_chips: int, n_engines: int) -> dict:
    from benchmarks.chip import trace
    path = glob.glob(str(tdir / "**" / "*.xplane.pb"), recursive=True)[0]
    events = trace.load(path)
    lo, hi = trace.window(events, "bench.window")
    planes = trace.device_planes(events)[:n_chips]
    if not planes:                      # no device in the trace
        return {"window_s": (hi - lo) * 1e-9, "chips": [],
                "engine_steps": 0, "engine_host_s": 0.0,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    per_chip = []
    for plane in planes:
        merged = trace.busy(events, plane)
        per_chip.append({
            "plane": plane,
            "busy_s": trace.covered(merged, lo, hi) * 1e-9,
            "programs": trace.programs(events, plane),
            "kernels": trace.kernels(events, plane),
        })
    # engine i steps on chip i % n_chips: its host time is the part of its
    # Engine.step spans in which its own chip ran nothing
    host_s, n_steps = 0.0, 0
    for i in range(n_engines):
        merged = trace.busy(events, planes[i % len(planes)])
        steps = [(s, e) for s, e in trace.spans(events, f"Engine.step:{i}")
                 if s >= lo and e <= hi]
        host_s += sum((e - s) - trace.covered(merged, s, e)
                      for s, e in steps) * 1e-9
        n_steps += len(steps)
    return {
        "window_s": (hi - lo) * 1e-9,
        "chips": per_chip,
        "engine_steps": n_steps,
        "engine_host_s": host_s,
        "breakdown": {
            "device_ops": trace.top_ops(events, planes[0], lo, hi),
            "idle_gaps": trace.idle_gaps(
                events, planes[0], lo, hi,
                ["Client.poll", "InProcessRouter.step", "Engine.step:0"]),
        },
    }


# -------------------------------------------------------------- correctness

def sample_requests(records: list, seed: int, want_tokens: int) -> list:
    """The longest finished request, then others drawn from the seed, until
    `want_tokens` served tokens are in the sample."""
    done = [r for r in records if r.finished]
    if not done or want_tokens <= 0:
        return []
    done.sort(key=lambda r: (r.prompt_len + r.max_new, r.sid, r.turn))
    out = [done.pop()]
    rng = np.random.default_rng(np.random.SeedSequence([seed % 2**64, 7]))
    for i in rng.permutation(len(done)):
        if sum(r.max_new for r in out) >= want_tokens:
            break
        out.append(done[i])
    return out


def check(params, config: dict, sample: list, prompts: dict,
          control: bool = False) -> dict:
    ref = importlib.import_module(f"benchmarks.chip.{config['reference']}")
    gaps = {"program": []}
    if control:
        gaps["control"] = []
    for r in sample:
        g = ref.served_gaps(params, config, prompts[(r.sid, r.turn)],
                            r.tokens, control=control)
        for k in gaps:
            gaps[k].append(float(np.max(g[k])))
    return {k: max(v) if v else float("nan") for k, v in gaps.items()}


# --------------------------------------------------------------------- run

def run(cell, seed: int, seconds: float, traced: bool, devices,
        control: bool = False, drain_cap_s: float | None = None) -> dict:
    import jax
    from benchmarks.chip import arith, serve, traffic, weights

    tr = cell.traffic
    cfg = cellmod.model_config(cell.config)
    ecfg = cellmod.engine_config(cell.config)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    params = weights.make_params(cell.config, seed, device=devices[0])
    n_eng = serve.n_engines(cell.deployment)
    per_engine = [params if i % len(devices) == 0
                  else jax.device_put(params, devices[i % len(devices)])
                  for i in range(n_eng)]
    router, engines = serve.build(cfg, ecfg, cell.deployment, per_engine,
                                  seed)
    shapes = serve.warm_shapes(ecfg, tr)
    t0 = time.monotonic()
    for e in engines:
        serve.warm_backend(e.backend, shapes, ecfg.page_size)
    serve.block_all(engines)
    say(f"warm: {len(shapes['prefill'])} prefill and "
        f"{len(shapes['decode'])} decode shapes per engine in "
        f"{time.monotonic() - t0:.1f} s (compile {clock.total:.1f} s)")

    schedule = traffic.build_schedule(
        dict(tr, arrival_share=cell.deployment["arrival_share"]),
        seed=seed, seconds=seconds, vocab=cfg.vocab,
        max_seq_len=ecfg.max_seq_len)
    t0 = time.monotonic()
    n_warm = serve.serve_warm(router, schedule, tr["drain_cap_s"])
    say(f"warm: {n_warm} history prefills in {time.monotonic() - t0:.1f} s")

    loop = serve.OpenLoop(schedule, router, seconds)
    inst = Instruments(router, engines, loop.client) if traced else None
    poll = loop.client.poll
    loop.start(time.monotonic() + schedule.ramp_s)
    loop.run_until(0.0, poll)
    c0 = serve.counters(router, engines)
    setup_s = loop.t0 - T_START

    tdir = ROOT / "benchmarks" / "chip" / "out" / "trace"
    trace_s = min(seconds, float(tr.get("trace_s", 6.0)))
    if traced:
        inst.active = True
        loop.run_until(seconds - trace_s, poll)
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
        t_tr = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.window"):
            loop.run_until(seconds, poll)
        inst.active = False
    else:
        loop.run_until(seconds, poll)
    c1 = serve.counters(router, engines)
    if traced:
        jax.profiler.stop_trace()
    drained = loop.drain(float(tr["drain_cap_s"] if drain_cap_s is None
                               else drain_cap_s), poll)
    horizon = loop.now()
    loop.finalize()

    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    timed = stats.in_window(loop.records, seconds)
    lost = sum(1 for r in timed if r.state not in ("queued", "finished")
               or (r.state == "finished" and not r.finished))
    if not drained and router.idle():
        lost += sum(1 for r in timed if r.state == "queued")
    failed = sum(1 for r in timed if not r.finished)
    result = {
        "attempted": len(timed), "failed": failed, "lost": lost,
        "setup_s": setup_s,
        "e2e": stats.end_to_end(loop.records, seconds, horizon),
        "pace": stats.pace(loop.records, seconds, horizon),
        "counters": {"start": c0, "end": c1},
        "compiles_in_window": c1["compiles"] - c0["compiles"],
        "memory_peak_bytes": mem,
    }
    late = stats.pct([1e3 * (r.submitted - r.due) for r in timed], 50)
    say(f"window: {len(timed)} requests due, {failed} unfinished, "
        f"{result['compiles_in_window']} compiles, late p50 {late:.2f} ms")

    if traced:
        reduced = reduce_trace(tdir, len(devices), len(engines))
        rec = {"cell": cell, "config": cell.config, "ecfg": ecfg,
               "seconds": seconds, "records": loop.records,
               "counters": (c0, c1), "spans": inst.spans,
               "decode": [d for d in inst.decode if d[0] >= t_tr],
               "decode_all": inst.decode,
               "trace": reduced,
               # a device in the trace must have its peaks in the table
               "peak": (arith.peaks(devices[0].device_kind)
                        if reduced["chips"] else None)}
        result["layer"] = read_layers(cell, rec)
        result["trace"] = rec["trace"]

    sample = sample_requests(loop.records, seed, int(tr["check_tokens"]))
    prompts = {(r.sid, r.turn): loop.sessions[r.sid].prompt(
        r.turn, schedule.templates, loop.outputs[r.sid]) for r in sample}
    del router, engines, per_engine, loop, inst, poll
    gc.collect()        # the deployment holds reference cycles
    t0 = time.monotonic()
    gaps = check(params, cell.config, sample, prompts, control=control)
    result["reference_s"] = time.monotonic() - t0
    result["sample"] = [(r.sid, r.turn, r.prompt_len, r.max_new)
                        for r in sample]
    result["gaps"] = gaps
    return result


def read_layers(cell, rec: dict) -> dict:
    """Each per-layer metric of the cell, from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        mod = importlib.import_module(f"benchmarks.chip.metrics.{m['name']}")
        v = mod.read(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def verdict(res: dict, limits: dict) -> tuple:
    """(correct, checks): the widest logit gap of the sampled served
    tokens within the cell's limit, and no request due in the window lost
    (ended other than finished with all its tokens, or left unfinished by
    an idle router). A request still running at the drain cap is late,
    not wrong."""
    gap = res["gaps"]["program"]
    checks = {"logit_gap_max": {"value": gap,
                                "limit": float(limits["logit_gap_max"])},
              "lost_requests": {"value": res["lost"], "limit": 0}}
    correct = (bool(np.isfinite(gap)) and gap <= checks["logit_gap_max"]["limit"]
               and res["lost"] == 0)
    return correct, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cellmod.load_cell(args.workload)
    devices = check_device(cell.chips)
    if devices is None:
        return 1
    enable_cache()
    res = run(cell, args.seed, args.seconds, bool(args.trace), devices)
    correct, checks = verdict(res, cell.limits)
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    if args.trace:
        metrics = res["layer"]
    else:
        vals = dict(res["e2e"], setup_s=res["setup_s"])
        metrics = {k: {"value": vals[k], "unit": units[k]} for k in units}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": bool(correct), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace:
        t = res["trace"]
        device["busy_s"] = (sum(c["busy_s"] for c in t["chips"])
                            / len(t["chips"]))
        device["window_s"] = t["window_s"]
        line["breakdown"] = t["breakdown"]
    line["setup_s"] = res["setup_s"]
    line["compiles_in_window"] = res["compiles_in_window"]
    line["reference_s"] = res["reference_s"]
    line["sample"] = res["sample"]
    line["checks"] = checks
    for k, v in checks.items():
        say(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
