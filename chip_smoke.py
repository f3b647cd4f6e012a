#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU: qwen3-0.6b at its published
widths, bf16 params from a seed, through the entry points a user calls.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips of one host

Phases of the one-chip run, in order, each printing its own lines:

  device   what JAX sees (devices, platform, device kind, count, memory);
           exits non-zero unless the platform is `tpu`
  kernels  paged_decode, paged_verify, page_gather and page_scatter through
           `repro.kernels.ops` (Pallas on the chip) against their
           `kernels/ref.py` oracles on the same chip, at qwen3 widths in
           bf16 with 16-token pages; fails beyond KERNEL_TOL
  engine   one Engine with a deployment-sized pool (4096 x 16-token pages,
           7.5 GB of KV beside 1.2 GB of weights) serves two turns of
           multi-turn sessions through Client(EngineHost(engine)); every
           request must finish with its token count and the second turns
           must hit the prefix cache. Then an engine with a pool of a few
           dozen pages and a host tier must demote and load back pages
           (page_gather and the donated page_scatter)
  router   InProcessRouter.from_spec(build_routing("skylb")) over two
           regions with one engine each on the same chip, skewed arrivals;
           every request finishes and at least one crosses regions

`--four-chips` runs only its own phase: the router puts four engines on
four chips, one per device, and serves one request trace; then the same
trace runs with the same four engines all on the first chip. The tick
router has no deadlines here, so it is deterministic: the routing decision
streams and every request's tokens must be identical between the two runs.

Lines tagged `info` (compile seconds, wall seconds, tokens/s, peak bytes)
are informational, not measurements. Everything runs in this one process:
a chip belongs to one process at a time. The last line of standard output
is one JSON object, {"ok": true, "device": {...}}, printed only when every
phase passed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.configs import get_config                          # noqa: E402
from repro.frontend import (Client, EngineHost, RequestState,  # noqa: E402
                            RouterHost)
from repro.kernels import ops, ref                            # noqa: E402
from repro.launch.compile_cache import enable_compile_cache   # noqa: E402
from repro.launch.serve import make_requests                  # noqa: E402
from repro.models import build_model                          # noqa: E402
from repro.routing import build_routing                       # noqa: E402
from repro.serving import (Engine, EngineConfig, GenRequest,  # noqa: E402
                           InProcessRouter, SamplingParams)

MODEL = "qwen3-0.6b"
PAGE = 16
# bf16 kernel tolerance, the one tests/test_kernels.py holds the Pallas
# kernels to: |kernel - oracle| <= KERNEL_TOL * (1 + |oracle|) elementwise
KERNEL_TOL = 2e-2
MAX_NEW = 16
# the deployment pool: 4096 pages x 16 tokens x 112 KiB/token = 7.5 GB
ENGINE = EngineConfig(page_size=PAGE, n_pages=4096, max_batch=32,
                      max_seq_len=4096, prefill_pad=64)
# a few dozen pages: the replay below must evict, so pages demote to host
HOST_TIER = EngineConfig(page_size=PAGE, n_pages=20, max_batch=4,
                         max_seq_len=512, prefill_pad=64, host_pages=64)
# router replicas: small enough that four fit on one chip beside weights
REPLICA = EngineConfig(page_size=PAGE, n_pages=1024, max_batch=4,
                       max_seq_len=4096, prefill_pad=64)
# tick heartbeats: cap the LB's between-probe optimism at about one
# engine iteration, so a burst spills across regions instead of piling
# onto the local replica that looked available (as the multiregion example)
ROUTING_OVERRIDES = {"max_inflight_per_probe": 2}


class SmokeFailure(Exception):
    """A phase saw a wrong result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def info(phase: str, **kv) -> None:
    say(phase, "info " + " ".join(f"{k}={v}" for k, v in kv.items()))


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, summed from its
    monitoring events (registered once, in `main`)."""

    def __init__(self):
        self.total = 0.0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += duration


def peak_bytes(device) -> int | None:
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


# ------------------------------------------------------------------ device

def device_phase(need: int) -> dict | None:
    devices = jax.devices()
    d = devices[0]
    say("device", f"devices={devices}")
    say("device", f"platform={d.platform} kind={d.device_kind} "
                  f"count={len(devices)}")
    say("device", f"memory_stats={d.memory_stats()}")
    if d.platform != "tpu":
        print(f"FAIL: no TPU: JAX runs on {d.platform}", file=sys.stderr)
        return None
    if len(devices) < need:
        print(f"FAIL: need {need} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return None
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


# ----------------------------------------------------------------- kernels

def kernel_phase(cfg, clock: CompileClock, seed: int) -> None:
    rng = np.random.default_rng(seed)
    H, K, hd, L = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.n_layers
    B, NPG, Q, P = 8, 32, 3, 512

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32),
                           jnp.bfloat16)

    k_pages, v_pages = normal(P, PAGE, K, hd), normal(P, PAGE, K, hd)
    table = rng.permutation(P)[:B * NPG].reshape(B, NPG).astype(np.int32)
    lens = rng.integers(Q, NPG * PAGE + 1, size=B).astype(np.int32)
    lens[:2] = Q, NPG * PAGE                    # shortest and fullest rows
    k_pool, v_pool = normal(L, 64, PAGE, K, hd), normal(L, 64, PAGE, K, hd)
    ids = rng.permutation(64)[:B].astype(np.int32)
    k_stack, v_stack = normal(B, L, PAGE, K, hd), normal(B, L, PAGE, K, hd)
    cases = {
        "paged_decode": (ops.paged_decode, ref.paged_decode_ref,
                         (normal(B, H, hd), k_pages, v_pages, table, lens)),
        "paged_verify": (ops.paged_verify, ref.paged_verify_ref,
                         (normal(B, Q, H, hd), k_pages, v_pages, table,
                          lens)),
        "page_gather": (ops.page_gather,
                        lambda kp, vp, i: (ref.page_gather_ref(kp, i),
                                           ref.page_gather_ref(vp, i)),
                        (k_pool, v_pool, ids)),
        "page_scatter": (ops.page_scatter,
                         lambda kp, vp, ks, vs, i: (
                             ref.page_scatter_ref(kp, ks, i),
                             ref.page_scatter_ref(vp, vs, i)),
                         (k_pool, v_pool, k_stack, v_stack, ids)),
    }
    for name, (kernel, oracle, args) in cases.items():
        t0 = time.perf_counter()
        compiled = jax.jit(kernel).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no Pallas kernel in the compiled program")
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(*args))
        wall_s = time.perf_counter() - t0
        want = jax.jit(oracle)(*args)
        err, bound_ok = 0.0, True
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            g = np.asarray(g, np.float32)
            w = np.asarray(w, np.float32)
            check(g.shape == w.shape and np.isfinite(g).all(),
                  f"{name}: shape {g.shape} vs {w.shape} or non-finite")
            diff = np.abs(g - w)
            err = max(err, float(diff.max()))
            bound_ok &= bool((diff <= KERNEL_TOL * (1 + np.abs(w))).all())
        say("kernels", f"{name} max_abs_err={err} tol={KERNEL_TOL}*(1+|ref|)")
        info("kernels", kernel=name, compile_s=round(compile_s, 3),
             wall_s=round(wall_s, 6))
        check(bound_ok, f"{name}: max abs error {err} beyond tolerance")


# ------------------------------------------------------------------ engine

def serve(client: Client, reqs: list, regions=None) -> tuple:
    """Submit, drain, and check every handle FINISHED with its token
    count. Returns (handles, wall seconds, tokens out)."""
    t0 = time.perf_counter()
    handles = [client.submit(r) if regions is None
               else client.submit(r, region=regions[i])
               for i, r in enumerate(reqs)]
    client.drain()
    wall = time.perf_counter() - t0
    for h in handles:
        want = h.request.sampling.max_new_tokens
        check(h.state is RequestState.FINISHED,
              f"request {h.rid} ended {h.state}")
        check(len(h.tokens) == want and h.result.output_tokens == h.tokens,
              f"request {h.rid}: {len(h.tokens)} tokens, wanted {want}")
    return handles, wall, sum(len(h.tokens) for h in handles)


def report(phase: str, clock: CompileClock, c0: float, wall: float,
           toks: int, device, **extra) -> None:
    info(phase, compile_s=round(clock.total - c0, 3), wall_s=round(wall, 3),
         tok_s=round(toks / wall, 2), peak_bytes_in_use=peak_bytes(device),
         **extra)


def engine_phase(cfg, params, clock: CompileClock, seed: int,
                 ecfg: EngineConfig = ENGINE) -> None:
    device = jax.tree.leaves(params)[0].devices().pop()
    c0 = clock.total
    eng = Engine(cfg, params, ecfg, seed=seed)
    client = Client(EngineHost(eng))
    reqs = make_requests(cfg.vocab, 12, sessions=6, max_new=MAX_NEW,
                         seed=seed)
    wall = toks = 0
    for turn in (reqs[:6], reqs[6:]):     # turn 2 extends turn 1's prompts
        _, w, n = serve(client, turn)
        wall, toks = wall + w, toks + n
    say("engine", f"requests=12 finished=12 pool_pages={ecfg.n_pages} "
                  f"hit_rate={eng.hit_rate():.3f}")
    report("engine", clock, c0, wall, toks, device, steps=eng.steps)
    check(eng.hit_rate() > 0, "no cached-prefix hit on the second turns")


def host_tier_phase(cfg, params, clock: CompileClock, seed: int,
                    ecfg: EngineConfig = HOST_TIER) -> None:
    device = jax.tree.leaves(params)[0].devices().pop()
    c0 = clock.total
    eng = Engine(cfg, params, ecfg, seed=seed)
    client = Client(EngineHost(eng))
    rng = np.random.default_rng(seed + 1)
    base = tuple(int(t) for t in rng.integers(1, cfg.vocab, size=40))
    prompts = [base + tuple(int(t) for t in rng.integers(1, cfg.vocab,
                                                         size=32))
               for _ in range(6)]
    wall = toks = 0
    for _ in range(2):                    # the replay finds them on host
        _, w, n = serve(client, [GenRequest(
            prompt_tokens=p, sampling=SamplingParams(max_new_tokens=MAX_NEW))
            for p in prompts])
        wall, toks = wall + w, toks + n
    be = eng.backend
    say("engine", f"host tier: pool_pages={ecfg.n_pages} "
                  f"demoted_pages={be.demoted_pages} "
                  f"loaded_pages={be.loaded_pages} "
                  f"host_hit_tokens={eng.core.host_hit_tokens}")
    report("engine-host-tier", clock, c0, wall, toks, device)
    check(be.demoted_pages > 0 and be.loaded_pages > 0,
          "the small pool never demoted and loaded back a page")


# ------------------------------------------------------------------ router

def skewed_regions(n: int) -> list:
    """Three of every four arrivals land on 'us', as launch/serve skews."""
    return ["eu" if i % 4 == 3 else "us" for i in range(n)]


def run_router(cfg, params_of: list, seed: int, ecfg: EngineConfig,
               record: bool = False) -> tuple:
    """Serve one multi-turn trace through a two-region skylb router whose
    engine i is built over params_of[i] (two engines per region when four
    are given, else one). Returns (router, handles, wall s, tokens)."""
    overrides = dict(ROUTING_OVERRIDES, record_decisions=record)
    router = InProcessRouter.from_spec(build_routing("skylb"),
                                       cfg_overrides=overrides)
    per_region = len(params_of) // 2
    for r, region in enumerate(("us", "eu")):
        lb = router.add_region(region)
        for k in range(per_region):
            lb.add_engine(f"{region}-r{k}", Engine(
                cfg, params_of[r * per_region + k], ecfg, seed=seed))
    reqs = make_requests(cfg.vocab, 16 * per_region, sessions=8,
                         max_new=MAX_NEW, seed=seed)
    handles, wall, toks = serve(Client(RouterHost(router)), reqs,
                                skewed_regions(len(reqs)))
    return router, handles, wall, toks


def router_phase(cfg, params, clock: CompileClock, seed: int,
                 ecfg: EngineConfig = REPLICA) -> None:
    device = jax.tree.leaves(params)[0].devices().pop()
    c0 = clock.total
    router, handles, wall, toks = run_router(cfg, [params, params], seed,
                                             ecfg)
    fwd = {r: lb.forwarded_out for r, lb in router.lbs.items()}
    say("router", f"requests={len(handles)} finished={len(handles)} "
                  f"forwarded_out={fwd} ticks={router.tick}")
    report("router", clock, c0, wall, toks, device)
    check(sum(fwd.values()) > 0, "no request was forwarded across regions")


# -------------------------------------------------------------- four chips

def router_trace(router, handles) -> dict:
    """What must not depend on placement: every LB's decision stream and
    the router's events, with request ids replaced by trace index, and
    every request's tokens."""
    index = {h.rid: i for i, h in enumerate(handles)}
    decisions = {
        region: [tuple(index.get(x, x) if j == 1 else x
                       for j, x in enumerate(d)) for d in lb.core.decisions]
        for region, lb in router.lbs.items()}
    return {"decisions": decisions, "events": list(router.events),
            "tokens": [h.tokens for h in handles]}


def four_chip_phase(cfg, params, clock: CompileClock, seed: int,
                    devices: list, ecfg: EngineConfig = REPLICA) -> None:
    spread = [jax.device_put(params, d) for d in devices]
    c0 = clock.total
    router, handles, wall, toks = run_router(cfg, spread, seed, ecfg,
                                             record=True)
    engines = [e for lb in router.lbs.values() for e in lb.engines.values()]
    for e, d in zip(engines, devices):
        check(e.backend.k_pages.devices() == {d}
              and e.backend.v_pages.devices() == {d},
              f"an engine over params on {d} keeps its state elsewhere")
    spread_trace = router_trace(router, handles)
    fwd = sum(lb.forwarded_out for lb in router.lbs.values())
    say("four-chips", f"one engine per chip: requests={len(handles)} "
                      f"forwarded_out={fwd} ticks={router.tick} "
                      f"devices={[str(d) for d in devices]}")
    report("four-chips spread", clock, c0, wall, toks, devices[0])
    for d in devices:
        info("four-chips", device=str(d), peak_bytes_in_use=peak_bytes(d))
    del router, handles, engines, spread
    gc.collect()

    c0 = clock.total
    shared = jax.device_put(params, devices[0])
    router, handles, wall, toks = run_router(cfg, [shared] * 4, seed, ecfg,
                                             record=True)
    same_trace = router_trace(router, handles)
    say("four-chips", f"all engines on {devices[0]}: "
                      f"requests={len(handles)} ticks={router.tick}")
    report("four-chips one-device", clock, c0, wall, toks, devices[0])
    info("four-chips", device=str(devices[0]),
         peak_bytes_in_use=peak_bytes(devices[0]))
    n_dec = sum(len(v) for v in spread_trace["decisions"].values())
    check(n_dec > 0, "the trace made no routing decision")
    check(spread_trace["decisions"] == same_trace["decisions"]
          and spread_trace["events"] == same_trace["events"],
          "routing decisions differ between the placements")
    check(spread_trace["tokens"] == same_trace["tokens"],
          "tokens differ between the placements")
    say("four-chips", f"decision streams ({n_dec} decisions) and tokens of "
                      f"all {len(handles)} requests identical across "
                      f"placements")


# -------------------------------------------------------------------- main

def build_params(cfg, seed: int):
    return build_model(cfg, jnp.bfloat16).init(jax.random.PRNGKey(seed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip placement phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = device_phase(4 if args.four_chips else 1)
    if device is None:
        return 1
    say("device", f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    cfg = get_config(MODEL)
    try:
        t0 = time.perf_counter()
        params = jax.block_until_ready(build_params(cfg, args.seed))
        n_params = sum(x.size for x in jax.tree.leaves(params))
        say("params", f"{MODEL} layers={cfg.n_layers} d_model={cfg.d_model} "
                      f"vocab={cfg.vocab} params={n_params} dtype=bfloat16")
        info("params", init_s=round(time.perf_counter() - t0, 3))
        if args.four_chips:
            four_chip_phase(cfg, params, clock, args.seed,
                            jax.devices()[:4])
        else:
            kernel_phase(cfg, clock, args.seed)
            for phase in (engine_phase, host_tier_phase, router_phase):
                phase(cfg, params, clock, args.seed)
                gc.collect()        # free the phase's pools before the next
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
