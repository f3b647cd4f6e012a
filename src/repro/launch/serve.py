"""Serving launcher: run the paged continuous-batching engine (bf16
params, Qwen3's published dtype) with batched requests — single replica,
or the full two-layer SkyLB router over several in-process replicas across
simulated regions. Both
modes drive the UNIFIED front API (`repro.frontend.Client`): submit returns
a streaming `RequestHandle`, and the reported TTFT comes from each
request's FIRST TokenEvent, not from the terminal result.

A third mode, `--procs`, serves the same front API from the multi-process
socket plane (`repro.plane`): one LB process per region, cost-model
replica processes, TCP transport with sender-paced WAN delay. JAX is not
imported in that mode (nor in any of its children).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b-reduced \
      --requests 24 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --multiregion --variant skylb
  PYTHONPATH=src python -m repro.launch.serve --procs --replicas 2
"""
from __future__ import annotations

import argparse
import statistics
import time

import numpy as np

from repro.frontend import Client, RequestState
from repro.serving import GenRequest, SamplingParams

REGIONS = ("us", "eu", "asia")


def make_requests(vocab: int, n: int, *, sessions: int = 6,
                  turns: int = 2, max_new: int = 16, seed: int = 0):
    """Multi-turn style requests: `sessions` users, each turn extends the
    previous prompt (prefix-shareable)."""
    rng = np.random.default_rng(seed)
    reqs, histories = [], {}
    for i in range(n):
        u = i % sessions
        hist = histories.get(u, tuple(rng.integers(1, vocab, size=24).tolist()))
        new = tuple(rng.integers(1, vocab, size=int(rng.integers(8, 24))).tolist())
        prompt = hist + new
        reqs.append(GenRequest(
            prompt_tokens=prompt, user_id=f"u{u}", session_key=f"u{u}",
            sampling=SamplingParams(max_new_tokens=max_new)))
        histories[u] = prompt + tuple(int(x) for x in
                                      rng.integers(1, vocab, size=max_new))
    return reqs


def _drain_and_stats(client: Client, handles: list) -> dict:
    t0 = time.time()
    client.drain()
    dt = time.time() - t0
    done = [h for h in handles if h.state is RequestState.FINISHED]
    out_toks = sum(len(h.result.output_tokens) for h in done)
    # client-observed TTFT: submission -> first streamed TokenEvent
    ttfts = [h.events[0].t - h.request.arrival_s for h in done
             if h.events and h.request.arrival_s is not None]
    return {"requests": len(done), "wall_s": round(dt, 2),
            "tok_per_s": round(out_toks / dt, 1),
            "ttft_p50_s": round(statistics.median(ttfts), 3) if ttfts
            else None}


def serve_single(arch: str, n_requests: int, max_new: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.frontend import EngineHost
    from repro.models import build_model
    from repro.serving import Engine, EngineConfig

    cfg = get_config(arch)
    model = build_model(cfg, jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, EngineConfig(page_size=8, n_pages=256,
                                           max_batch=8, max_seq_len=1024,
                                           prefill_pad=32))
    client = Client(EngineHost(eng))
    handles = [client.submit(r)
               for r in make_requests(cfg.vocab, n_requests, max_new=max_new)]
    out = _drain_and_stats(client, handles)
    out.update({"hit_rate": round(eng.hit_rate(), 3),
                "engine_steps": eng.steps})
    return out


def serve_multiregion(arch: str, n_requests: int, max_new: int,
                      variant: str = "skylb") -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.frontend import RouterHost
    from repro.models import build_model
    from repro.routing import build_routing
    from repro.serving import Engine, EngineConfig, InProcessRouter

    cfg = get_config(arch)
    model = build_model(cfg, jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    # the same build_routing() spec the simulator's ServingSystem uses
    router = InProcessRouter.from_spec(build_routing(variant))
    for r, region in enumerate(REGIONS):
        lb = router.add_region(region)
        for k in range(2):
            lb.add_engine(f"{region}-r{k}", Engine(
                cfg, params, EngineConfig(page_size=8, n_pages=128,
                                          max_batch=4, max_seq_len=1024,
                                          prefill_pad=32)))
    client = Client(RouterHost(router))
    reqs = make_requests(cfg.vocab, n_requests, max_new=max_new)
    # skew arrivals: most load lands on 'us' (the diurnal-peak region)
    handles = [client.submit(req,
                             region="us" if i % 4 < 2 else REGIONS[i % 3])
               for i, req in enumerate(reqs)]
    out = _drain_and_stats(client, handles)
    out["forwarded"] = {r: lb.forwarded_out for r, lb in router.lbs.items()}
    out["hit_rates"] = {
        r: {e: round(lb.engines[e].hit_rate(), 3) for e in lb.engines}
        for r, lb in router.lbs.items()}
    return out


def serve_procs(n_requests: int, max_new: int, *, variant: str = "skylb",
                regions: tuple = ("us", "eu"), replicas: int = 2) -> dict:
    """The multi-process plane behind the same unified front API: real
    LB / replica processes over TCP, cost-model engines (no JAX anywhere
    in the process tree), sender-paced WAN delay."""
    from repro.plane import PlaneConfig, ServingPlane

    plane = ServingPlane(PlaneConfig(
        regions=regions, replicas=replicas, variant=variant,
        backend="cost", wan_delay_ms=10.0, time_scale=0.02)).start()
    host = plane.host()
    try:
        client = Client(host)
        reqs = make_requests(5000, n_requests, max_new=max_new)
        handles = [client.submit(req, region=regions[0] if i % 4 < 2
                                 else regions[i % len(regions)])
                   for i, req in enumerate(reqs)]
        out = _drain_and_stats(client, handles)
        m = plane.metrics()
        out.update({"processes": m["n_processes"],
                    "forwards": m["forwards"],
                    "unresolved": m["unresolved"]})
    finally:
        host.close()
        plane.shutdown()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b-reduced")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--multiregion", action="store_true")
    ap.add_argument("--procs", action="store_true",
                    help="multi-process socket plane (cost backend)")
    ap.add_argument("--regions", default="us,eu",
                    help="--procs: comma-separated region list")
    ap.add_argument("--replicas", type=int, default=2,
                    help="--procs: replica processes per region")
    ap.add_argument("--variant", default="skylb",
                    help="routing variant (see repro.routing.VARIANTS)")
    args = ap.parse_args()
    if not args.procs:                  # --procs never imports JAX
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    if args.procs:
        out = serve_procs(args.requests, args.max_new,
                          variant=args.variant.lower(),
                          regions=tuple(args.regions.split(",")),
                          replicas=args.replicas)
    elif args.multiregion:
        out = serve_multiregion(args.arch, args.requests, args.max_new,
                                args.variant.lower())
    else:
        out = serve_single(args.arch, args.requests, args.max_new)
    print(out)


if __name__ == "__main__":
    main()
