"""Serving hot-path benchmark: shape-stable bucketed/packed/fused engine
vs. the exact-shape sequential configuration (the pre-PR dispatch
behaviour), on a mixed prefill/decode workload with varied prompt and
output lengths.

Reported and CI-gated (deterministic, machine-independent):
  decode_programs       jit cache entries decode_step needed (bucketed) —
                        must stay bounded by decode_program_bound
  decode_shapes_exact   entries the SAME workload costs with exact shapes
                        (one program per distinct (B, NPG) — the churn)
  steps / tokens        per-phase step and token counts (scheduling and
                        sampled tokens must not drift)

Nothing here is a time: every number is a count, so a regeneration on any
machine reproduces it. Speed is measured on the chip (benchmarks/chip/).

The host_tier section replays demoted prompts through an engine whose host
tier is on, once with the H2D page staging dispatched concurrently with
decode (overlap_loads=True, the default) and once forced synchronous;
host_hits_tok confirms the replay actually loads back.

The multiprocess section runs the SAME cost-model engines and workload
twice — through the in-process tick router and through the socket plane
(repro.plane: real processes, real TCP, sender-paced WAN delay) — then
kill -9s a replica with decode in flight. Gated: `unresolved` == 0 and
`drill_ok` (the crash loses zero requests).

One process per chip: this parent imports JAX before it spawns the plane,
so on a machine with a chip the parent holds it. The plane children are
cost-backend and import no JAX; keep them so, because a JAX child could
not take a chip its parent already holds.
"""
from __future__ import annotations

import time

import numpy as np


def _workload(vocab: int, smoke: bool):
    rng = np.random.default_rng(0)
    n = 10 if smoke else 24
    lens = rng.integers(5, 120 if smoke else 200, size=n)
    news = rng.integers(4, 16 if smoke else 32, size=n)
    return [(tuple(rng.integers(0, vocab, size=int(L)).tolist()), int(m))
            for L, m in zip(lens, news)]


def _drive(model_cfg, params, reqs, *, bucketed: bool):
    from repro.serving import Engine, EngineConfig, GenRequest, SamplingParams
    from repro.serving import model_runner as mr
    ecfg = EngineConfig(page_size=8, n_pages=256, max_batch=8,
                        max_seq_len=512, prefill_pad=16,
                        bucket_shapes=bucketed, packed_prefill=bucketed)
    eng = Engine(model_cfg, params, ecfg, seed=0)
    before = mr.compile_counts()
    res = eng.generate([GenRequest(
        prompt_tokens=p, sampling=SamplingParams(max_new_tokens=m))
        for p, m in reqs])
    after = mr.compile_counts()
    return {
        "steps": eng.steps,
        "tokens": sum(len(r.output_tokens) for r in res),
        "decode_compiles": after["decode_step"] - before["decode_step"],
        "prefill_compiles": (
            after["prefill_pack_step"] - before["prefill_pack_step"]
            + after["prefill_step"] - before["prefill_step"]),
    }, ecfg


def main(smoke: bool = False) -> dict:
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.bucketing import n_buckets
    import jax
    import jax.numpy as jnp

    model_cfg = get_config("qwen3-0.6b").reduced()
    model = build_model(model_cfg, jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _workload(model_cfg.vocab, smoke)

    bucketed, ecfg = _drive(model_cfg, params, reqs, bucketed=True)
    exact, _ = _drive(model_cfg, params, reqs, bucketed=False)
    deadlines = _deadline_goodput(model_cfg, params, reqs, ecfg)
    host_tier = _host_tier_overlap(model_cfg, params)
    speculation = _speculation(model_cfg, params, reqs, ecfg)
    hedging = _hedging(smoke)
    multiprocess = _multiprocess(smoke)

    bound = (n_buckets(ecfg.max_batch)
             * n_buckets(-(-ecfg.max_seq_len // ecfg.page_size)))
    out = {
        "smoke": smoke,
        "n_requests": len(reqs),
        "bucketed": bucketed,
        "exact": exact,
        "decode_programs": bucketed["decode_compiles"],
        "decode_program_bound": bound,
        "decode_shapes_exact": exact["decode_compiles"],
        "bounded_ok": 1.0 if bucketed["decode_compiles"] <= bound else 0.0,
        "deadlines": deadlines,
        "host_tier": host_tier,
        "speculation": speculation,
        "hedging": hedging,
        "multiprocess": multiprocess,
    }
    for name, row in (("bucketed", bucketed), ("exact", exact)):
        print(f"[serving] {name:9s} {row['steps']:4d} steps "
              f"{row['tokens']:5d} tokens "
              f"{row['decode_compiles']:3d} decode compiles "
              f"{row['prefill_compiles']:3d} prefill compiles")
    print(f"[serving] decode programs {out['decode_programs']} <= bound "
          f"{bound} (exact-shape churn: {out['decode_shapes_exact']})")
    print(f"[serving] deadlines: {deadlines['deadline_aborted_n']} aborted "
          f"(FinishReason.DEADLINE), goodput {deadlines['goodput_tok']} of "
          f"{deadlines['offered_tok']} offered tok "
          f"({100 * deadlines['goodput_frac']:.0f}%)")
    print(f"[serving] host tier: replay "
          f"{host_tier['overlap']['replay_steps_n']} steps overlapped, "
          f"{host_tier['blocking']['replay_steps_n']} blocking, "
          f"{host_tier['overlap']['host_hits_tok']} host-hit tok")
    print(f"[serving] speculation: {speculation['spec_tokens_per_dispatch']:.2f}"
          f" tok/seq/dispatch (gate > 1.5), acceptance "
          f"{speculation['acceptance_rate']:.3f}, exact-match run "
          f"byte-identical: {'OK' if speculation['exact_match_ok'] else 'FAIL'}"
          f", {speculation['decode_programs']} spec programs <= "
          f"{speculation['decode_program_bound']}")
    print(f"[serving] hedging: latency-class ttft p99 "
          f"{hedging['off_ttft_p99_s']:.3f}s -> {hedging['on_ttft_p99_s']:.3f}s"
          f" ({hedging['hedge_n']} hedged, {hedging['hedge_wins_n']} wins, "
          f"{hedging['hedge_wasted_tok']} wasted tok)")
    print(f"[serving] multiprocess: {multiprocess['n_processes']} processes;"
          f" kill -9 drill "
          f"re-dispatched {multiprocess['drill_redispatched_n']}, "
          f"unresolved {multiprocess['unresolved']} (gate == 0); "
          f"partition drill re-homed {multiprocess['partition_rehomed_n']}, "
          f"fenced {multiprocess['partition_fenced_n']}, duplicates "
          f"{multiprocess['duplicate_results']} (gate == 0)")
    return out


def _multiprocess(smoke: bool) -> dict:
    """The multi-process socket plane (repro.plane) vs the in-process tick
    router, SAME cost-model engines, SAME workload, SAME RoutingCore.

    Gated (deterministic): `unresolved` == 0 and `drill_ok` == 1 after a
    kill -9 replica drill — a crash with decode in flight must lose ZERO
    requests (stale heartbeats -> target removed -> stranded work
    re-dispatched) — plus `partition_drill_ok` == 1 and
    `duplicate_results` == 0 after a partition-and-heal drill: one region
    is blackholed from its peers and the client mid-stream (silence, not
    EOF), the client re-homes its parked requests, and after the heal the
    zombie region's frames are fenced so every request resolves exactly
    once."""
    from repro.frontend import Client, RequestState, RouterHost
    from repro.plane import CostEngine, PlaneConfig, ServingPlane, blackhole
    from repro.routing import build_routing
    from repro.serving import GenRequest, InProcessRouter, SamplingParams

    n = 10 if smoke else 24
    max_new, tscale = 12, 0.01

    def reqs():
        rng = np.random.default_rng(5)
        return [GenRequest(
            prompt_tokens=tuple(int(x) for x in
                                rng.integers(1, 5000, size=20)),
            sampling=SamplingParams(max_new_tokens=max_new))
            for _ in range(n)]

    def skew(i):    # diurnal peak on us
        return "us" if i % 3 < 2 else "eu"

    # in-process reference: same RoutingCore over the tick transport,
    # engines stepped serially in this one process
    router = InProcessRouter.from_spec(build_routing("skylb"))
    for region in ("us", "eu"):
        lb = router.add_region(region)
        for k in range(2):
            lb.add_engine(f"{region}-r{k}", CostEngine(time_scale=tscale))
    client = Client(RouterHost(router))
    handles = [client.submit(r, region=skew(i))
               for i, r in enumerate(reqs())]
    client.drain()
    assert all(h.state is RequestState.FINISHED for h in handles)

    # the socket plane: one OS process per engine and per LB
    plane = ServingPlane(PlaneConfig(
        regions=("us", "eu"), replicas=2, backend="cost",
        wan_delay_ms=5.0, time_scale=tscale, stale_after_s=0.3)).start()
    host = plane.host()
    try:
        pclient = Client(host)
        ph = [pclient.submit(r, region=skew(i))
              for i, r in enumerate(reqs())]
        pclient.drain()
        assert all(h.state is RequestState.FINISHED for h in ph)

        # partition-and-heal drill: blackhole eu's LB from its peer and
        # the client mid-stream (silence, not EOF — TCP stays up), let the
        # client's ping liveness re-home the parked requests, then heal
        # after well past 2x stale_after_s and require the zombie region's
        # late frames to be FENCED, not double-resolved
        rng = np.random.default_rng(11)
        pdrill = [pclient.submit(GenRequest(
            prompt_tokens=tuple(int(x) for x in
                                rng.integers(1, 5000, size=20)),
            sampling=SamplingParams(max_new_tokens=200)),
            region=r) for r in ("us", "eu", "eu", "eu")]
        while not all(h.events for h in pdrill):
            pclient.poll()
        plane.isolate_region("eu")
        host.node.set_fault("eu", blackhole())
        t1 = time.perf_counter()
        while time.perf_counter() - t1 < 3 * 0.3 \
                or (host.rehomed < 1 and time.perf_counter() - t1 < 15):
            pclient.poll()
        rehomed_n = host.rehomed
        plane.heal_region("eu")
        host.node.set_fault("eu", None)
        t1 = time.perf_counter()
        while any(not h.done for h in pdrill) \
                and time.perf_counter() - t1 < 60:
            pclient.poll()
        t1 = time.perf_counter()
        while host.counters()["fenced_frames"] < 1 \
                and time.perf_counter() - t1 < 15:
            pclient.poll()
        pc = host.counters()
        partition_ok = (all(h.done for h in pdrill) and rehomed_n >= 1
                        and pc["fenced_frames"] >= 1
                        and pc["duplicate_results"] == 0)

        # kill -9 drill: crash a replica with decode in flight
        drill = [pclient.submit(r, region="us") for r in reqs()[:6]]
        while not any(h.events for h in drill):
            pclient.poll()
        plane.kill_replica("us-r0")
        t1 = time.perf_counter()
        while any(not h.done for h in drill) \
                and time.perf_counter() - t1 < 60:
            pclient.poll()
        drill_ok = all(h.state is RequestState.FINISHED for h in drill)
        m = plane.metrics()
    finally:
        host.close()
        plane.shutdown()
    assert drill_ok, "kill -9 drill lost requests"
    assert partition_ok, (
        f"partition drill failed: rehomed={rehomed_n} counters={pc} "
        f"states={[h.state.value for h in pdrill]}")
    return {
        # CI-gated: the crash drill loses nothing
        "unresolved": m["unresolved"],
        "drill_ok": 1.0 if drill_ok else 0.0,
        # CI-gated: partition-and-heal resolves every request exactly once
        "partition_drill_ok": 1.0 if partition_ok else 0.0,
        "duplicate_results": pc["duplicate_results"],
        "partition_fenced_n": pc["fenced_frames"],
        "partition_rehomed_n": rehomed_n,
        # ungated detail
        "n_requests": n,
        "n_processes": m["n_processes"],
        "drill_redispatched_n": m["redispatched"],
    }


def _host_tier_overlap(model_cfg, params) -> dict:
    """Load-back under eviction pressure (ungated counts): six prompts
    sharing a 40-token stem through a device pool that holds barely two of
    them, then replayed so the demoted chains load back from the host pool
    — with the double-buffered H2D staging dispatched concurrently with
    decode vs forced synchronous."""
    import dataclasses as _dc
    from repro.serving import Engine, EngineConfig, GenRequest, SamplingParams

    rng = np.random.default_rng(7)
    vocab = model_cfg.vocab
    base = tuple(int(t) for t in rng.integers(1, vocab, size=40))
    prompts = [base + tuple(int(t) for t in rng.integers(1, vocab, size=32))
               for _ in range(6)]
    ecfg = EngineConfig(page_size=8, n_pages=23, max_batch=3,
                        max_seq_len=256, prefill_pad=16, host_pages=64)

    def reqs():
        return [GenRequest(prompt_tokens=p,
                           sampling=SamplingParams(max_new_tokens=8))
                for p in prompts]

    def drive(overlap: bool) -> dict:
        eng = Engine(model_cfg, params,
                     _dc.replace(ecfg, overlap_loads=overlap), seed=0)
        eng.generate(reqs())            # demote under pressure
        s0, h0 = eng.steps, eng.core.host_hit_tokens
        eng.generate(reqs())            # replay: host hits -> load-backs
        return {
            "replay_steps_n": eng.steps - s0,
            "host_hits_tok": eng.core.host_hit_tokens - h0,
            "loaded_pages": eng.backend.loaded_pages,
        }

    overlap = drive(True)
    blocking = drive(False)
    assert overlap["host_hits_tok"] > 0, "replay produced no load-backs"
    return {"overlap": overlap, "blocking": blocking}


def _speculation(model_cfg, params, reqs, ecfg) -> dict:
    """Speculative decoding through the fused hot path, CI-gated.

    Two spec-mode runs of the same mixed workload:
      exact-match   drafter == target, real acceptance rule -> outputs must
                    be BYTE-IDENTICAL to the non-speculative engine
                    (exact_match_ok); acceptance is 1.0 by construction
      synthetic     a tiny random-init drafter with the fixed synthetic
                    acceptance coin (spec_synth_rate) -> deterministic
                    spec_tokens_per_dispatch / acceptance_rate numbers the
                    summary gate tracks (gate: > 1.5 emitted tok/seq/step)

    Also re-asserts PR 4's hot-path invariants with speculation ON:
    spec_decode_step programs stay within the bucket bound, and a stable
    batch uploads nothing between steps (steady-state no-upload)."""
    import dataclasses
    from repro.models import build_model
    from repro.serving import Engine, EngineConfig, GenRequest, SamplingParams
    from repro.serving import model_runner as mr
    from repro.serving.bucketing import n_buckets
    import jax
    import jax.numpy as jnp

    dcfg = dataclasses.replace(
        model_cfg, name="drafter", n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=1, d_ff=64, head_dim=16)
    dparams = build_model(dcfg, jnp.float32).init(jax.random.PRNGKey(99))
    k_spec = 3

    def gen(spec_cfg, spec_params, synth):
        ecfg2 = dataclasses.replace(
            ecfg, bucket_shapes=True, packed_prefill=True,
            spec_k=0 if spec_cfg is None else k_spec,
            spec_synth_rate=synth)
        eng = Engine(model_cfg, params, ecfg2, seed=0,
                     draft_cfg=spec_cfg, draft_params=spec_params)
        res = eng.generate([GenRequest(
            prompt_tokens=p, sampling=SamplingParams(max_new_tokens=m))
            for p, m in reqs])
        return eng, [tuple(r.output_tokens) for r in res]

    before = mr.compile_counts()["spec_decode_step"]
    _, base_out = gen(None, None, None)
    eng_x, exact_out = gen(model_cfg, params, None)      # drafter == target
    eng_s, _ = gen(dcfg, dparams, 0.6)                   # synthetic coin
    programs = mr.compile_counts()["spec_decode_step"] - before
    bound = (n_buckets(ecfg.max_batch)
             * n_buckets(-(-ecfg.max_seq_len // ecfg.page_size)))

    b = eng_s.backend
    per_seq_steps = b.spec_drafted / max(1, k_spec)      # seq-steps dispatched
    tpd = eng_s.core.spec_tokens / max(1, per_seq_steps)
    assert tpd > 1.5, f"spec_tokens_per_dispatch {tpd:.2f} <= 1.5"
    bx = eng_x.backend
    tpd_exact = eng_x.core.spec_tokens / max(1, bx.spec_drafted / k_spec)

    # steady-state no-upload, speculation ON: once membership is stable,
    # decode_many reuses the persistent device state end-to-end
    eng2 = Engine(model_cfg, params,
                  dataclasses.replace(ecfg, spec_k=k_spec,
                                      spec_synth_rate=0.6),
                  seed=0, draft_cfg=dcfg, draft_params=dparams)
    for p, m in reqs[:2]:
        eng2.submit(GenRequest(prompt_tokens=p,
                               sampling=SamplingParams(max_new_tokens=64)))
    eng2.step()                                  # admits (prefill only)
    eng2.step()                                  # first spec decode -> sync
    syncs = {"n": 0}
    orig = eng2.backend._sync_slots

    def counting(seqs):
        syncs["n"] += 1
        return orig(seqs)

    eng2.backend._sync_slots = counting
    for _ in range(5):
        eng2.step()
    assert syncs["n"] == 0, "speculative steady state re-uploaded state"

    return {
        "k_spec": k_spec,
        # CI-gated (names shared with the hot-path gate -> auto-matched)
        "decode_programs": programs,
        "decode_program_bound": bound,
        "bounded_ok": 1.0 if programs <= bound else 0.0,
        "spec_tokens_per_dispatch": round(tpd, 3),
        "acceptance_rate": round(b.spec_accepted / max(1, b.spec_drafted), 4),
        "exact_match_ok": 1.0 if exact_out == base_out else 0.0,
        "tokens": sum(len(o) for o in exact_out),
        # ungated detail
        "tok_per_dispatch_exact": round(tpd_exact, 3),
        "steady_sync_uploads": syncs["n"],
    }


def _hedging(smoke: bool) -> dict:
    """Cross-region hedged dispatch, tail-TTFT vs wasted work (ungated —
    custom key names keep every number out of the CI summary gate): a
    two-region sim where the local region's replica is a straggler; the
    `latency` class is duplicated to the healthy peer when predicted TTFT
    blows the budget, first token wins, loser reaped exactly once."""
    from repro.core.metrics import pct
    from repro.core.simulator import ReplicaConfig, Request
    from repro.core.system import ServingSystem
    from repro.routing.hedging import HedgeParams

    rng = np.random.default_rng(3)
    n_lat = 8 if smoke else 24

    def build(hedge: bool):
        sys = ServingSystem("skylb", {"us": 1, "eu": 1},
                            replica_cfg=ReplicaConfig(kv_budget=8192))
        if hedge:
            for lb in sys.lbs.values():
                lb.cfg.hedging = True
                lb.cfg.hedge_params = HedgeParams(ttft_budget_s=0.05)
        sys.replicas[0].cfg.speed_factor = 8.0       # us straggler
        rid = [0]

        def req(region, out_len, slo="standard"):
            rid[0] += 1
            return Request(
                rid=rid[0], user_id=f"u{rid[0]}", session_key=f"s{rid[0]}",
                region=region, output_len=out_len, slo_class=slo,
                prompt_tokens=tuple(
                    int(t) for t in rng.integers(1, 5000, size=64)),
                output_tokens=tuple(range(out_len)))

        for i in range(6):                           # background load
            sys.submit(req("us", 64))
        lat = []
        for i in range(n_lat):
            sys.sim.after(0.2 + 0.15 * i, (lambda r: lambda: sys.submit(r))(
                req("us", 8, slo="latency")))
            lat.append(rid[0])
        sys.run(until=600.0)
        ttfts = [r.ttft - r.issued for r in sys.metrics.completed
                 if r.rid in set(lat) and r.ttft is not None]
        return sys, ttfts

    rng = np.random.default_rng(3)
    sys_off, off = build(False)
    rng = np.random.default_rng(3)
    sys_on, on = build(True)
    m = sys_on.metrics
    assert m.summary()["unresolved"] == 0
    assert sys_off.metrics.summary()["unresolved"] == 0
    return {
        "lat_requests_n": len(on),
        "off_ttft_p50_s": round(pct(off, 50), 4),
        "off_ttft_p99_s": round(pct(off, 99), 4),
        "on_ttft_p50_s": round(pct(on, 50), 4),
        "on_ttft_p99_s": round(pct(on, 99), 4),
        "hedge_n": m.hedged,
        "hedge_wins_n": m.hedge_wins,
        "hedge_wasted_tok": m.wasted_work_tok,
    }


def _deadline_goodput(model_cfg, params, reqs, ecfg) -> dict:
    """Goodput vs throughput through the unified front API: every third
    request arrives with an already-expired deadline (deterministic) and
    aborts with `FinishReason.DEADLINE` before any dispatch; the rest
    stream to completion. Reported ungated (names avoid the CI-gated
    keys): the split is what deadline-aware routing will optimize."""
    import dataclasses
    from repro.frontend import Client, EngineHost, RequestState
    from repro.serving import Engine, GenRequest, SamplingParams
    eng = Engine(model_cfg, params, dataclasses.replace(ecfg), seed=0)
    client = Client(EngineHost(eng))
    handles = [client.submit(GenRequest(
        prompt_tokens=p, sampling=SamplingParams(max_new_tokens=m),
        deadline_s=(0.0 if i % 3 == 0 else None)))
        for i, (p, m) in enumerate(reqs)]
    client.drain()
    served = [h for h in handles if h.state is RequestState.FINISHED]
    aborted = [h for h in handles if h.state is RequestState.DEADLINE]
    assert len(served) + len(aborted) == len(handles)
    goodput = sum(len(h.result.output_tokens) for h in served)
    offered = sum(m for _, m in reqs)
    return {"deadline_aborted_n": len(aborted),
            "goodput_tok": goodput, "offered_tok": offered,
            "goodput_frac": round(goodput / max(1, offered), 4)}


if __name__ == "__main__":
    main(smoke=True)
