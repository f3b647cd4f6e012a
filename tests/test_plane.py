"""The multi-process serving plane (repro.plane): wire codec, socket
transport parity with the tick transport, end-to-end 2x2 runs over real
processes, and the crash drills (kill -9 a replica, kill -9 an LB).

The multi-process tests spawn REAL OS processes over REAL TCP sockets on
the cost-model backend (JAX-free children, ~0.15 s import each); the
conftest `no_leaked_children` fixture asserts every one of them is reaped.
"""
from __future__ import annotations

import time

import pytest

from repro.frontend import Client
from repro.plane import wire
from repro.plane.mailbox import Node
from repro.plane.metrics import merge_snapshots
from repro.plane.replica import CostEngine
from repro.plane.transport import SocketTransport
from repro.routing import RoutingCore, TargetView, build_routing
from repro.serving.request import (FinishReason, GenRequest, SamplingParams)


def _roundtrip(m):
    """pack() emits a full frame (length prefix + body); unpack() takes
    the body — exactly what a reader hands it after the length read."""
    return wire.unpack(wire.pack(m)[4:])


def _req(rid=None, prompt=(1, 2, 3, 4), max_new=4, **kw):
    r = GenRequest(prompt_tokens=tuple(prompt),
                   sampling=SamplingParams(max_new_tokens=max_new), **kw)
    if rid is not None:
        r.rid = rid
    return r


# ---------------------------------------------------------------- wire codec

class TestWire:
    @pytest.mark.parametrize("codec", ["msgpack", "json"])
    def test_request_roundtrip(self, codec, monkeypatch):
        monkeypatch.setenv("REPRO_PLANE_CODEC", codec)
        req = _req(prompt=range(10), max_new=7, user_id="u1",
                   session_key="s1", priority=2, deadline_s=1.5,
                   slo_class="latency")
        req.arrival_s = 123.0
        req.on_token = lambda *a: None
        m = _roundtrip(wire.msg("submit", req=wire.encode_request(req)))
        got = wire.decode_request(m["req"])
        assert got.rid == req.rid
        assert got.prompt_tokens == tuple(range(10))
        assert got.sampling == req.sampling
        assert (got.user_id, got.session_key) == ("u1", "s1")
        assert got.slo_class == "latency"
        assert got.deadline_s == 1.5
        # callbacks never cross the wire; arrival is re-stamped by the
        # accepting process
        assert got.on_token is None and got.arrival_s is None

    def test_result_roundtrip(self):
        from repro.serving.request import GenResult
        res = GenResult(rid=9, output_tokens=(5, 6),
                        finish_reason=FinishReason.STOP, cached_tokens=3,
                        prompt_len=8, ttft_s=0.1, e2e_s=0.5)
        got = wire.decode_result(_roundtrip(
            wire.msg("result", res=wire.encode_result(res)))["res"])
        assert got == res

    def test_bytes_payload_both_codecs(self, monkeypatch):
        for codec in ("msgpack", "json"):
            monkeypatch.setenv("REPRO_PLANE_CODEC", codec)
            m = _roundtrip(wire.msg(
                "kvpages", kv=wire.encode_bytes(b"\x00\xffpages")))
            assert wire.decode_bytes(m["kv"]) == b"\x00\xffpages"


class TestDeadlineClockOwnership:
    """The cross-process deadline rule (repro.plane.wire docstring):
    deliver frames STRIP the deadline (replicas never judge one on their
    own clock), forward frames carry the REMAINING duration (the receiving
    LB re-stamps arrival and owns it), submit frames keep it whole."""

    def test_deliver_strips(self):
        req = _req(deadline_s=2.0)
        req.arrival_s = 100.0
        d = wire.encode_request(req, deadline=wire.STRIP)
        assert d["deadline_s"] is None

    def test_forward_carries_remaining(self):
        req = _req(deadline_s=2.0)
        req.arrival_s = 100.0
        d = wire.encode_request(req, deadline=wire.REMAINING, now=100.75)
        assert d["deadline_s"] == pytest.approx(1.25)

    def test_submit_keeps(self):
        d = wire.encode_request(_req(deadline_s=3.0), deadline=wire.KEEP)
        assert d["deadline_s"] == 3.0

    def test_cost_engine_never_judges_deadlines(self):
        """A replica-side engine must not re-judge deadlines against its
        own monotonic epoch: a request whose LB-side deadline would look
        ancient here still runs to completion (the LB sends an explicit
        cancel frame when ITS clock expires it)."""
        e = CostEngine(time_scale=0)
        req = _req(max_new=5)
        req.arrival_s = time.monotonic() - 10_000.0   # "hours" old
        assert req.deadline_s is None                 # wire-delivered shape
        e.submit(req)
        res = e.run_until_idle()[req.rid]
        assert res.finish_reason == FinishReason.LENGTH
        assert len(res.output_tokens) == 5


def test_clone_for_dispatch_resets_lifecycle():
    done = []
    req = _req(prompt=(7, 8, 9), deadline_s=1.0, user_id="u",
               session_key="sess", priority=2, slo_class="latency")
    req.arrival_s, req.cancelled, req.cached_tokens = 5.0, "cancelled", 3
    req.first_token_s = 6.0
    req.on_done = done.append
    req.output_tokens = (11, 12)
    clone = req.clone_for_dispatch()
    assert clone.rid != req.rid
    assert clone.prompt_tokens == req.prompt_tokens
    assert clone.sampling == req.sampling
    assert (clone.user_id, clone.session_key) == ("u", "sess")
    assert (clone.priority, clone.slo_class) == (2, "latency")
    assert clone.output_tokens == (11, 12)      # content rides along
    # every lifecycle field reset: no second deadline owner, no travelling
    # cancel, no inherited callbacks double-firing the primary's handle
    assert clone.deadline_s is None and clone.cancelled is None
    assert clone.arrival_s is None and clone.first_token_s is None
    assert clone.cached_tokens == 0
    assert clone.on_admit is None and clone.on_token is None \
        and clone.on_done is None
    same = req.clone_for_dispatch(fresh_rid=False)
    assert same.rid == req.rid


# ------------------------------------------------------- transport parity

def _drive(core, rids):
    """The scripted entry-call trace both transports replay: probe, local
    dispatches, capacity collapse, cross-region forwards, a cancel."""
    fresh = lambda: [TargetView(id="us-r0"), TargetView(id="us-r1")]
    core.refresh_local(fresh())
    core.refresh_remote([TargetView(id="eu", n_avail_replicas=2,
                                    n_replicas=2)])
    for rid in rids[:4]:
        core.on_request(_req(rid=rid, prompt=(rid % 2, 1, 2, 3)))
    # local capacity collapses -> the next requests must forward to eu
    core.refresh_local([TargetView(id="us-r0", available=False,
                                   pending=9, outstanding=9),
                        TargetView(id="us-r1", available=False,
                                   pending=9, outstanding=9)])
    for rid in rids[4:6]:
        core.on_request(_req(rid=rid, prompt=(rid % 2, 1, 2, 3)))
    # one queued request (nothing eligible anywhere), then cancelled
    core.refresh_remote([TargetView.unavailable("eu")])
    core.on_request(_req(rid=rids[6]))
    core.cancel(rids[6])
    # capacity returns; one more local dispatch
    core.refresh_local(fresh())
    core.on_request(_req(rid=rids[7]))


def test_tick_vs_socket_decision_parity():
    """The SAME RoutingCore fed the SAME entry-call trace must produce the
    SAME decision stream over the tick transport (InProcessRouter's
    `_TickTransport`) and over `SocketTransport` (real frames on real
    sockets, delays zeroed) — the socket plane changes the substrate, never
    the brain.  The socket side's frames are then decoded at the receiving
    nodes to confirm the wire carried exactly the decided dispatches."""
    from repro.serving.router import InProcessRouter
    rids = list(range(9100, 9108))

    # --- tick side
    router = InProcessRouter.from_spec(
        "skylb", cfg_overrides={"record_decisions": True},
        wan_delay_ticks=0, local_delay_ticks=0)
    lb = router.add_region("us")
    router.add_region("eu")
    lb.add_engine("us-r0", CostEngine(time_scale=0))
    lb.add_engine("us-r1", CostEngine(time_scale=0))
    _drive(lb.core, rids)
    tick_decisions = list(lb.core.decisions)

    # --- socket side: one LB node + a sink node per peer, zero delay
    spec = build_routing("skylb")
    lb_node = Node()
    sinks = {name: Node() for name in ("us-r0", "us-r1", "eu")}
    try:
        for name, sink in sinks.items():
            lb_node.connect(sink.addr, name, delay_s=0.0)
        transport = SocketTransport(lb_node, "us", stale_after_s=60.0)
        core = RoutingCore("us", spec.local_policy(), spec.remote_policy(),
                           spec.make_config(record_decisions=True),
                           transport)
        for name in sinks:
            transport.saw(name)
        core.target_added(TargetView(id="us-r0"))
        core.target_added(TargetView(id="us-r1"))
        core.peer_added("eu")
        _drive(core, rids)
        assert core.decisions == tick_decisions
        # equal decisions must also be what physically left on the wire
        deadline = time.monotonic() + 5.0
        seen = []
        want = sum(1 for d in tick_decisions
                   if d[0] in ("local", "forward"))
        while len(seen) < want and time.monotonic() < deadline:
            for name, sink in sinks.items():
                got = sink.poll(0.01)
                if got is not None:
                    _conn, m = got
                    if m["t"] in ("deliver", "forward"):
                        seen.append((m["t"], m["req"]["rid"], name))
        wire_expect = [("deliver" if d[0] == "local" else "forward",
                        d[1], d[2]) for d in tick_decisions
                       if d[0] in ("local", "forward")]
        assert sorted(seen) == sorted(wire_expect)
    finally:
        lb_node.close()
        for sink in sinks.values():
            sink.close()
    assert [d for d in tick_decisions if d[0] == "forward"], \
        "trace must exercise cross-region forwarding"
    assert [d for d in tick_decisions if d[0] == "cancel"]


# ------------------------------------------------------- wan delay pacing

def test_sender_side_wan_delay():
    a, b = Node(), Node()
    try:
        a.connect(b.addr, "b", delay_s=0.12)
        t0 = time.monotonic()
        a.send_to("b", wire.msg("ping", n=1))
        got = b.poll(5.0)
        dt = time.monotonic() - t0
        assert got is not None and got[1]["t"] == "ping"
        assert dt >= 0.11, f"frame arrived after {dt:.3f}s, delay not paced"
    finally:
        a.close()
        b.close()


def test_socket_transport_liveness_is_heartbeat_freshness():
    a, b = Node(), Node()
    try:
        a.connect(b.addr, "rep")
        tr = SocketTransport(a, "us", stale_after_s=0.08)
        assert not tr.target_alive("rep")       # never heard from it
        tr.saw("rep")
        assert tr.target_alive("rep")
        time.sleep(0.1)
        assert not tr.target_alive("rep")       # stale: kill -9 semantics
    finally:
        a.close()
        b.close()


def test_merge_snapshots_schema():
    merged = merge_snapshots([
        {"kind": "replica", "id": "us-r0", "uptime_s": 2.0, "completed": 3,
         "output_tokens": 30, "prompt_tokens": 40, "cached_tokens": 10,
         "cancelled": 1, "deadline_aborted": 1, "rejected": 0, "steps": 50},
        {"kind": "lb", "id": "us", "uptime_s": 2.1, "issued": 6,
         "resolved": 5, "forwarded_out": 2, "hedged": 1, "hedge_wins": 1,
         "wasted_work_tok": 4, "redispatched": 1},
    ])
    # the exact keys benchmark tables gate on (RunMetrics.summary shape)
    for key in ("requests", "throughput_tok_s", "hit_rate", "forwards",
                "cancelled", "deadline_aborted", "issued", "unresolved",
                "hedged", "hedge_wins", "wasted_work_tok"):
        assert key in merged
    assert merged["requests"] == 3
    assert merged["hit_rate"] == pytest.approx(0.25)
    assert merged["unresolved"] == 1
    assert merged["forwards"] == 2


# --------------------------------------------------- multi-process E2E

def _mkplane(**kw):
    from repro.plane import PlaneConfig, ServingPlane
    cfg = dict(regions=("eu", "us"), replicas=2, wan_delay_ms=5.0,
               time_scale=0.01, stale_after_s=0.3)
    cfg.update(kw)
    return ServingPlane(PlaneConfig(**cfg)).start()


def _drain(client, handles, timeout_s=30.0):
    t0 = time.monotonic()
    while any(not h.done for h in handles) \
            and time.monotonic() - t0 < timeout_s:
        client.poll()
    return [h.state.value for h in handles]


def test_plane_2x2_smoke_streaming_cancel_deadline():
    """The acceptance run: 2 regions x 2 replica processes over
    SocketTransport — streaming, cancel, and deadline all end-to-end
    across real process boundaries."""
    plane = _mkplane()
    host = plane.host()
    try:
        client = Client(host)
        # streaming: every token arrives as an indexed event
        hs = [client.submit(_req(prompt=range(i, i + 20), max_new=6),
                            region=("us" if i % 2 else "eu"))
              for i in range(6)]
        assert _drain(client, hs) == ["finished"] * 6
        for h in hs:
            assert [e.index for e in h.events] == list(range(6))
            assert len(h.result.output_tokens) == 6
        # cancel: a long request abandoned mid-flight resolves CANCELLED
        hc = client.submit(_req(prompt=range(40, 70), max_new=500),
                           region="us")
        t0 = time.monotonic()
        while not hc.events and time.monotonic() - t0 < 10:
            client.poll()
        assert hc.cancel()
        _drain(client, [hc])
        assert hc.state.value == "cancelled"
        # deadline: owned by the accepting LB's clock; the replica never
        # judges it (it sees no deadline at all) yet the request resolves
        # DEADLINE through the LB's explicit cancel
        hd = client.submit(_req(prompt=range(70, 100), max_new=900,
                                deadline_s=0.1), region="us")
        _drain(client, [hd])
        assert hd.state.value == "deadline"
        assert hd.result.finish_reason == FinishReason.DEADLINE
        # expired-at-submit short-circuits on the client's clock
        he = client.submit(_req(deadline_s=-1.0), region="us")
        assert he.done and he.state.value == "deadline"
        m = plane.metrics()
        assert m["unresolved"] == 0
        assert m["n_processes"] >= 6
    finally:
        host.close()
        plane.shutdown()


def test_kill9_replica_failover():
    """kill -9 a replica with work in flight: heartbeats go stale, the LB
    removes the target and re-dispatches — ZERO requests lost."""
    plane = _mkplane(replicas=1, time_scale=0.1)
    host = plane.host()
    try:
        client = Client(host)
        hs = [client.submit(_req(prompt=range(i, i + 30), max_new=30),
                            region="us") for i in range(6)]
        t0 = time.monotonic()
        while not any(h.events for h in hs) and time.monotonic() - t0 < 10:
            client.poll()
        assert any(h.events for h in hs), "no request started in time"
        plane.kill_replica("us-r0")         # a real SIGKILL on a real pid
        assert _drain(client, hs, 40.0) == ["finished"] * 6
        for h in hs:
            assert len(h.result.output_tokens) == 30
        m = plane.metrics()
        assert m["redispatched"] >= 1, "failover must have re-dispatched"
        assert m["unresolved"] == 0
        us_lb = next(s for s in m["per_process"]
                     if s.get("kind") == "lb" and s["id"] == "us")
        assert any("failover us-r0" in e for e in us_lb["events"])
    finally:
        host.close()
        plane.shutdown()


def test_kill9_lb_failover():
    """kill -9 a region's LB: the client re-homes its unresolved requests
    to a surviving LB (deadline re-owned on the client's clock), the
    orphaned replicas get adopted, and everything still resolves."""
    plane = _mkplane(replicas=1, time_scale=0.05)
    host = plane.host()
    try:
        client = Client(host)
        hs = [client.submit(_req(prompt=range(i, i + 25), max_new=20),
                            region="us") for i in range(5)]
        t0 = time.monotonic()
        while not any(h.events for h in hs) and time.monotonic() - t0 < 10:
            client.poll()
        plane.kill_lb("us")
        plane.adopt("eu", "us")             # controller-style failover
        states = _drain(client, hs, 40.0)
        assert all(s in ("finished", "abort") for s in states)
        assert states.count("finished") >= 4
        assert host.resubmitted, "client must have re-homed requests"
    finally:
        host.close()
        plane.shutdown()


def test_graceful_shutdown_reaps_everything():
    """Drain-based shutdown: every child exits 0 (no SIGKILL escalation),
    and the conftest leak check sees nothing left behind."""
    import multiprocessing as mp
    plane = _mkplane()
    host = plane.host()
    try:
        client = Client(host)
        hs = [client.submit(_req(max_new=4), region=r)
              for r in ("us", "eu")]
        _drain(client, hs)
    finally:
        host.close()
        plane.shutdown()
    for name, p in plane.procs.items():
        assert p.exitcode == 0, f"{name} exited {p.exitcode}"
    assert not mp.active_children()


# ------------------------------------------------------- start-up failures

def test_replica_that_fails_at_start_reports_over_its_pipe():
    """A replica that cannot build its engine says why on its ready pipe
    and exits; the launcher raises with that reason instead of timing
    out (and reaps the child)."""
    from repro.plane import PlaneConfig, ServingPlane
    plane = ServingPlane(PlaneConfig(regions=("us",), replicas=1,
                                     backend="no-such-backend"))
    with pytest.raises(RuntimeError, match="failed at start.*"
                                           "no-such-backend"):
        plane.start()
    plane.shutdown()


def test_jax_replica_refuses_to_serve_on_the_cpu_unasked(monkeypatch):
    """Without JAX_PLATFORMS naming the CPU, a JAX replica that finds no
    accelerator fails at start rather than serving on the CPU."""
    from repro.plane.replica import ReplicaSpec, _build_engine
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(RuntimeError, match="no accelerator"):
        _build_engine(ReplicaSpec(rid="us-r0", region="us", backend="jax",
                                  arch="qwen3-0.6b-reduced"))
