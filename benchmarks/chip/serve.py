"""Drive a deployment through the client: build it, warm it, run the open
loop, drain. Everything the program is asked to do enters at
`Client.submit` over `RouterHost(InProcessRouter)`.

The shapes the window will use are compiled during set-up, through the
engine backend's own packing code (`prefill_batch` and `decode` on
placeholder sequences whose every page is the backend's scratch page, the
page it already pads block tables with). Which shapes: decode batches of
every power of two up to `max_batch`, over the block-table buckets that
the traffic's contexts reach; packed prefill rounds of up to
`max_admissions_per_step` segments (the router's SP-P pushing with
`max_inflight_per_probe` hands an engine no more per tick), every token
bucket up to the engine's `prefill_chunk` per segment, and every bucket of
cached pages those segments can attend to.
"""
from __future__ import annotations

import heapq
import time
import types

import jax

from benchmarks.chip.stats import Record
from repro.frontend import Client, RouterHost
from repro.routing import build_routing
from repro.serving import Engine, GenRequest, InProcessRouter, SamplingParams
from repro.serving.bucketing import bucket_tokens, next_pow2


# ------------------------------------------------------------- deployment

def build(cfg, ecfg, deployment: dict, params_per_engine: list, seed: int):
    """(router, engines) for a deployment file; engine i serves
    params_per_engine[i], which also fixes its chip."""
    router = InProcessRouter.from_spec(
        build_routing(deployment["routing"]),
        cfg_overrides=dict(deployment.get("routing_overrides", {})))
    engines, i = [], 0
    for region, n in deployment["regions"].items():
        lb = router.add_region(region)
        for k in range(n):
            eng = Engine(cfg, params_per_engine[i], ecfg, seed=seed % 2**31)
            lb.add_engine(f"{region}-r{k}", eng)
            engines.append(eng)
            i += 1
    return router, engines


def n_engines(deployment: dict) -> int:
    return sum(deployment["regions"].values())


# ------------------------------------------------------------------ shapes

def _fake(n_tokens: int, n_pages: int, scratch: int):
    """A placeholder sequence: every page is the scratch page."""
    req = types.SimpleNamespace(
        sampling=SamplingParams(max_new_tokens=1), first_token_s=None)
    return types.SimpleNamespace(tokens=[0] * n_tokens, pos=n_tokens,
                                 pages=[scratch] * n_pages, req=req)


def _ladder(lo: int, hi: int) -> list:
    """Powers of two from next_pow2(lo) to next_pow2(hi)."""
    out, x = [], next_pow2(max(1, lo))
    while x <= next_pow2(hi):
        out.append(x)
        x *= 2
    return out


def warm_shapes(ecfg, traffic: dict) -> dict:
    """The decode and prefill shape buckets the traffic can reach."""
    ps, pad = ecfg.page_size, ecfg.prefill_pad
    npg_cap = -(-ecfg.max_seq_len // ps)
    # every context holds a template and at least one token each way
    shortest = traffic["template_len"] + traffic["user_len"]["min"] + 1
    decode = sorted({(min(nb, ecfg.max_batch), min(npgb, npg_cap))
                     for nb in _ladder(1, ecfg.max_batch)
                     for npgb in _ladder(-(-shortest // ps), npg_cap)})
    chunk = ecfg.prefill_chunk or ecfg.max_seq_len
    past_max = (ecfg.max_seq_len - 1) // ps
    prefill = []
    for nseg in _ladder(1, traffic["max_admissions_per_step"]):
        for S in sorted({bucket_tokens(n, pad) for n in
                         range(nseg, nseg * chunk + 1, pad)}):
            # any cached prefix: eviction can leave part of a template
            for cp in _ladder(1, nseg * past_max):
                prefill.append((nseg, S, cp))
    return {"decode": decode, "prefill": prefill}


def warm_backend(backend, shapes: dict, ps: int) -> None:
    """Run each shape once through the backend's own packing."""
    scratch = backend._scratch
    for nseg, S, cp in shapes["prefill"]:
        per_seg = S // nseg
        past = cp // nseg
        items = []
        for _ in range(nseg):
            start = past * ps
            end = start + per_seg
            items.append((_fake(end, -(-end // ps), scratch), start, end,
                          True))
        backend.prefill_batch(items)
    for nb, npgb in shapes["decode"]:
        seqs = [_fake(npgb * ps - 1, npgb, scratch) for _ in range(nb)]
        backend.decode(seqs)


# ---------------------------------------------------------------- counters

def counters(router, engines) -> dict:
    """What the program counts, summed over engines."""
    from repro.serving import model_runner as mr
    return {
        "prefill_tokens": sum(e.core.total_prefill_tokens for e in engines),
        "cached_tokens": sum(e.core.total_cached_tokens for e in engines),
        "steps": sum(e.core.steps for e in engines),
        "completions": sum(e.core.completions for e in engines),
        "preemptions": sum(e.core.preemptions for e in engines),
        "rejections": sum(e.core.rejections for e in engines),
        "forwarded_out": sum(lb.forwarded_out for lb in router.lbs.values()),
        "ticks": router.tick,
        "queued": (sum(len(lb.queue) for lb in router.lbs.values())
                   + sum(len(e.pending) for e in engines)),
        # queued, pending and running: grows past the knee even while the
        # running batch still has room and the queue stays empty
        "in_flight": (sum(len(lb.queue) for lb in router.lbs.values())
                      + sum(e.core.outstanding() for e in engines)),
        "compiles": sum(v for v in mr.compile_counts().values() if v > 0),
    }


# ---------------------------------------------------------------- the loop

class OpenLoop:
    """Sessions on the host's clock: each turn is due at its scheduled time
    (a session's first live turn) or at the previous turn's last token plus
    the think time; it is submitted at the first loop pass at or after it
    is due. Time is relative to the start of the measured window."""

    def __init__(self, schedule, router, seconds: float):
        self.schedule = schedule
        self.client = Client(RouterHost(router))
        self.router = router
        self.seconds = seconds
        self.records: list = []
        self.outputs: dict = {s.sid: {} for s in schedule.sessions}
        self.sessions = {s.sid: s for s in schedule.sessions}
        self._heap: list = []
        self._handles: list = []
        self.t0 = None              # monotonic time of the window start

    def now(self) -> float:
        return time.monotonic() - self.t0

    def start(self, t0: float) -> None:
        self.t0 = t0
        for s in self.schedule.sessions:
            heapq.heappush(self._heap, (s.first_due, s.sid, s.live))

    def _submit(self, due: float, sid: int, k: int) -> None:
        s = self.sessions[sid]
        turn = s.turns[k]
        prompt = s.prompt(k, self.schedule.templates, self.outputs[sid])
        req = GenRequest(
            prompt_tokens=prompt, user_id=f"s{sid}", session_key=f"s{sid}",
            # greedy: the correctness check compares served tokens with
            # the reference's best
            sampling=SamplingParams(max_new_tokens=turn.out_len,
                                    temperature=0.0))
        rec = Record(sid=sid, turn=k, due=due, submitted=self.now(),
                     max_new=turn.out_len, prompt_len=len(prompt))
        h = self.client.submit(req, region=s.region)
        h.on_done(lambda res, h=h, rec=rec: self._done(h, rec))
        self.records.append(rec)
        self._handles.append((h, rec))

    def _done(self, h, rec: Record) -> None:
        rec.done_at = self.now()
        rec.state = h.state.value
        rec.tokens = list(h.tokens)
        rec.times = [e.t - self.t0 for e in h.events]
        s = self.sessions[rec.sid]
        if not rec.finished:
            return
        self.outputs[rec.sid][rec.turn] = tuple(rec.tokens)
        k = rec.turn + 1
        if k < len(s.turns):
            due = rec.done_at + s.turns[rec.turn].think_s
            if due < self.seconds:
                heapq.heappush(self._heap, (due, rec.sid, k))

    def submit_due(self) -> int:
        n, now = 0, self.now()
        while self._heap and self._heap[0][0] <= now:
            due, sid, k = heapq.heappop(self._heap)
            self._submit(due, sid, k)
            n += 1
        return n

    def run_until(self, t_end: float, poll) -> None:
        """Submit what falls due and pump the router until `t_end`."""
        while True:
            now = self.now()
            if now >= t_end:
                return
            self.submit_due()
            if self.router.idle():
                nxt = self._heap[0][0] if self._heap else t_end
                time.sleep(max(0.0, min(nxt, t_end) - self.now()))
            else:
                poll()

    def drain(self, cap_s: float, poll) -> bool:
        """After the window: pump until every request due in it is
        terminal, or `cap_s` has passed. True when all are."""
        end = self.now() + cap_s
        while self.now() < end:
            if all(r.state != "queued" or r.due >= self.seconds
                   for r in self.records):
                return True
            if self.router.idle():
                return all(r.state != "queued" for r in self.records
                           if r.due < self.seconds)
            poll()
        return False

    def finalize(self) -> None:
        """Copy what each unfinished handle has so far into its record."""
        for h, rec in self._handles:
            if rec.done_at is None:
                rec.tokens = list(h.tokens)
                rec.times = [e.t - self.t0 for e in h.events]


def serve_warm(router, schedule, cap_s: float) -> int:
    """Prefill every warm prompt (one token out) and wait for all."""
    client = Client(RouterHost(router))
    handles = [client.submit(GenRequest(
        prompt_tokens=p, sampling=SamplingParams(max_new_tokens=1)),
        region=region) for region, p in schedule.warm_prompts()]
    end = time.monotonic() + cap_s
    while client.handles and time.monotonic() < end:
        if not client.poll():
            break
    return sum(1 for h in handles if h.done)


def block_all(engines) -> None:
    jax.block_until_ready([(e.backend.k_pages, e.backend.v_pages)
                           for e in engines])
