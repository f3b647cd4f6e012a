"""Per-request stamps and backend counters on the engine path.

A request entering through `InProcessRouter` is stamped, on one monotonic
clock, at router submit (`arrival_s`), at `Engine.submit`
(`dispatched_s`), just before the engine step that first admits it
(`admitted_s`), at its prefill's host sync (`first_token_s`), and at the
drain that hands the token to the client (`TokenEvent.t`)."""
from __future__ import annotations

import gc

import numpy as np

from repro.frontend import Client, RequestState, RouterHost
from repro.serving import (Engine, EngineConfig, GenRequest, InProcessRouter,
                           SamplingParams)
from repro.serving import spans

ECFG = dict(page_size=8, n_pages=64, max_batch=2, max_seq_len=256,
            prefill_pad=16)


def _req(vocab, n, max_new, seed, **kw):
    rng = np.random.default_rng(seed)
    return GenRequest(
        prompt_tokens=tuple(rng.integers(1, vocab, size=n).tolist()),
        sampling=SamplingParams(max_new_tokens=max_new), **kw)


def test_stamp_chain_in_order_through_router(qwen_reduced, qwen_model_params):
    _, params = qwen_model_params
    router = InProcessRouter()
    router.add_region("us").add_engine(
        "us-e0", Engine(qwen_reduced, params, EngineConfig(**ECFG)))
    client = Client(RouterHost(router))
    # twice the batch: half of them wait in the engine's pending queue
    handles = [client.submit(_req(qwen_reduced.vocab, 10 + 3 * i, 4, i))
               for i in range(4)]
    client.drain()
    waited = 0
    for h in handles:
        r = h.request
        assert h.state is RequestState.FINISHED
        chain = [r.arrival_s, r.dispatched_s, r.admitted_s,
                 r.first_token_s, h.events[0].t]
        assert None not in chain
        assert chain == sorted(chain), chain
        waited += r.admitted_s > r.dispatched_s
    assert waited >= 2


def test_admit_time_is_taken_before_the_prefill(qwen_reduced,
                                                qwen_model_params):
    """`on_admit` gets the time from before `begin_step`, which runs the
    admission's prefill, not a time taken after it."""
    _, params = qwen_model_params
    eng = Engine(qwen_reduced, params, EngineConfig(**ECFG))
    req = _req(qwen_reduced.vocab, 12, 3, 0)
    seen = []
    req.on_admit = lambda r, t: seen.append(t)
    eng.submit(req)
    eng.step()
    assert seen == [req.admitted_s]
    assert req.dispatched_s <= req.admitted_s <= req.first_token_s


def test_preemption_resume_keeps_first_admission(qwen_reduced,
                                                 qwen_model_params):
    _, params = qwen_model_params
    eng = Engine(qwen_reduced, params, EngineConfig(
        page_size=8, n_pages=8, max_batch=4, max_seq_len=256,
        prefill_pad=16, preemption=True))
    low = _req(qwen_reduced.vocab, 16, 16, 1)          # 4 pages of 7
    admits = []
    low.on_admit = lambda r, t: admits.append(t)
    eng.submit(low)
    eng.step()
    first = low.admitted_s
    assert admits == [first]
    eng.submit(_req(qwen_reduced.vocab, 16, 16, 2, priority=1))
    eng.run_until_idle()
    assert eng.core.preemptions == 1
    assert len(admits) == 2 and admits[1] > first      # resumed, re-admitted
    assert low.admitted_s == first


def test_decode_counters(qwen_reduced, qwen_model_params):
    _, params = qwen_model_params
    eng = Engine(qwen_reduced, params, EngineConfig(**ECFG))
    for i in range(2):
        eng.submit(_req(qwen_reduced.vocab, 10 + i, 5, 10 + i))
    eng.run_until_idle()
    be = eng.backend
    # both admitted in one step (prefill gives token 1), then 4 decodes
    # of both rows; the batch state was uploaded once
    assert (be.decode_calls, be.state_uploads) == (4, 1)


def test_gc_span_installed_once(qwen_reduced, qwen_model_params):
    _, params = qwen_model_params
    Engine(qwen_reduced, params, EngineConfig(**ECFG))
    Engine(qwen_reduced, params, EngineConfig(**ECFG))
    assert gc.callbacks.count(spans._on_gc) == 1
    gc.collect(0)
    gc.collect()
    assert spans._gc_open == []                        # every span closed
