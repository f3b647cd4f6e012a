"""The sessions generator and the end-to-end arithmetic, on the CPU, and
the harness's refusal to run without a chip."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import cell as cellmod  # noqa: E402
from benchmarks.chip import stats, traffic  # noqa: E402

MIX = dict(cellmod.load_cell("qwen3-0.6b.sessions").traffic,
           rate_sessions_s=1.0, arrival_share={"us": 0.75, "eu": 0.25})
SECONDS, VOCAB, MAX_LEN = 30.0, 151936, 4096


def sched(seed, mix=MIX, seconds=SECONDS):
    return traffic.build_schedule(dict(mix), seed=seed, seconds=seconds,
                                  vocab=VOCAB, max_seq_len=MAX_LEN)


def _shape(s):
    return [(s.sid, s.region, s.template, s.live, s.first_due,
             [(t.user, t.out_len, t.think_s) for t in s.turns], s.history)
            for s in s.sessions]


def test_schedule_is_deterministic_per_seed():
    assert _shape(sched(2**33 + 5)) == _shape(sched(2**33 + 5))
    assert sched(7).templates == sched(7).templates
    assert _shape(sched(7)) != _shape(sched(8))


def test_every_seed_gets_the_same_arrivals_and_sizes():
    """The seed draws templates, regions and tokens; the work is the
    mix's own."""
    def work(s):
        return [(x.sid, x.live, x.first_due,
                 [(len(t.user), t.out_len, t.think_s) for t in x.turns])
                for x in s.sessions]

    a, b = sched(1), sched(2)
    assert work(a) == work(b)
    assert [x.turns[0].user for x in a.sessions] != \
        [x.turns[0].user for x in b.sessions]


def test_length_clamps_and_context_limit_hold():
    s = sched(3)
    u, o = MIX["user_len"], MIX["output_len"]
    n = 0
    for x in s.sessions:
        total = MIX["template_len"]
        for t in x.turns:
            assert u["min"] <= len(t.user) <= u["max"]
            assert o["min"] <= t.out_len <= o["max"]
            assert t.think_s >= 0
            total += len(t.user) + t.out_len
            n += 1
        assert total <= MAX_LEN
        assert all(len(h) == x.turns[j].out_len
                   for j, h in enumerate(x.history))
    assert n > 50
    assert {x.region for x in s.sessions} == {"us", "eu"}


def test_each_prompt_extends_the_previous_prompt_and_its_outputs():
    s = sched(4)
    x = next(x for x in s.sessions if len(x.turns) - x.live >= 3)
    outs = {}
    prev = None
    for k in range(x.live, len(x.turns)):
        p = x.prompt(k, s.templates, outs)
        assert p[:len(s.templates[x.template])] == s.templates[x.template]
        if prev is not None:
            assert p[:len(prev) + len(outs[k - 1])] == prev + outs[k - 1]
            assert p[len(prev) + len(outs[k - 1]):] == x.turns[k].user
        outs[k] = tuple(range(100 + k, 100 + k + x.turns[k].out_len))
        prev = p
    if x.live:
        warm = dict((p, r) for r, p in s.warm_prompts())
        assert x.prompt(x.live, s.templates, outs)[
            :-len(x.turns[x.live].user)] in warm


def test_sessions_running_before_the_ramp_start_with_history():
    s = sched(5)
    assert any(x.live > 0 for x in s.sessions)
    assert all(x.first_due < SECONDS for x in s.sessions)
    # some are mid-turn when the ramp starts: their turn is due already
    running = [x for x in s.sessions if x.first_due < -s.ramp_s]
    assert running
    for x in running:
        end = x.first_due + MIX["est_ttft_s"] \
            + x.turns[x.live].out_len * MIX["est_itl_s"]
        assert end >= -s.ramp_s


def test_latencies_are_timed_from_the_due_time():
    r = stats.Record(sid=0, turn=0, due=1.0, submitted=1.2, max_new=3,
                     times=[1.5, 1.55, 1.65], tokens=[1, 2, 3],
                     state="finished")
    assert stats.ttfts_ms([r], horizon=9.0) == pytest.approx([500.0])
    assert stats.gaps_ms([r]) == pytest.approx([50.0, 100.0])
    # a request with no first token by the end of the drain waited at
    # least that long
    never = stats.Record(sid=1, turn=0, due=2.0, submitted=2.0, max_new=3)
    assert stats.ttfts_ms([never], horizon=9.0) == pytest.approx([7000.0])


def test_percentiles_pool_every_request():
    recs = [stats.Record(sid=i, turn=0, due=0.0, submitted=0.0, max_new=2,
                         times=[0.1 * (i + 1), 0.1 * (i + 1) + g],
                         tokens=[1, 2], state="finished")
            for i, g in enumerate([0.01, 0.02, 0.03, 0.04, 0.9])]
    e2e = stats.end_to_end(recs, seconds=10.0, horizon=20.0)
    # five gaps pooled: the 95th percentile is the one long gap, which no
    # per-request median would show
    assert e2e["itl_p95_ms"] == pytest.approx(900.0)
    assert e2e["ttft_p50_ms"] == pytest.approx(300.0)
    assert e2e["output_tok_s"] == pytest.approx(1.0)
    late = stats.Record(sid=9, turn=0, due=10.5, submitted=10.5, max_new=1,
                        times=[10.6], tokens=[1], state="finished")
    assert stats.in_window(recs + [late], 10.0) == recs


def test_run_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/chip/run.py"),
         "--workload", "qwen3-0.6b.sessions", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
