"""The correctness check, on the CPU at a size a test run can hold: the
whole harness (set-up, open loop, drain, reference) runs without the
chip look, over a two-layer model of the qwen3 family.

The control, the float32 reference computed with fp8 products, reads a
widest gap several times the program's, and a limit set between the two
readings passes the program and fails the control. Each fault that a
one-chip serving cell can have, planted under the timed path, turns
`correct` false: a decode step that leaves the KV cache as it was, half
of the batch left out, and a token altered where it is produced.
"""
from __future__ import annotations

import copy
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import cell as cellmod  # noqa: E402
from benchmarks.chip import run as runmod  # noqa: E402

SECONDS = 4.0


def tiny_cell() -> cellmod.Cell:
    here = cellmod.HERE
    config = dict(cellmod.load_json(here / "configs" / "qwen3-0.6b.json"),
                  name="qwen3-tiny", hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, vocab_size=503)
    config["engine"] = {"page_size": 16, "n_pages": 256, "max_batch": 4,
                        "max_seq_len": 256, "prefill_pad": 64,
                        "prefill_chunk": 128}
    mix = dict(cellmod.load_json(here / "traffic" / "sessions.json"),
               templates=2, template_len=64, turns=3,
               user_len={"median": 16, "sigma": 0.5, "min": 4, "max": 48},
               output_len={"median": 12, "sigma": 0.5, "min": 6, "max": 24},
               think_s_mean=0.2, history_s=6.0, ramp_s=1.0,
               est_ttft_s=0.02, est_itl_s=0.005, rate_sessions_s=3.0,
               drain_cap_s=60.0, check_tokens=60, trace_s=1.0)
    return cellmod.Cell(
        name="tiny", chips=1, config=config, traffic=mix,
        deployment=cellmod.load_json(here / "deployments" / "single.json"),
        limits={}, end_to_end=[], per_layer=[])


@pytest.fixture(scope="module")
def env():
    cell = tiny_cell()
    clean = [runmod.run(cell, seed, SECONDS, False, jax.devices()[:1],
                        control=True) for seed in (2**33 + 1, 5, 6)]
    program = max(r["gaps"]["program"] for r in clean)
    control = min(r["gaps"]["control"] for r in clean)
    # the limit as the cell's is set: between the two readings, nearer
    # the program's
    limit = math.sqrt(program * control)
    return cell, clean, {"logit_gap_max": limit}


def test_control_reads_far_above_the_program(env):
    cell, clean, limits = env
    for r in clean:
        assert r["attempted"] > 5 and r["lost"] == 0
        assert sum(n for *_, n in r["sample"]) >= 24
        assert r["gaps"]["control"] >= 3 * r["gaps"]["program"]
        assert runmod.verdict(r, limits)[0]
        control = dict(r, gaps={"program": r["gaps"]["control"]})
        assert not runmod.verdict(control, limits)[0]


def _faulty(env, monkeypatch, fault) -> dict:
    cell, _, limits = env
    from repro.serving import jax_backend, model_runner as mr
    fault(monkeypatch, mr, jax_backend.JaxPagedBackend)
    res = runmod.run(cell, 5, SECONDS, False, jax.devices()[:1])
    return runmod.verdict(res, limits)


def test_a_decode_step_that_leaves_the_cache_unchanged_fails(
        env, monkeypatch):
    def fault(mp, mr, _be):
        real = mr.decode_step

        def stale(params, state, k_pages, v_pages, key, **kw):
            k0, v0 = jnp.copy(k_pages), jnp.copy(v_pages)
            toks, state, _, _ = real(params, state, k_pages, v_pages, key,
                                     **kw)
            return toks, state, k0, v0
        mp.setattr(mr, "decode_step", stale)
    assert not _faulty(env, monkeypatch, fault)[0]


def test_half_of_the_batch_left_out_fails(env, monkeypatch):
    def fault(mp, _mr, be):
        real = be.decode

        def half(self, seqs):
            out = real(self, seqs)
            keep = max(1, len(out) // 2)
            return out[:keep] + [0] * (len(out) - keep)
        mp.setattr(be, "decode", half)
    assert not _faulty(env, monkeypatch, fault)[0]


def test_a_token_altered_where_it_is_produced_fails(env, monkeypatch):
    def fault(mp, _mr, be):
        real = be.decode

        def altered(self, seqs):
            out = real(self, seqs)
            return [(t + 1) % 503 if len(getattr(s, "out", ())) == 3 else t
                    for s, t in zip(seqs, out)]
        mp.setattr(be, "decode", altered)
    assert not _faulty(env, monkeypatch, fault)[0]


def test_the_verdict_needs_every_due_request(env):
    _, clean, limits = env
    lost = dict(copy.deepcopy(clean[0]), lost=1)
    assert not runmod.verdict(lost, limits)[0]
    nan = dict(clean[0], gaps={"program": float("nan")})
    assert not runmod.verdict(nan, limits)[0]


def test_sample_holds_the_longest_finished_request():
    from benchmarks.chip.stats import Record
    recs = [Record(sid=i, turn=0, due=0.0, submitted=0.0, max_new=5 + i,
                   prompt_len=100 * i, tokens=[1] * (5 + i),
                   state="finished") for i in range(8)]
    recs.append(Record(sid=99, turn=0, due=0.0, submitted=0.0, max_new=50,
                       prompt_len=5000, tokens=[1] * 3, state="finished"))
    s = runmod.sample_requests(recs, seed=3, want_tokens=20)
    assert s[0].sid == 7                  # longest among the finished
    assert sum(r.max_new for r in s) >= 20
    assert all(r.finished for r in s)
    assert [r.sid for r in s] == [r.sid for r in
                                  runmod.sample_requests(recs, 3, 20)]
    assert dataclasses.is_dataclass(s[0])
