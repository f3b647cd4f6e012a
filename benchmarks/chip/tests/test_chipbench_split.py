"""The split tool: its arithmetic on hand-made stamps and trace events,
and its hooks through the whole harness on the CPU at a tiny size."""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import run as runmod  # noqa: E402
from benchmarks.chip import serve, split, trace  # noqa: E402
from benchmarks.chip.tests.test_chipbench_correct import tiny_cell  # noqa: E402

DEV = "/device:TPU:0"


def test_ttft_split_by_consecutive_stamps():
    # due, submitted, arrival, dispatched, admitted, first_token_s, first
    chains = [(0.0, 0.010, 0.010, 0.011, 0.011, 0.041, 0.141),
              (1.0, 1.030, 1.030, 1.033, 1.034, 1.064, 1.104)]
    got = split.ttft_split(chains)
    assert got["n"] == 2 and got["backwards"] == 0
    assert got["submit_late"]["median"] == pytest.approx(20.0)
    assert got["router_queue"]["max"] == pytest.approx(3.0)
    assert got["first_token_hold"]["mean"] == pytest.approx(70.0)
    assert got["ttft"]["median"] == pytest.approx(122.5)
    # with arrival at submission, the term means add up to the TTFT mean
    assert sum(got[k]["mean"] for k in split.TERMS) == pytest.approx(
        got["ttft"]["mean"])


def test_ttft_split_counts_a_chain_that_runs_backwards():
    # admitted before dispatched
    got = split.ttft_split([(0.0, 0.0, 0.0, 0.02, 0.01, 0.05, 0.06)])
    assert got["backwards"] == 1


def _module(name, start, end):
    return dict(plane=DEV, line=trace.MODULES_LINE, name=f"jit_{name}(1)",
                start_ns=start, dur_ns=end - start)


def _op(start, end):
    return dict(plane=DEV, line=trace.OPS_LINE, name="%fusion.1 = f32[] x",
                start_ns=start, dur_ns=end - start)


def _span(name, start, end):
    return dict(plane=trace.HOST_PLANE, line="python", name=name,
                start_ns=start, dur_ns=end - start)


def test_gaps_named_by_the_innermost_span_covering_most():
    ev = [_span("bench.window", 0, 1000),
          _span("Engine.step:0", 0, 1000), _span("engine.step", 0, 1000),
          _module("prefill_pack_step", 0, 100), _op(0, 100),
          _span("backend.sync", 150, 390),
          _module("decode_step", 400, 500), _op(400, 500),
          _span("host.gc", 520, 540),
          _module("decode_step", 600, 1000), _op(600, 1000)]
    got = split.gaps(ev, DEV, 0, 1000, split.GAP_NAMES, n=5)
    assert [g["ms"] for g in got] == pytest.approx([3e-4, 1e-4])
    first, second = got
    assert first["label"] == "backend.sync"
    assert first["at_ms"] == pytest.approx(1e-4)
    assert first["cover"]["backend.sync"] == pytest.approx(0.8)
    assert first["cover"]["engine.step"] == pytest.approx(1.0)
    assert (first["before"], first["after"]) == (["prefill_pack_step"],
                                                 ["decode_step"])
    # gc covers a fifth: the gap goes to the step around it
    assert second["label"] == "engine.step"
    assert second["cover"]["host.gc"] == pytest.approx(0.2)


@pytest.fixture
def hooked(monkeypatch):
    """Hooks installed for one test; the harness as it was afterwards."""
    for obj, name in ((serve.OpenLoop, "_done"), (serve.OpenLoop, "finalize"),
                      (serve, "counters"), (runmod, "read_layers")):
        monkeypatch.setattr(obj, name, getattr(obj, name))
    hooks = split.Hooks()
    hooks.install()
    return hooks


def test_hooks_through_the_harness(hooked):
    res = runmod.run(tiny_cell(), 2**33 + 3, 4.0, False, jax.devices()[:1])
    st = hooked.extra["stamps"]
    assert st["n"] >= res["attempted"] - res["failed"] > 0
    assert st["backwards"] == 0
    assert st["pending_wait"]["median"] >= 0.0
    c = hooked.counters()
    assert c["decode_calls"][1] > c["decode_calls"][0]
    assert 0.0 < c["upload_share"] <= 100.0
    # run.py's own record of the window is untouched by the hooks
    assert set(res["counters"]["end"]) == set(res["counters"]["start"])
    assert "decode_calls" not in res["counters"]["end"]


def test_counters_missing_on_a_program_without_them():
    hooks = split.Hooks()
    hooks.counts = [None, None]
    assert hooks.counters() is None
