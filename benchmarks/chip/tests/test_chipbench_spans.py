"""The program's spans: how they nest in a real trace, and the readers
built on them, checked on synthetic events worked out by hand."""
from __future__ import annotations

import gc
import glob
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import spans, trace  # noqa: E402
from benchmarks.chip.metrics import backend_host_ms  # noqa: E402
from benchmarks.chip.metrics import sched_host_ms  # noqa: E402
from benchmarks.chip.metrics import state_upload_share  # noqa: E402

DEV = "/device:TPU:0"
HOST = trace.HOST_PLANE


def _op(start, end):
    return dict(plane=DEV, line="XLA Ops", name="%fusion.1 = f32[] fusion()",
                start_ns=start, dur_ns=end - start)


def _span(name, start, end):
    return dict(plane=HOST, line="python", name=name, start_ns=start,
                dur_ns=end - start)


SYNTH = [
    _span("bench.window", 0, 1000),
    _span("router.tick", 90, 905),          # encloses the engine's step:
    _span("router.route", 92, 99),          # belongs to no engine
    _span("Engine.step:0", 100, 900),
    _span("engine.step", 110, 890),
    _span("replica.admit", 120, 400),
    _span("backend.prefill", 150, 380),
    _span("backend.sync", 300, 370),
    _span("replica.finish", 420, 800),
    _span("backend.decode", 430, 780),
    _span("backend.upload", 440, 470),
    _span("backend.sync", 600, 770),
    _span("host.gc", 785, 795),
    _span("engine.stream", 810, 880),
    _op(200, 260), _op(320, 360), _op(480, 760),
    # a second step: a decode with no upload
    _span("Engine.step:0", 920, 990),
    _span("engine.step", 925, 985),
    _span("replica.finish", 930, 980),
    _span("backend.decode", 935, 975),
    _span("backend.sync", 940, 970),
    _op(945, 965),
    # after the window: not counted
    _span("Engine.step:0", 1090, 1210),
    _span("engine.step", 1100, 1200),
]


def test_nesting_of_synthetic_spans():
    got = spans.nest(SYNTH, 0, 1000)
    parent = {(n, s): (got[p][0] if p >= 0 else None) for n, s, _, p in got}
    assert parent[("router.route", 92)] == "router.tick"
    assert parent[("engine.step", 110)] == "router.tick"
    assert parent[("backend.sync", 300)] == "backend.prefill"
    assert parent[("backend.upload", 440)] == "backend.decode"
    assert parent[("host.gc", 785)] == "replica.finish"
    assert parent[("engine.stream", 810)] == "engine.step"
    assert parent[("engine.step", 925)] is None
    assert ("engine.step", 1100) not in parent


def test_self_and_chip_idle_time():
    p = spans.reduce(SYNTH, 1, 1)
    assert p["engine_steps"] == 2
    sp = p["spans"]
    assert "router.tick" not in sp and "router.route" not in sp
    # replica.admit [120, 400) less backend.prefill [150, 380): 30 + 20
    assert sp["replica.admit"]["idle_self_s"] == pytest.approx(50e-9)
    # backend.prefill less its sync: [150, 300) + [370, 380), chip busy
    # over [200, 260)
    assert sp["backend.prefill"]["idle_self_s"] == pytest.approx(100e-9)
    # decode less upload and sync: [430, 440) + [470, 600) + [770, 780),
    # busy [480, 600); then [935, 940) + [970, 975)
    assert sp["backend.decode"]["idle_self_s"] == pytest.approx(40e-9)
    assert sp["backend.decode"]["count"] == 2
    assert sp["backend.decode"]["total_s"] == pytest.approx(390e-9)
    assert sp["backend.sync"]["idle_self_s"] == pytest.approx(50e-9)
    assert sp["host.gc"]["idle_self_s"] == pytest.approx(10e-9)
    assert sp["engine.step"]["idle_self_s"] == pytest.approx(60e-9)


def test_readers(monkeypatch):
    monkeypatch.setattr(spans, "program",
                        lambda rec: spans.reduce(SYNTH, 1, 1))
    # admit 50 + finish 20 + 10, over two steps
    assert sched_host_ms.read({}) == pytest.approx(1e3 * 40e-9)
    # prefill 100 + decode 40 + upload 30, over two steps
    assert backend_host_ms.read({}) == pytest.approx(1e3 * 85e-9)
    assert state_upload_share.read({}) == pytest.approx(50.0)


def test_readers_find_nothing_without_program_spans(monkeypatch):
    older = [e for e in SYNTH if e["plane"] == DEV
             or e["name"] in ("bench.window", "Engine.step:0")]
    assert spans.reduce(older, 1, 1) is None
    monkeypatch.setattr(spans, "program",
                        lambda rec: spans.reduce(older, 1, 1))
    for reader in (sched_host_ms, backend_host_ms, state_upload_share):
        assert reader.read({}) is None


def test_program_reads_the_runs_trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path / "none")
    rec = {"trace": {"chips": [{"plane": DEV}]}, "spans": {}}
    assert spans.program(rec) is None


@pytest.fixture(scope="module")
def small_router():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import (Engine, EngineConfig, GenRequest,
                               InProcessRouter, SamplingParams)
    cfg = get_config("qwen3-0.6b").reduced()
    params = build_model(cfg, jnp.float32).init(jax.random.PRNGKey(0))
    router = InProcessRouter()
    eng = Engine(cfg, params, EngineConfig(page_size=8, n_pages=64,
                                           max_batch=4, max_seq_len=256,
                                           prefill_pad=16))
    router.add_region("us").add_engine("us-e0", eng)
    rng = np.random.default_rng(0)

    def submit():
        router.submit("us", GenRequest(
            prompt_tokens=tuple(rng.integers(1, cfg.vocab, 12).tolist()),
            sampling=SamplingParams(max_new_tokens=4)))
    return router, eng, submit


def test_program_spans_nest_in_a_cpu_trace(small_router, tmp_path):
    import jax
    router, eng, submit = small_router
    submit()
    submit()
    router.run_until_idle()             # compiles every shape used below
    step = eng.step

    def harness_step():
        with jax.profiler.TraceAnnotation("Engine.step:0"):
            return step()

    eng.step = harness_step
    submit()
    submit()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(4):
            router.step()
        gc.collect()
    jax.profiler.stop_trace()
    eng.step = step
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    ev = trace.load(path)
    lo, hi = trace.window(ev, "bench.window")
    got = spans.nest(ev, lo, hi)
    parents: dict = {}
    for name, _, _, p in got:
        parents.setdefault(name, set()).add(got[p][0] if p >= 0 else None)
    # the collection asked for above; the collector may also run on its own
    assert None in parents.pop("host.gc")
    assert parents == {
        "router.tick": {None},
        "router.route": {"router.tick"},
        "engine.step": {"router.tick"},
        "replica.admit": {"engine.step"},
        "backend.prefill": {"replica.admit"},
        "replica.finish": {"engine.step"},
        "backend.decode": {"replica.finish"},
        "backend.upload": {"backend.decode"},
        "backend.sync": {"backend.prefill", "backend.decode"},
        "engine.stream": {"engine.step"},
    }
    assert sum(n == "engine.step" for n, *_ in got) == 4
    # every program span of an engine lies inside the harness's step span
    steps = trace.spans(ev, "Engine.step:0")
    for name, s, e, _ in got:
        if not name.startswith(("router.", "host.")):
            assert any(a <= s and e <= b for a, b in steps), name
