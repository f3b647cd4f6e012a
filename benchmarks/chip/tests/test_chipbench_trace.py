"""The trace reduction and the peaks table.

`trace_v5e.json` beside this file is a slice of a real profiler trace of
the `qwen3-0.6b` engine on one TPU v5 lite (the events `trace.load`
keeps: device ops and program executions, and the benchmark's host
spans). The reduction's numbers on it are worked out by hand below from
the same events. A trace written here on the CPU checks the loader.
"""
from __future__ import annotations

import glob
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import arith, trace  # noqa: E402

HERE = Path(__file__).resolve().parent
DEV = "/device:TPU:0"


def _ev(line, name, start, dur, plane=DEV, **kw):
    return dict(plane=plane, line=line, name=name, start_ns=start,
                dur_ns=dur, **kw)


KERNEL = '%closed_call.8 = bf16[8] custom-call(), custom_call_target="tpu_custom_call"'
SYNTH = [
    _ev("XLA Modules", "jit_decode_step(11)", 100, 50),
    _ev("XLA Ops", "%while.1 = (s32[]) while(%t)", 100, 45),
    _ev("XLA Ops", "%fusion.1 = bf16[8] fusion(%a)", 100, 20),
    _ev("XLA Ops", KERNEL, 120, 15),
    _ev("XLA Ops", "%copy.2 = bf16[8] copy(%b)", 135, 10),
    _ev("XLA Modules", "jit_prefill_pack_step(12)", 200, 80),
    _ev("XLA Ops", "%fusion.9 = bf16[8] fusion(%c)", 200, 80),
    _ev("XLA Modules", "jit_decode_step(11)", 300, 40),
    _ev("XLA Ops", KERNEL, 310, 30),
    _ev("python", "bench.window", 50, 400, plane=trace.HOST_PLANE),
    _ev("python", "Engine.step:0", 90, 200, plane=trace.HOST_PLANE),
    _ev("python", "Engine.step:0", 295, 100, plane=trace.HOST_PLANE),
]


def test_busy_union_and_idle_share():
    merged = trace.busy(SYNTH, DEV)
    assert merged == [[100, 145], [200, 280], [310, 340]]
    lo, hi = trace.window(SYNTH, "bench.window")
    busy = trace.covered(merged, lo, hi)
    assert busy == 45 + 80 + 30
    assert 1 - busy / (hi - lo) == pytest.approx(1 - 155 / 400)


def test_per_program_and_per_kernel_time():
    progs = trace.programs(SYNTH, DEV)
    assert progs["decode_step"] == {"time_s": pytest.approx(90e-9),
                                    "calls": 2}
    assert progs["prefill_pack_step"]["calls"] == 1
    t, n = trace.kernels(SYNTH, DEV)["decode_step"]["closed_call"]
    assert n == 2 and t == pytest.approx(45e-9)
    assert "prefill_pack_step" not in trace.kernels(SYNTH, DEV)


@pytest.mark.parametrize("op, name", [
    (KERNEL, "closed_call"),
    ('%paged_decode.3.1 = bf16[8] custom-call(), '
     'custom_call_target="tpu_custom_call"', "paged_decode"),
    ('  ROOT %moe_gmm = bf16[8] custom-call(), '
     'custom_call_target="tpu_custom_call"', "moe_gmm"),
    ("%fusion.1 = bf16[8] fusion(%a)", None),
    ('%sort.2 = s32[8] custom-call(), custom_call_target="TopK"', None),
])
def test_kernel_names(op, name):
    assert trace.kernel_name(op) == name


def test_a_second_kernel_in_the_decode_step_keeps_its_own_entry():
    from benchmarks.chip.metrics import paged_decode_roofline
    other = ('%moe_gmm.2 = bf16[8] custom-call(), '
             'custom_call_target="tpu_custom_call"')
    two = SYNTH + [_ev("XLA Ops", other, 146, 3),
                   _ev("XLA Ops", other, 300, 8)]
    one_k, two_k = trace.kernels(SYNTH, DEV), trace.kernels(two, DEV)
    assert two_k["decode_step"]["closed_call"] == \
        one_k["decode_step"]["closed_call"]
    assert two_k["decode_step"]["moe_gmm"] == [pytest.approx(11e-9), 2]
    cfg = {"arch": "dense", "hidden_size": 1024, "intermediate_size": 3072,
           "num_hidden_layers": 1, "num_attention_heads": 16,
           "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936}

    def rec(kernels):
        return {"config": cfg, "peak": arith.peaks("TPU v5 lite"),
                "decode": [(0, 1, [100, 300]), (1, 2, [101])],
                "trace": {"chips": [{"kernels": kernels}]}}
    got = paged_decode_roofline.read(rec(two_k))
    assert got is not None and got == paged_decode_roofline.read(rec(one_k))
    # the roofline of one layer's call per step, over the kernel's 45 ns
    least = sum(arith.roofline_s(*arith.paged_decode_cost(c, 16, 8, 128),
                                 arith.peaks("TPU v5 lite"))
                for c in ([100, 300], [101]))
    assert got == pytest.approx(100 * least / 45e-9)
    # a kernel given its own name is read under it, whatever else runs
    named = {"decode_step": {"paged_decode": [45e-9, 2],
                             "closed_call": [9e-9, 7]}}
    assert paged_decode_roofline.read(rec(named)) == pytest.approx(got)


def test_host_spans_not_covered_by_the_device():
    merged = trace.busy(SYNTH, DEV)
    steps = trace.spans(SYNTH, "Engine.step:0")
    host = sum((e - s) - trace.covered(merged, s, e) for s, e in steps)
    # [90, 290): busy 45 + 80; [295, 395): busy 30
    assert host == (200 - 125) + (100 - 30)


def test_breakdown_names_ops_and_gaps():
    lo, hi = trace.window(SYNTH, "bench.window")
    top = trace.top_ops(SYNTH, DEV, lo, hi, n=2)
    # the while loop's own span is not a leaf: its body's ops count
    assert top == [["prefill_pack_step/%fusion.9", pytest.approx(80e-9)],
                   ["decode_step/%closed_call.8 tpu_custom_call",
                    pytest.approx(45e-9)]]
    gaps = trace.idle_gaps(SYNTH, DEV, lo, hi, ["Engine.step:0"], n=3)
    assert [round(g * 1e9) for _, g in gaps] == [110, 55, 50]
    assert gaps[0][0] == "none" and gaps[1][0] == "Engine.step:0"


def test_program_names():
    assert trace.program_name("jit_decode_step(123)") == "decode_step"
    assert trace.program_name("jit_prefill_pack_step.7") == \
        "prefill_pack_step"


def test_recorded_v5e_trace():
    rec = json.loads((HERE / "trace_v5e.json").read_text())
    ev, want = rec["events"], rec["expect"]
    planes = trace.device_planes(ev)
    assert planes == [DEV]
    lo, hi = trace.window(ev, "bench.window")
    merged = trace.busy(ev, DEV)
    assert trace.covered(merged, lo, hi) == want["busy_ns"]
    progs = trace.programs(ev, DEV)
    assert progs["decode_step"]["calls"] == want["decode_calls"]
    t, n = trace.kernels(ev, DEV)["decode_step"][want["kernel"]]
    assert n == want["kernel_calls"]
    assert t * 1e9 == pytest.approx(want["kernel_ns"])


def test_loader_reads_host_spans_from_an_xplane(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("Engine.step:0"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    ev = trace.load(path)
    lo, hi = trace.window(ev, "bench.window")
    (s, e), = trace.spans(ev, "Engine.step:0")
    assert lo <= s < e <= hi


def test_peaks_by_device_kind():
    p = arith.peaks("TPU v5 lite")
    assert p["bf16_flop_s"] == 197e12 and p["hbm_bytes_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no peaks"):
        arith.peaks("TPU v9 imaginary")


def test_roofline_arithmetic():
    # qwen3-0.6b's attention widths: 16 heads, 8 KV heads of 128
    flops, nbytes = arith.paged_decode_cost([100, 300], 16, 8, 128)
    assert flops == 4.0 * 400 * 16 * 128
    assert nbytes == (2 * 400 * 8 * 128 + 2 * 2 * 16 * 128) * 2
    peak = arith.peaks("TPU v5 lite")
    assert arith.roofline_s(flops, nbytes, peak) == nbytes / 819e9
