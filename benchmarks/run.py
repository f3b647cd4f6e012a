"""Benchmark harness: one module per paper figure + the kernel sweep.
Runs everything, prints per-figure results, writes artifacts/bench/*.json
plus a consolidated BENCH_summary.json at the repo root (throughput / TTFT
/ hit-rate per figure) that scripts/ci.sh diffs against the committed
baseline (artifacts/bench-smoke/BENCH_summary.json) so the perf trajectory
is tracked across PRs.

  PYTHONPATH=src python -m benchmarks.run [--only fig9] [--smoke]

--smoke bounds the simulated horizons so the whole sweep finishes in about
a minute — enough signal to catch routing-throughput regressions in CI
(scripts/ci.sh) without the full-length figures.
"""
from __future__ import annotations

import argparse
import json
import os
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# deterministic sim metrics worth tracking across PRs (wall-clock metrics
# like the kernel sweep's *_us timings are deliberately NOT matched)
SUMMARY_KEYS = frozenset({
    "tok_s", "req_s", "ttft_p50", "ttft_p90", "e2e_p50", "hit_rate",
    "throughput_tok_s", "skylb_tok_s", "local_tok_s", "gap_pct",
    "within_user", "cross_user_same_region", "cross_region",
    "saving_vs_region_local", "forwards", "rejected",
    # fig11 elastic-provisioning gate: measured dollars + SLO + drops
    "cost_usd_per_day", "slo_attainment", "unresolved",
    "global_vs_per_region_saving",
    # serving hot-path gate: compile-count boundedness + deterministic
    # step/token counts (scheduling must not drift); wall-clock-derived
    # values (steps_per_s, tok_s, speedup, meets_1_3x) stay ungated like
    # the kernel timings
    "decode_programs", "decode_program_bound", "decode_shapes_exact",
    "bounded_ok", "steps", "tokens",
    # hierarchical-KV gate (fig6 host_tier sweep + kv_transfer sim):
    # combined-vs-device hit rates, pages moved across regions, and the
    # bytes-vs-recompute decision count are pure functions of the
    # deterministic traces
    "host_hit_rate", "pulled_pages", "pull_vs_push_decisions",
    # speculative decoding gate: emitted tokens per seq per fused dispatch,
    # the synthetic-coin acceptance rate, drafter==target byte-identity,
    # and the kernel sweep's interpret-vs-oracle paged_verify agreement —
    # all deterministic (threefry PRNG, fixed seeds)
    "spec_tokens_per_dispatch", "acceptance_rate", "exact_match_ok",
    "verify_ok",
    # multi-process plane gate (serving.multiprocess): the kill -9 drill
    # must lose zero requests — both are deterministic 0/1 outcomes
    # (`unresolved` is already matched above); wall-clock tok/s stays out
    "drill_ok",
    # partition-tolerance gate (serving.multiprocess): the blackhole-and-
    # heal drill must re-home, fence the zombie region's frames, and
    # resolve every request exactly once — 0/1 outcome plus the
    # duplicate-terminal count, which must stay 0
    "partition_drill_ok", "duplicate_results",
    # fig12 multi-tenant fairness gate: per-tenant p90 TTFT spread
    # (max/min), deadline-aware admission sheds, and SLO attainment
    # (already matched above) are pure functions of the deterministic
    # tenant streams; the >=2x spread-improvement and goodput gates raise
    # inside the benchmark itself
    "ttft_p90_spread", "shed", "spread_improvement",
})


def _flatten(node, prefix: str, out: dict) -> None:
    if isinstance(node, dict):
        for k, v in node.items():
            _flatten(v, f"{prefix}.{k}" if prefix else str(k), out)
    elif isinstance(node, list):
        for i, v in enumerate(node):
            _flatten(v, f"{prefix}[{i}]", out)
    elif isinstance(node, (int, float)) and not isinstance(node, bool):
        # "a.b.tok_s" and "a.b.tok_s[1]" both key on "tok_s"
        key = prefix.rsplit(".", 1)[-1].split("[", 1)[0]
        if key in SUMMARY_KEYS:
            out[prefix] = node


def write_summary(results: dict, path: str) -> dict:
    """Consolidate per-figure results into {figure: {metric.path: value}}."""
    summary = {}
    for name, res in sorted(results.items()):
        flat: dict = {}
        _flatten(res, "", flat)
        if flat:
            summary[name] = flat
    with open(path, "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True)
    return summary


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="artifacts/bench")
    ap.add_argument("--smoke", action="store_true",
                    help="bounded sim horizons (fast CI regression check)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (beyond_steal, fig3_aggregation, fig5_prefix,
                            fig6_hitrate, fig8_macro, fig9_pushing,
                            fig10_diurnal, fig11_provision, fig12_fairness,
                            kernels_bench, serving_bench)
    suites = {
        "fig3": fig3_aggregation.main,
        "fig5": fig5_prefix.main,
        "fig6": fig6_hitrate.main,
        "fig8": fig8_macro.main,
        "fig9": fig9_pushing.main,
        "fig10": fig10_diurnal.main,
        "fig11": fig11_provision.main,
        "fig12": fig12_fairness.main,
        "kernels": kernels_bench.main,
        "serving": serving_bench.main,
        "steal": beyond_steal.main,
    }
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    results: dict = {}
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        print(f"===== {name} =====", flush=True)
        try:
            result = fn(smoke=args.smoke)
            results[name] = result
            with open(os.path.join(args.out, f"{name}.json"), "w") as f:
                json.dump(result, f, indent=1, default=str)
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"[{name}] FAILED: {e}")
            failures += 1
        print(f"[{name}] {time.time() - t0:.1f}s", flush=True)
    summary_path = os.path.join(REPO_ROOT, "BENCH_summary.json")
    if args.only or failures:
        # partial or failed runs must not clobber the full consolidated
        # summary (scripts/ci.sh diffs it figure-by-figure; a baseline
        # missing a figure loses that figure's CI coverage silently) —
        # and a STALE root summary must not validate against the baseline
        # as if it were fresh
        if os.path.exists(summary_path):
            os.remove(summary_path)
        print(f"benchmarks done; {failures} failures (summary not written)")
    else:
        # one copy beside the per-figure jsons (so regenerating the
        # committed artifacts/bench-smoke baseline needs no hand-copy) and
        # one at the repo root (what scripts/ci.sh diffs)
        write_summary(results, os.path.join(args.out, "BENCH_summary.json"))
        write_summary(results, summary_path)
        print(f"benchmarks done; {failures} failures; "
              f"summary -> {summary_path}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
