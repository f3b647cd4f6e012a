#!/usr/bin/env bash
# CI entry point: tier-1 test suite + smoke benchmark sweep.
#
# The smoke sweep runs every figure benchmark with bounded sim horizons
# (~a minute total), so routing-throughput regressions in the shared
# repro/routing core surface without a full benchmark run.
#
#   bash scripts/ci.sh            # from the repo root
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# everything here runs on the CPU; the chip path is `python chip_smoke.py`
export JAX_PLATFORMS=cpu

echo "=== tier-1: pytest ==="
python -m pytest -x -q

echo "=== examples smoke (front API) ==="
# the examples ARE the front-API contract users copy from: run them (fast
# paths) so a breakage in submit -> stream -> result / cancel / deadline
# fails CI, not users. quickstart covers routing + engine + SP-P;
# serve_multiregion covers the Client/handle lifecycle over the two-layer
# router (6 requests keep it to one closed-loop turn).
python examples/quickstart.py
python examples/serve_multiregion.py --requests 6

echo "=== multi-process plane smoke (sockets + kill -9 drills) ==="
# the same example over REAL processes and TCP (cost backend, JAX-free
# children): streaming/cancel/deadline across process boundaries plus both
# crash drills. A hard timeout bounds a hung plane, and the orphan check
# fails CI if ANY spawned process outlives the run (the plane must reap
# everything even after two SIGKILL drills).
timeout 300 python examples/serve_multiregion.py --procs --requests 6
# [.] keeps the pattern from matching this script's own text in ps output
if pgrep -f "multiprocessing[.]spawn" > /dev/null; then
    echo "FAIL: orphaned plane processes survived the --procs smoke" >&2
    pgrep -af "multiprocessing[.]spawn" >&2
    exit 1
fi

echo "=== partition-and-heal chaos drill ==="
# the partition drill from the fault-model table (README): blackhole one
# region's LB from its peers and the client mid-stream (TCP up, frames
# dropped — silence, not EOF), re-home the parked requests, heal, and
# require the zombie region's late frames to be FENCED. Gates: every
# request resolves exactly once (unresolved == 0 AND duplicates == 0).
timeout 300 python examples/serve_multiregion.py --chaos
if pgrep -f "multiprocessing[.]spawn" > /dev/null; then
    echo "FAIL: orphaned plane processes survived the --chaos drill" >&2
    pgrep -af "multiprocessing[.]spawn" >&2
    exit 1
fi

echo "=== smoke benchmarks ==="
# fresh per-figure outputs land in a scratch dir (the committed
# artifacts/bench-smoke/ stays the baseline); benchmarks.run also writes the
# consolidated BENCH_summary.json at the repo root
python -m benchmarks.run --smoke --out artifacts/bench-smoke-ci

echo "=== bench summary vs committed baseline ==="
python scripts/diff_bench.py BENCH_summary.json \
    artifacts/bench-smoke/BENCH_summary.json

echo "CI OK"
