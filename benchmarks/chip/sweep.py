#!/usr/bin/env python3
"""Find a cell's knee once, on the chip: the highest session start rate
the deployment sustains.

    python3 benchmarks/chip/sweep.py --workload <name> \
        --seeds 7000000001,7000000002 --rates 0.16,0.18,0.2 --seconds 51

One process; for each seed and rate a fresh deployment, the set-up of a
run, and a window of the cell's own mix at that rate, with no drain and no
reference check. Prints one JSON line per point:

  queued_*     requests in the router's queues and the engines' pending
               queues at the window's start and end;
  in_flight_*  the same plus the running requests: past the knee this
               grows while the running batch still has room;
  e2e, pace    the end-to-end numbers, the TTFT median and the mean gap
               between tokens (what the cell's `est_ttft_s` and
               `est_itl_s` are set from), and `due_tok_s`, the output
               tokens of the requests due in the window per second,
               beside `output_tok_s`, the tokens delivered.

The cell then runs at about 0.8 times the knee.
"""
from __future__ import annotations

import argparse
import gc
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import cell as cellmod  # noqa: E402
from benchmarks.chip import run as runmod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    cell = cellmod.load_cell(args.workload)
    devices = runmod.check_device(cell.chips)
    if devices is None:
        return 1
    runmod.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for rate in (float(r) for r in args.rates.split(",")):
            c = dataclasses.replace(
                cell, traffic=dict(cell.traffic, rate_sessions_s=rate,
                                   check_tokens=0))
            res = runmod.run(c, seed, args.seconds, False, devices,
                             drain_cap_s=0.0)
            gc.collect()
            c0, c1 = res["counters"]["start"], res["counters"]["end"]
            print(json.dumps({"seed": seed, "rate_sessions_s": rate,
                              "queued_start": c0["queued"],
                              "queued_end": c1["queued"],
                              "in_flight_start": c0["in_flight"],
                              "in_flight_end": c1["in_flight"],
                              "attempted": res["attempted"],
                              "compiles_in_window":
                                  res["compiles_in_window"],
                              "e2e": res["e2e"], "pace": res["pace"]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
