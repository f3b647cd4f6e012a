#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 101,102,... --seconds 15

In one process, for each seed: a short window of the cell's own traffic
and load, then the float32 reference over the same sample of served
requests that a run checks. Prints one JSON line per seed with the
program's widest gap and the fp8 control's widest gap (the gap of the
token that the fp8 pass puts first, at each position of the same
prompts and served tokens). The limit goes between the program's largest
reading and the control's smallest.
"""
from __future__ import annotations

import argparse
import gc
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
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    cell = cellmod.load_cell(args.workload)
    devices = runmod.check_device(cell.chips)
    if devices is None:
        return 1
    runmod.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = runmod.run(cell, seed, args.seconds, False, devices,
                         control=True)
        gc.collect()
        print(json.dumps({"seed": seed, "gaps": res["gaps"],
                          "sample": res["sample"], "lost": res["lost"],
                          "attempted": res["attempted"],
                          "reference_s": res["reference_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
