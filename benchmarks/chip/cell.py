"""A benchmark cell, assembled from data files found by name.

`BENCHMARK.json` names each cell's configuration and traffic mix. The rest
lives in files of their own under this directory, so a later change adds a
cell, a configuration, an architecture, a deployment or a mix by adding
files:

  configs/<config>.json       published sizes, dtype, engine settings,
                              and the architecture module ("arch")
  arch/<arch>.py              what depends on the architecture's keys
  <reference>.py              its plain reference ("reference")
  traffic/<traffic>.json      the mix's parameters (one general generator)
  cells/<workload>.json       the cell's own: deployment name and fixed rate
  deployments/<name>.json     regions, replicas per region, routing
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from benchmarks.chip import arch

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<mix>.json overlaid by the cell's file
    deployment: dict      # deployments/<name>.json
    limits: dict          # the cell's correctness limits
    end_to_end: list      # BENCHMARK.json end_to_end entries of this cell
    per_layer: list       # BENCHMARK.json per_layer entries of this cell


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {known}")
    config = load_json(HERE / "configs" / f"{entry['config']}.json")
    arch.load(config)
    own = load_json(HERE / "cells" / f"{name}.json")
    traffic = dict(load_json(HERE / "traffic" / f"{entry['traffic']}.json"))
    traffic.update(own.get("traffic", {}))
    deployment = load_json(HERE / "deployments" / f"{own['deployment']}.json")
    return Cell(
        name=name, chips=int(entry["chips"]), config=config,
        traffic=traffic, deployment=deployment, limits=own["limits"],
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def model_config(config: dict):
    """The program's ModelConfig, from the configuration's own
    architecture module."""
    return arch.load(config).model_config(config)


def engine_config(config: dict):
    from repro.serving import EngineConfig
    return EngineConfig(**config["engine"])

