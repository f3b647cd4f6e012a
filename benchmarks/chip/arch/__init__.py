"""Per-architecture modules, one file each.

A configuration file names its module with the key "arch", a module path
relative to this package as in a relative import: "dense" is
`arch/dense.py`, and a leading dot climbs out of `arch/`, as a test's
fixture under `tests/fixtures/` is named. Everything that depends on the architecture's keys lives in
that module:

  model_config(config)             the program's ModelConfig
  shapes(config)                   {leaf path: shape} of the parameter tree
  scale(path, config)              a weight leaf's init standard deviation,
                                   None for a norm gain
  init_keys(config)                the (key, value) pairs the jitted init
                                   depends on
  decode_row_flops(config, ctx)    model operations of one decoded token
                                   with `ctx` tokens in its cache
  paged_decode_costs(config, ctxs) (operations, bytes) of each
                                   paged-attention kernel call one decode
                                   step makes over rows of these contexts

The rest of the harness reads only the generic keys (name, source, arch,
reference, dtype, engine) and what these functions return, so a new
architecture is a new file here, a reference module and a configuration.
"""
from __future__ import annotations

import importlib


def load(config: dict):
    """The module a configuration names; a configuration that names none
    is an error."""
    if "arch" not in config:
        raise KeyError(f"configuration {config.get('name')!r} names no "
                       f"architecture module (key 'arch')")
    return importlib.import_module("." + config["arch"], __name__)
