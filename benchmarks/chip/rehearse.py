#!/usr/bin/env python3
"""Compile a configuration's step programs for a described v5e chip, with
no chip attached, and print what each needs of the chip's memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py \
        --config deepseek-7b

Compiles the largest decode bucket (max_batch rows over max_seq_len) and
the largest packed-prefill round the traffic can reach (the harness's
warm set), at the configuration's widths and pool (the parameter leaves
from its architecture module), and prints `memory_analysis()` of each
beside the weights and the pools, and the Pallas kernels each program
holds, under the names the trace reduction gives them. A compile that
passes is not a chip run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="sessions")
    ap.add_argument("--n-pages", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.chip import arch, cell as cellmod, serve, trace, weights
    from repro.kernels import ops
    from repro.serving import model_runner as mr

    # compile the chip's path: the Pallas kernels, not the CPU oracles
    ops._on_tpu = lambda: True

    jax.config.update("jax_enable_compilation_cache", False)
    config = cellmod.load_json(cellmod.HERE / "configs"
                               / f"{args.config}.json")
    if args.n_pages:
        config["engine"] = dict(config["engine"], n_pages=args.n_pages)
    mix = cellmod.load_json(cellmod.HERE / "traffic" / f"{args.traffic}.json")
    cfg, ecfg = cellmod.model_config(config), cellmod.engine_config(config)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    dt = jnp.dtype(config["dtype"])
    params = {k: spec(v, dt)
              for k, v in arch.load(config).shapes(config).items()}
    params = weights._nest(params)
    kp, vp = (spec(s.shape, s.dtype) for s in
              mr.kv_pool_spec(cfg, ecfg.n_pages, ecfg.page_size, dt))
    nbytes = {
        "weights": sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params)),
        "pools": 2 * kp.size * kp.dtype.itemsize,
    }
    key = spec((2,), jnp.uint32)
    i32, f32 = jnp.int32, jnp.float32
    bcap = ecfg.max_batch
    npg = -(-ecfg.max_seq_len // ecfg.page_size)
    state = {"bt": spec((bcap, npg), i32), "lens": spec((bcap,), i32),
             "toks": spec((bcap,), i32), "temps": spec((bcap,), f32),
             "top_ks": spec((bcap,), i32), "seeds": spec((bcap,), i32)}
    out = {"config": args.config, "n_pages": ecfg.n_pages, "bytes": nbytes}

    def report(name, compiled):
        m = compiled.memory_analysis()
        out[name] = {k: getattr(m, k) for k in
                     ("argument_size_in_bytes", "output_size_in_bytes",
                      "alias_size_in_bytes", "temp_size_in_bytes")}
        out[name]["kernels"] = sorted(
            {trace.kernel_name(op) for op in compiled.as_text().splitlines()}
            - {None})
        print(json.dumps({name: out[name]}), flush=True)

    nb, npgb = serve.warm_shapes(ecfg, mix)["decode"][-1]
    c = mr.decode_step.lower(params, state, kp, vp, key, cfg=cfg,
                             page_size=ecfg.page_size, nb=nb,
                             npgb=npgb).compile()
    report(f"decode_step[{nb},{npgb}]", c)

    nseg, S, cp = max(serve.warm_shapes(ecfg, mix)["prefill"],
                      key=lambda s: (s[1] * (s[2] * ecfg.page_size + s[1])))
    ns = serve.next_pow2(nseg)
    c = mr.prefill_pack_step.lower(
        params, spec((S,), i32), spec((S,), i32), spec((S,), i32),
        spec((S,), i32), spec((S,), i32), kp, vp, spec((cp,), i32),
        spec((ns,), i32), spec((ns,), i32), spec((ns,), i32),
        spec((ns,), f32), spec((ns,), i32), spec((ns,), i32),
        spec((ns,), i32), key, cfg=cfg, page_size=ecfg.page_size).compile()
    report(f"prefill_pack_step[{nseg},{S},{cp}]", c)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
