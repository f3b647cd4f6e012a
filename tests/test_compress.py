"""Compressed collectives: int8 psum with error feedback, hierarchical
reduction. Multi-device behaviour runs in a SUBPROCESS with 8 host devices
(XLA device count locks at first jax init, so it can't run in-process)."""
from __future__ import annotations

import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp


def test_compressed_psum_single_device_close():
    """axis size 1: compressed psum == identity up to int8 quantization."""
    from jax.sharding import Mesh
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.training.compress import compressed_psum, init_error_state

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(8, 8)),
                          jnp.float32)}
    e = init_error_state(g)

    def f(g, e):
        return compressed_psum(g, e, "data")

    out, new_e = shard_map(f, mesh=mesh,
                           in_specs=(P(), P()), out_specs=(P(), P()))(g, e)
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=scale)


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.training.compress import compressed_psum, init_error_state
    from repro.distributed.collectives import (hierarchical_psum,
                                               compressed_hierarchical_psum,
                                               shard_error_state, psum_mean)

    rng = np.random.default_rng(0)
    G = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)  # row per device

    # ---- compressed_psum mean over 8 devices vs exact mean
    mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
    e0 = jnp.zeros((1, 16), jnp.float32)

    def f(g, e):
        m, ne = compressed_psum(g, e, "data")
        return m, ne
    mean, _ = shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                        out_specs=(P("data"), P("data")))(G, jnp.zeros_like(G))
    want = np.tile(np.asarray(G).mean(0, keepdims=True), (8, 1))
    got = np.asarray(mean)
    scale = np.abs(np.asarray(G)).max() / 127.0
    assert np.abs(got - want).max() <= scale, (got - want)
    print("compressed_psum ok", np.abs(got - want).max())

    # ---- error feedback: repeated compression of the SAME grads converges
    e = jnp.zeros_like(G)
    acc = np.zeros((8, 16))
    for step in range(16):
        m, e = shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                         out_specs=(P("data"), P("data")))(G, e)
        acc += np.asarray(m)
    avg = acc / 16
    assert np.abs(avg - want).max() <= 0.25 * scale, np.abs(avg - want).max()
    print("error feedback ok", np.abs(avg - want).max())

    # ---- hierarchical psum on a (pod, data) mesh == flat psum
    mesh2 = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("pod", "data"))
    X = jnp.asarray(rng.normal(size=(8, 5, 3)), jnp.float32)

    def h(x):
        return hierarchical_psum({"x": x[0]}, inner_axis="data",
                                 outer_axis="pod")["x"][None]
    got2 = shard_map(h, mesh=mesh2, in_specs=(P(("pod", "data")),),
                     out_specs=P(("pod", "data")))(X)
    want2 = np.asarray(X).sum(0)
    assert np.allclose(np.asarray(got2)[0], want2, atol=1e-4), "hier"
    print("hierarchical ok")

    # ---- compressed hierarchical: pod hop int8 => close to exact sum
    def ch(x, e):
        s, ne = compressed_hierarchical_psum({"x": x[0]}, {"x": e[0]},
                                             inner_axis="data",
                                             outer_axis="pod")
        return s["x"][None], ne["x"][None]
    E = jnp.zeros((8, (5 * 3 + 3) // 4 * 1 + 0,), jnp.float32)
    # shard error state: chunk = ceil(15/4)=4 padded -> 16/4 = 4
    E = jnp.zeros((8, 4), jnp.float32)
    got3, _ = shard_map(ch, mesh=mesh2,
                        in_specs=(P(("pod", "data")), P(("pod", "data"))),
                        out_specs=(P(("pod", "data")), P(("pod", "data"))))(X, E)
    err = np.abs(np.asarray(got3)[0] - want2).max()
    tol = np.abs(np.asarray(X)).max() * 2 / 127 * 2 + 1e-3
    assert err <= tol, (err, tol)
    print("compressed hierarchical ok", err)
""")


def test_multi_device_collectives_subprocess():
    import os
    env = dict(os.environ)
    env.update({"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    env.pop("XLA_FLAGS", None)      # the subprocess sets its own
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       capture_output=True, text=True, timeout=360, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "compressed_psum ok" in r.stdout
    assert "error feedback ok" in r.stdout
    assert "hierarchical ok" in r.stdout
    assert "compressed hierarchical ok" in r.stdout
