"""Compile the serving path for a described TPU v5e chip, with no chip.

The TPU compiler is installed beside the CPU backend, so the kernels and
the engine's step programs can be lowered and compiled for one chip of a
`v5e:2x2` topology that is described, not attached. That catches what the
Pallas interpreter cannot: Mosaic refusals (tiling, VMEM), programs that do
not fit HBM, and a kernel silently missing from a step. Nothing runs, so
nothing here says anything about results or times.

The topology is described only inside the module-scoped fixtures below:
one process at a time may load the TPU library, so doing it at import (or
in a `skipif` / `parametrize` / conftest hook) would break multi-worker
collection.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.page_copy import page_gather, page_scatter
from repro.kernels.paged_decode import paged_decode
from repro.kernels.paged_verify import paged_verify

# qwen3-0.6b attention widths, a 16-token page, bf16 (its published dtype)
H, K, HD, PAGE = 16, 8, 128, 16
B, NPG, Q = 8, 32, 3
L, P_POOL = 28, 4096
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A chip-targeted compile can be written to the persistent cache but
    not read back without a chip: keep it out for these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """`kernels.ops` picks Pallas only when the default backend is a TPU;
    the described chip is not the default backend, so steer it here."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, s):
    pool = s((P_POOL, PAGE, K, HD), BF16)
    table, lens = s((B, NPG), I32), s((B,), I32)
    if name == "paged_decode":
        return paged_decode, (s((B, H, HD), BF16), pool, pool, table, lens)
    if name == "paged_verify":
        return paged_verify, (s((B, Q, H, HD), BF16), pool, pool, table,
                              lens)
    pools = s((L, P_POOL, PAGE, K, HD), BF16)
    ids = s((B,), I32)
    if name == "page_gather":
        return page_gather, (pools, pools, ids)
    stack = s((B, L, PAGE, K, HD), BF16)
    return page_scatter, (pools, pools, stack, stack, ids)


@pytest.mark.parametrize("name", ["paged_decode", "paged_verify",
                                  "page_gather", "page_scatter"])
def test_serving_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(
        name, lambda shape, dt: _spec(shape, dt, one_chip))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# (H, K, pool pages) of qwen3-0.6b and deepseek-llm-7b, as the cells run them
WIDTHS = {"qwen3": (16, 8, 4096), "deepseek": (32, 32, 2048)}


@pytest.mark.parametrize("nb,npgb", [(1, 1), (8, 32), (32, 256)])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_paged_decode_compiles_at_decode_buckets(one_chip, width, nb, npgb):
    """The kernel at both cells' widths and the decode buckets' extremes:
    one page of one row, and 32 rows of 256 pages."""
    h, k, pages = WIDTHS[width]
    s = lambda shape, dt: _spec(shape, dt, one_chip)           # noqa: E731
    pool = s((pages, PAGE, k, HD), BF16)
    text = jax.jit(paged_decode).lower(
        s((nb, h, HD), BF16), pool, pool, s((nb, npgb), I32),
        s((nb,), I32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("h,k,hd", [
    (16, 8, 64),       # granite-moe: half a lane tile
    (32, 32, 112),     # zamba2: no multiple of 128
    (8, 1, 128),       # one bf16 KV head: half a packed sublane
])
def test_paged_decode_compiles_for_untiled_pages(one_chip, h, k, hd):
    """Pages that do not fill whole (sublane, lane) tiles are padded to
    them before the kernel's copies, which Mosaic refuses otherwise."""
    s = lambda shape, dt: _spec(shape, dt, one_chip)           # noqa: E731
    pool = s((512, PAGE, k, hd), BF16)
    text = jax.jit(paged_decode).lower(
        s((B, h, hd), BF16), pool, pool, s((B, NPG), I32),
        s((B,), I32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def _two_layer_qwen():
    """qwen3-0.6b at published widths, depth cut to 2 layers."""
    return dataclasses.replace(get_config("qwen3-0.6b"), n_layers=2)


def _param_specs(cfg, sharding):
    from repro.models import build_model
    shapes = jax.eval_shape(build_model(cfg, BF16).init,
                            jax.random.PRNGKey(0))
    return jax.tree.map(lambda x: _spec(x.shape, x.dtype, sharding), shapes)


def test_decode_and_prefill_steps_compile_for_v5e(one_chip, on_tpu):
    from repro.serving import model_runner as mr
    cfg = _two_layer_qwen()
    s = lambda shape, dt: _spec(shape, dt, one_chip)           # noqa: E731
    params = _param_specs(cfg, one_chip)
    kp, vp = (s(x.shape, x.dtype)
              for x in mr.kv_pool_spec(cfg, P_POOL, PAGE, BF16))
    key = s((2,), jnp.uint32)
    bcap, npg_cap = 32, 256
    state = {"bt": s((bcap, npg_cap), I32), "lens": s((bcap,), I32),
             "toks": s((bcap,), I32), "temps": s((bcap,), jnp.float32),
             "top_ks": s((bcap,), I32), "seeds": s((bcap,), I32)}
    decode = mr.decode_step.lower(params, state, kp, vp, key, cfg=cfg,
                                  page_size=PAGE, nb=8, npgb=16).compile()
    # one kernel a layer: the paged-attention call inside the layer scan
    assert decode.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1

    S, NSEG, CP = 512, 8, 64
    tok_rows = [s((S,), I32)] * 5
    seg_rows = [s((CP,), I32)] + [s((NSEG,), I32)] * 3 + [
        s((NSEG,), jnp.float32)] + [s((NSEG,), I32)] * 3
    prefill = mr.prefill_pack_step.lower(
        params, *tok_rows, kp, vp, *seg_rows, key, cfg=cfg,
        page_size=PAGE).compile()
    assert prefill.memory_analysis().temp_size_in_bytes > 0


def test_page_import_aliases_the_pool(one_chip, on_tpu):
    """The donated `_scatter_pages` writes into the pools it was given:
    a page import at a deployment-sized pool needs no second pool."""
    from repro.serving.jax_backend import _scatter_pages
    _, (pools, _, stack, _, ids) = _kernel_case(
        "page_scatter", lambda shape, dt: _spec(shape, dt, one_chip))
    mem = _scatter_pages.lower(pools, pools, stack, stack,
                               ids).compile().memory_analysis()
    pool_bytes = L * P_POOL * PAGE * K * HD * 2
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
