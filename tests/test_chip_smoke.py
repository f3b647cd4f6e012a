"""`chip_smoke.py` on the CPU: it refuses to report success without a
TPU, and its phases run end to end at reduced width, so the script a chip
run depends on cannot rot between chip runs. Also the compile-cache
placement its `main()` (and every entry point's) relies on.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]
SMOKE = REPO / "chip_smoke.py"


def _cpu_env(**extra) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"), **extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_fails_without_a_tpu_and_prints_no_ok_line():
    out = subprocess.run([sys.executable, str(SMOKE)], cwd=REPO,
                         env=_cpu_env(), capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_smoke_engine_and_router_phases_at_reduced_width(smoke,
                                                         qwen_reduced):
    """The one-chip phases on the CPU: turn-2 prefix hits, host-tier
    demotion and load-back at the few-dozen-page pool, and a cross-region
    forward — the checks the chip run makes, at a width the CPU affords."""
    params = smoke.build_params(qwen_reduced, 0)
    clock = smoke.CompileClock()
    smoke.engine_phase(qwen_reduced, params, clock, 0,
                       ecfg=dataclasses.replace(smoke.ENGINE, n_pages=256))
    smoke.host_tier_phase(qwen_reduced, params, clock, 0)
    smoke.router_phase(qwen_reduced, params, clock, 0)


_FOUR_DEVICES = textwrap.dedent("""
    import sys
    sys.path.insert(0, {repo!r})
    import jax
    import chip_smoke as smoke
    from repro.configs import get_config
    cfg = get_config("qwen3-0.6b").reduced()
    assert len(jax.devices()) == 4
    smoke.four_chip_phase(cfg, smoke.build_params(cfg, 0),
                          smoke.CompileClock(), 0, jax.devices())
    print("FOUR-DEVICE PHASE OK")
""")


def test_four_chip_phase_on_four_cpu_devices():
    """One engine per device versus all four on the first: each engine's
    pools follow its params' device, and decisions and tokens match."""
    out = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICES.format(repo=str(REPO))],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "FOUR-DEVICE PHASE OK" in out.stdout


def test_compile_cache_is_placed_from_outside(monkeypatch):
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/caller")
        assert compile_cache.enable_compile_cache() == "/set/by/caller"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(REPO / ".jax_cache")
        assert compile_cache.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
