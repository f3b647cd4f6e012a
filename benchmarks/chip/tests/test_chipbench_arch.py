"""Architecture modules (arch/): what a configuration's module gives the
harness, pinned to the numbers the harness gave before the modules were
split out of it, and an architecture that no shared harness file names,
served end to end on the CPU from its files alone.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import arch, arith, cell as cellmod  # noqa: E402
from benchmarks.chip import run as runmod, weights  # noqa: E402
from benchmarks.chip.tests.test_chipbench_correct import tiny_cell  # noqa: E402

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent


def config(name: str) -> dict:
    return cellmod.load_json(CHIP / "configs" / f"{name}.json")


# sha256 (first 16 hex digits) of each leaf's bytes, for the seed 2**33 + 7,
# made at the commit before arch/ by that harness's weights.make_params
# on the CPU
LEAF_SHA = {
    "qwen3-0.6b": {
        "embedding": "a6922eda976c46a7",
        "final_norm": "91c9732ae8c7ffa8",
        "layers/attn/k_scale": "dd278564cb555582",
        "layers/attn/q_scale": "247e430158936a75",
        "layers/attn/wk": "0729b7eaf189998f",
        "layers/attn/wo": "0dfad608acdae32b",
        "layers/attn/wq": "843f4388edf35e57",
        "layers/attn/wv": "c9aff133568ae814",
        "layers/ln1": "616accc27b041ed3",
        "layers/ln2": "542c04c448645347",
        "layers/mlp/w_down": "e1e1adf3b2671ead",
        "layers/mlp/w_gate": "2b4efbf9cc1f8bc3",
        "layers/mlp/w_up": "649b0a78eb500915",
    },
    "deepseek-7b": {
        "embedding": "09410096ad62e716",
        "final_norm": "ba3feb9e72b2615e",
        "layers/attn/wk": "1a9d4b7ff0a9047c",
        "layers/attn/wo": "f03ab5a4f02287eb",
        "layers/attn/wq": "0536cfb0cd9fff50",
        "layers/attn/wv": "3108333f417c8550",
        "layers/ln1": "87a1b4bb2225e34e",
        "layers/ln2": "4b389639f4813f9f",
        "layers/mlp/w_down": "2cb8f112ed99ecb0",
        "layers/mlp/w_gate": "72f15240da53b3ff",
        "layers/mlp/w_up": "6d43ef32d8a032b2",
        "lm_head": "58ad0b7637ae3b4f",
    },
}


@pytest.mark.parametrize("name", sorted(LEAF_SHA))
def test_weights_are_bit_identical_to_the_dense_harness(name):
    # one layer at the published widths; the vocabulary cut to 512 rows
    # keeps the CPU's memory under 3 GB (deepseek's full embedding and
    # head alone would take 13 GB to make)
    cut = dict(config(name), num_hidden_layers=1, vocab_size=512)
    params = weights.make_params(cut, 2**33 + 7)
    got = {"/".join(k.key for k in path):
           hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
           for path, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == LEAF_SHA[name]


# decode_row_flops at contexts 1, 1000, 4096; and per context list, the
# calls a step, operations and bytes of one call; by the dense harness
# (arith.decode_row_flops, num_hidden_layers x arith.paged_decode_cost)
DENSE = {
    "qwen3-0.6b": {
        "token": 1191968768.0,
        "row": {1: 1192198144.0, 1000: 1421344768.0, 4096: 2131492864.0},
        "paged": {(17,): (28, 139264.0, 77824),
                  (100, 300): (28, 3276800.0, 1654784),
                  (1024, 2048, 4096, 3): (28, 58744832.0, 29405184)},
    },
    "deepseek-7b": {
        "token": 4886364160.0,
        "row": {1: 4886528000.0, 1000: 5050204160.0, 4096: 5557452800.0},
        "paged": {(17,): (10, 278528.0, 294912),
                  (100, 300): (10, 6553600.0, 6586368),
                  (1024, 2048, 4096, 3): (10, 117489664.0, 117555200)},
    },
}


@pytest.mark.parametrize("name", sorted(DENSE))
def test_dense_counts_equal_the_dense_harness(name):
    cfg, want = config(name), DENSE[name]
    mod = arch.load(cfg)
    assert mod.__name__ == "benchmarks.chip.arch.dense"
    assert mod.token_flops(cfg) == want["token"]
    for ctx, flops in want["row"].items():
        assert mod.decode_row_flops(cfg, ctx) == flops
    for ctxs, (calls, flops, nbytes) in want["paged"].items():
        assert mod.paged_decode_costs(cfg, list(ctxs)) == \
            [(flops, nbytes)] * calls


def test_dense_token_flops():
    cfg = {"hidden_size": 1024, "intermediate_size": 3072,
           "num_hidden_layers": 28, "num_attention_heads": 16,
           "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936}
    from benchmarks.chip.arch import dense
    # qwen3-0.6b: 0.44 B matrix parameters in the layers plus the head
    assert dense.token_flops(cfg) == pytest.approx(
        28 * (2 * 1024 * (2 * 2048 + 2 * 1024) + 6 * 1024 * 3072)
        + 2 * 1024 * 151936)


def test_a_configuration_without_arch_is_an_error():
    cfg = config("qwen3-0.6b")
    del cfg["arch"]
    with pytest.raises(KeyError, match="names no architecture module"):
        arch.load(cfg)
    with pytest.raises(KeyError, match="names no architecture module"):
        weights.make_params(cfg, 1)


# ------------------------------------------- an architecture added by files

FIXTURES = HERE / "fixtures"
SECONDS = 4.0


def moe_cell() -> cellmod.Cell:
    return dataclasses.replace(
        tiny_cell(), name="moe-tiny",
        config=cellmod.load_json(FIXTURES / "moe.json"))


def test_no_shared_harness_file_names_the_fixture():
    shared = [*CHIP.glob("*.py"), *CHIP.glob("arch/*.py"),
              *CHIP.glob("metrics/*.py")]
    assert shared
    for f in shared:
        text = f.read_text()
        assert "moe_arch" not in text and "moe_reference" not in text, f


def test_the_fixture_has_its_own_leaves_and_counts():
    cfg = moe_cell().config
    mod = arch.load(cfg)
    params = weights.make_params(cfg, 11)
    moe = params["layers"]["moe"]
    assert "mlp" not in params["layers"]
    assert moe["w_gate"].shape == (2, 8, 64, 32)
    assert moe["router"].shape == (2, 64, 8)
    assert moe["w_gate"].dtype == jax.numpy.float32
    # the two routed experts of eight do work, the others none
    d, f, H, K, hd, L, V = 64, 32, 4, 2, 16, 2, 503
    proj = 2 * d * (2 * H * hd + 2 * K * hd)
    want = L * (proj + 2 * d * 8 + 2 * 3 * 2 * d * f) + 2 * d * V
    assert mod.token_flops(cfg) == want
    assert mod.decode_row_flops(cfg, 100) == want + L * 4 * 100 * H * hd
    assert len(mod.paged_decode_costs(cfg, [5, 7])) == L
    assert runmod.cellmod.model_config(cfg).is_moe


def test_an_architecture_added_by_files_serves_through_the_harness():
    cell = moe_cell()
    runs = [runmod.run(cell, seed, SECONDS, False, jax.devices()[:1],
                       control=True) for seed in (2**33 + 3, 9)]
    for r in runs:
        assert r["attempted"] > 5 and r["lost"] == 0
        assert sum(n for *_, n in r["sample"]) >= 24
        assert r["gaps"]["control"] >= 3 * r["gaps"]["program"]
    program = max(r["gaps"]["program"] for r in runs)
    control = min(r["gaps"]["control"] for r in runs)
    limits = {"logit_gap_max": math.sqrt(program * control)}
    for r in runs:
        assert runmod.verdict(r, limits)[0]
        low = dict(r, gaps={"program": r["gaps"]["control"]})
        assert not runmod.verdict(low, limits)[0]
