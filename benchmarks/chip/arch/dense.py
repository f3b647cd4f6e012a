"""Dense pre-norm decoders (Llama and Qwen3 families), under the key names
of the model's own config.json: every layer has full attention (GQA or
multi-head, optional per-head RMSNorm on q and k) and a gated MLP.

Parameter layout as the program's engine takes it: layers stacked on a
leading axis, head-major attention projections. Init scales: inputs of
each projection at fan-in**-0.5, residual outputs further divided by
sqrt(2 * layers), embeddings at 0.02.
"""
from __future__ import annotations

from benchmarks.chip import arith


def head_dim(config: dict) -> int:
    return config.get("head_dim") or (config["hidden_size"]
                                      // config["num_attention_heads"])


def model_config(config: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim", 0),
        d_ff=config["intermediate_size"],
        vocab=config["vocab_size"],
        qk_norm=bool(config.get("qk_norm", False)),
        gated_mlp=config.get("hidden_act", "silu") == "silu",
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        max_seq_len=config["engine"]["max_seq_len"],
        source=config["source"])


def shapes(config: dict) -> dict:
    d, f = config["hidden_size"], config["intermediate_size"]
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    hd, L, V = head_dim(config), config["num_hidden_layers"], config["vocab_size"]
    out = {
        "embedding": (V, d),
        "final_norm": (d,),
        "layers/ln1": (L, d),
        "layers/ln2": (L, d),
        "layers/attn/wq": (L, d, H, hd),
        "layers/attn/wk": (L, d, K, hd),
        "layers/attn/wv": (L, d, K, hd),
        "layers/attn/wo": (L, H, hd, d),
        "layers/mlp/w_up": (L, d, f),
        "layers/mlp/w_gate": (L, d, f),
        "layers/mlp/w_down": (L, f, d),
    }
    if not config["tie_word_embeddings"]:
        out["lm_head"] = (d, V)
    if config.get("qk_norm"):
        out["layers/attn/q_scale"] = (L, hd)
        out["layers/attn/k_scale"] = (L, hd)
    return out


def scale(path: str, config: dict) -> float | None:
    d, f = config["hidden_size"], config["intermediate_size"]
    H, L = config["num_attention_heads"], config["num_hidden_layers"]
    resid = (2 * L) ** -0.5
    leaf = path.rsplit("/", 1)[-1]
    return {"embedding": 0.02, "lm_head": d ** -0.5,
            "wq": d ** -0.5, "wk": d ** -0.5, "wv": d ** -0.5,
            "wo": (H * head_dim(config)) ** -0.5 * resid,
            "w_up": d ** -0.5, "w_gate": d ** -0.5,
            "w_down": f ** -0.5 * resid}.get(leaf)


def init_keys(config: dict) -> tuple:
    keep = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "tie_word_embeddings", "qk_norm")
    return tuple((k, config[k]) for k in keep if k in config)


def token_flops(config: dict) -> float:
    """Matrix-product operations of one token through every layer and
    the output head (the dense-layer arithmetic of repro.analysis.flops:
    q, k, v and output projections, gated MLP)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    hd, L, V = head_dim(config), config["num_hidden_layers"], config["vocab_size"]
    proj = 2.0 * d * (2 * H * hd + 2 * K * hd)
    mlp = 3 * 2.0 * d * f
    return L * (proj + mlp) + 2.0 * d * V


def decode_row_flops(config: dict, ctx: int) -> float:
    H, L = config["num_attention_heads"], config["num_hidden_layers"]
    return token_flops(config) + L * 4.0 * ctx * H * head_dim(config)


def paged_decode_costs(config: dict, ctxs: list) -> list:
    """One call a layer, each over every live row's whole context."""
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    cost = arith.paged_decode_cost(ctxs, H, K, head_dim(config))
    return [cost] * config["num_hidden_layers"]
