"""Test fixture: the architecture module of a tiny decoder of the
program's `moe` family, which no shared harness file names. Every layer
has full attention and a mixture of SwiGLU experts: a router over
`num_experts`, softmax, the top `num_experts_per_tok`, gates renormalised
over those. Its leaves (layers/moe/...) and its operations per token
differ from a dense decoder's. It serves in float32: in bfloat16 a router
score rounded across a near tie picks another expert than the float32
reference does, a difference of routing and not of precision.
"""
from __future__ import annotations

from benchmarks.chip import arith


def model_config(config: dict):
    from repro.configs.base import ModelConfig, MoEConfig
    E, k = config["num_experts"], config["num_experts_per_tok"]
    f = config["moe_intermediate_size"]
    return ModelConfig(
        name=config["name"], family="moe",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=f,
        vocab=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        # room in every expert for every token of a group: none is dropped
        moe=MoEConfig(n_experts=E, top_k=k, d_expert=f,
                      capacity_factor=E / k),
        max_seq_len=config["engine"]["max_seq_len"],
        source=config["source"])


def _dims(config: dict) -> tuple:
    return (config["hidden_size"], config["moe_intermediate_size"],
            config["num_experts"], config["num_experts_per_tok"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["num_hidden_layers"],
            config["vocab_size"])


def shapes(config: dict) -> dict:
    d, f, E, _, H, K, hd, L, V = _dims(config)
    return {
        "embedding": (V, d),
        "lm_head": (d, V),
        "final_norm": (d,),
        "layers/ln1": (L, d),
        "layers/ln2": (L, d),
        "layers/attn/wq": (L, d, H, hd),
        "layers/attn/wk": (L, d, K, hd),
        "layers/attn/wv": (L, d, K, hd),
        "layers/attn/wo": (L, H, hd, d),
        "layers/moe/router": (L, d, E),
        "layers/moe/w_gate": (L, E, d, f),
        "layers/moe/w_up": (L, E, d, f),
        "layers/moe/w_down": (L, E, f, d),
    }


def scale(path: str, config: dict) -> float | None:
    d, f, _, _, H, _, hd, L, _ = _dims(config)
    resid = (2 * L) ** -0.5
    return {"embedding": 0.02, "lm_head": d ** -0.5, "router": d ** -0.5,
            "wq": d ** -0.5, "wk": d ** -0.5, "wv": d ** -0.5,
            "wo": (H * hd) ** -0.5 * resid,
            "w_up": d ** -0.5, "w_gate": d ** -0.5,
            "w_down": f ** -0.5 * resid}.get(path.rsplit("/", 1)[-1])


def init_keys(config: dict) -> tuple:
    keep = ("hidden_size", "moe_intermediate_size", "num_experts",
            "num_experts_per_tok", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size")
    return tuple((k, config[k]) for k in keep)


def token_flops(config: dict) -> float:
    """Projections, the router, and the top-k experts only: the experts a
    token is not routed to do no work for it."""
    d, f, E, k, H, K, hd, L, V = _dims(config)
    proj = 2.0 * d * (2 * H * hd + 2 * K * hd)
    moe = 2.0 * d * E + k * 3 * 2.0 * d * f
    return L * (proj + moe) + 2.0 * d * V


def decode_row_flops(config: dict, ctx: int) -> float:
    *_, H, _, hd, L, _ = _dims(config)
    return token_flops(config) + L * 4.0 * ctx * H * hd


def paged_decode_costs(config: dict, ctxs: list) -> list:
    *_, H, K, hd, L, _ = _dims(config)
    return [arith.paged_decode_cost(ctxs, H, K, hd)] * L
