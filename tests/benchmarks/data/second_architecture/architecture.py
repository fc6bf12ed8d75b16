"""What the benchmark knows of how the program lays a ``mixtral`` out and how
its work is counted. ``tests/benchmarks/test_data_driven.py`` copies this file
into a copy of ``benchmarks/`` as ``architectures/mixtral.py``: other config
keys than ``gpt_neox``'s (KV heads, experts), another parameter tree (no
biases, one norm scale, experts stacked under ``moe``), and a token meets only
``num_experts_per_tok`` of a layer's experts."""

from __future__ import annotations

# never cut; the number of experts per token is a width too
WIDTH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
              "num_experts_per_tok")


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]


def kv_heads(cfg: dict) -> int:
    return cfg["num_key_value_heads"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _layer_params(cfg: dict, experts: int) -> int:
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    attention = 2 * h * heads(cfg) * d + 2 * h * kv_heads(cfg) * d
    return attention + h * cfg["num_local_experts"] + experts * 3 * h * f


def matmul_params(cfg: dict) -> int:
    """What one token meets in a matrix product: the attention projections,
    the router, its ``num_experts_per_tok`` experts, and the output head."""
    return (layers(cfg) * _layer_params(cfg, cfg["num_experts_per_tok"])
            + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    tables = (1 if cfg.get("tie_word_embeddings") else 2) * cfg["vocab_size"] * h
    return layers(cfg) * (_layer_params(cfg, cfg["num_local_experts"]) + 2 * h) + h + tables


def reference_weights(params) -> dict:
    """The program's (scan-stacked) parameter tree under the names the plain
    reference reads. Relabelling only."""
    layer, attn, moe = params["layers"], params["layers"]["attn"], params["layers"]["moe"]
    return {
        "embed_in": params["embed"]["embedding"],
        "embed_out": params["lm_head"]["kernel"],
        "final_norm": params["final_norm"]["scale"],
        "layers": {
            "norm1": layer["attn_norm"]["scale"], "norm2": layer["mlp_norm"]["scale"],
            "wq": attn["wq"]["kernel"], "wk": attn["wk"]["kernel"], "wv": attn["wv"]["kernel"],
            "wo": attn["wo"]["kernel"],
            "router": moe["gate"]["wg"]["kernel"],
            "w_gate": moe["experts"]["w_gate"], "w_up": moe["experts"]["w_up"],
            "w_down": moe["experts"]["w_down"],
        },
    }
