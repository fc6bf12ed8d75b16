"""What the benchmark knows about how the PROGRAM lays ``gpt_neox`` out and
how the architecture's work is counted: its parameter tree under the names
``benchmarks/reference/gpt_neox.py`` reads, which keys of a published config
are widths, and parameter counts and attention shapes from such a config.
``harness.load_architecture`` finds this file by the ``architecture`` a
configuration file names, as ``load_reference`` finds the plain reference;
the runners and the readers reach these facts through the loaded module only.
"""

from __future__ import annotations

# never cut: a configuration whose ``reduced`` names one of these is refused
WIDTH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads", "rotary_pct")


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]


def kv_heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]  # no grouped-query variant of gpt_neox


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def matmul_params(cfg: dict) -> int:
    """Parameters that sit in a matrix multiplication once per token: the
    four attention projections and the two MLP matrices of every layer, and
    the output head. The embedding table is a lookup and is left out."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * h * h + 2 * h * f) + h * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    h, f, L, V = (cfg["hidden_size"], cfg["intermediate_size"],
                  cfg["num_hidden_layers"], cfg["vocab_size"])
    per_layer = 4 * h * h + 4 * h + 2 * h * f + f + h + 4 * h  # weights, biases, two norms
    return L * per_layer + 2 * h + (1 if cfg.get("tie_word_embeddings") else 2) * V * h


def reference_weights(params) -> dict:
    """The program's (scan-stacked) parameter tree under the names
    ``benchmarks/reference/gpt_neox.py`` reads. Relabelling only: the arrays
    are the program's own, whatever their dtype and placement."""
    layers, attn, mlp = params["layers"], params["layers"]["attn"], params["layers"]["mlp"]
    return {
        "embed_in": params["embed"]["embedding"],
        "embed_out": params["lm_head"]["kernel"],
        "final_ln_scale": params["final_norm"]["scale"],
        "final_ln_bias": params["final_norm"]["bias"],
        "layers": {
            "ln1_scale": layers["attn_norm"]["scale"], "ln1_bias": layers["attn_norm"]["bias"],
            "ln2_scale": layers["mlp_norm"]["scale"], "ln2_bias": layers["mlp_norm"]["bias"],
            "wq": attn["wq"]["kernel"], "bq": attn["wq"]["bias"],
            "wk": attn["wk"]["kernel"], "bk": attn["wk"]["bias"],
            "wv": attn["wv"]["kernel"], "bv": attn["wv"]["bias"],
            "wo": attn["wo"]["kernel"], "bo": attn["wo"]["bias"],
            "w_in": mlp["w_up"]["kernel"], "b_in": mlp["w_up"]["bias"],
            "w_out": mlp["w_down"]["kernel"], "b_out": mlp["w_down"]["bias"],
        },
    }
