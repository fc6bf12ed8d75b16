"""The seam to the system under test: its model config from a published
``config.json``, and its parameter tree relabelled for the plain reference.
Everything else the benchmark knows about the program is in the runners."""

from __future__ import annotations

import dataclasses

PUBLISHED_KEYS = (
    "model_type", "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "max_position_embeddings", "hidden_act", "rotary_pct",
    "rotary_emb_base", "layer_norm_eps", "use_parallel_residual", "tie_word_embeddings")


def published(config: dict) -> dict:
    """The configuration file without the benchmark's own notes."""
    return {k: config[k] for k in PUBLISHED_KEYS if k in config}


def model_config(config: dict, dtype):
    """The program's TransformerConfig for a published config, through the
    mapping it applies to HuggingFace checkpoints; no preset of its own."""
    from deepspeed_tpu.checkpoint.hf import config_from_hf

    return dataclasses.replace(config_from_hf(published(config)), dtype=dtype)


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


def relative_error(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))
