"""What the benchmark knows about how the PROGRAM lays ``xing4_0`` out and how
the architecture's work is counted: its parameter tree under the names
``benchmarks/reference/xing4_0.py`` reads, which keys of a published config are
widths, parameter counts and attention shapes from such a config, how the
program's own expert picks come out of the ``put`` path and of the decode
chain (the architecture is ROUTED: PERF.md, section 7), what the latent kernel
and the routed layers cost at these widths (``glm4_moe_lite.py``'s counts),
and what the hyper-connections cost by the mathematics alone (``mhc_cost``).
"""

from __future__ import annotations

from typing import Tuple

# the latent attention, the routed layers and the picks are ``glm4_moe_lite``'s, letter for letter: its counts at these widths
from benchmarks.architectures.glm4_moe_lite import (  # noqa: F401  (what the runners and readers ask this file for)
    BF16, attention_params, expert_params, experts_per_token, generate_with_picks, head_dim, heads, kv_heads,
    latent_decode_cost, layers, put_with_picks, routed_decode_cost, routed_experts, routed_layers)

# never cut: a configuration whose ``reduced`` names one of these is refused
WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
              "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
              "routed_scaling_factor", "norm_topk_prob", "hc_mult", "hc_sinkhorn_iters")


def hyper_connection_params(cfg: dict) -> int:
    """One sublayer's ``phi`` [n hidden, n^2 + 2n], ``b`` and the three ``alpha``."""
    n = cfg["hc_mult"]
    return (n * cfg["hidden_size"] + 1) * (n * n + 2 * n) + 3


def _params(cfg: dict, experts: int) -> int:
    """The matrices a layer's products read: attention, the feed-forward at
    ``experts`` routed experts, the router, both hyper-connections' ``phi``."""
    h, n = cfg["hidden_size"], cfg["hc_mult"]
    dense, routed = cfg["first_k_dense_replace"], routed_layers(cfg)
    phi = 2 * n * h * (n * n + 2 * n)
    return (layers(cfg) * (attention_params(cfg) + phi) + dense * 3 * h * cfg["intermediate_size"]
            + routed * (h * cfg["n_routed_experts"]
                        + (experts + cfg["n_shared_experts"]) * expert_params(cfg)))


def matmul_params(cfg: dict) -> int:
    """What one token meets in a matrix product: attention, the dense layers'
    MLP, the router, its own experts and the shared one, the hyper-connections'
    ``phi``, and the output head."""
    return _params(cfg, cfg["num_experts_per_tok"]) + cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    h, n = cfg["hidden_size"], cfg["hc_mult"]
    # norms, correction bias, and of each hyper-connection ``b`` and ``alpha``
    small = (layers(cfg) * (2 * h + 2 * (n * n + 2 * n + 3)) + h
             + routed_layers(cfg) * cfg["n_routed_experts"])
    return _params(cfg, cfg["n_routed_experts"]) + small + 2 * cfg["vocab_size"] * h


def reference_weights(params) -> dict:
    """The program's parameter tree (leading dense layers ``dense_<i>``, the
    routed stack scan-stacked under ``layers``) under the names the plain
    reference reads. Relabelling only: the routed stack's arrays, the
    embedding and the head are the program's own, whatever their dtype and
    placement; only the leading dense layers, which the program keeps apart,
    are stacked here (0.26 GB each at the cell's size)."""
    import jax.numpy as jnp

    def shared(layer):
        attn = layer["attn"]
        out = {"norm1": layer["attn_norm"]["scale"], "norm2": layer["mlp_norm"]["scale"],
               "wq_a": attn["wq_a"]["kernel"], "q_norm": attn["q_norm"]["scale"],
               "wq_b": attn["wq_b"]["kernel"], "wkv_a": attn["wkv_a"]["kernel"],
               "kv_norm": attn["kv_norm"]["scale"], "wkv_b": attn["wkv_b"]["kernel"],
               "wo": attn["wo"]["kernel"]}
        for which in ("attn", "mlp"):
            out.update({f"hc_{which}_{leaf}": layer[f"{which}_hc"][leaf] for leaf in ("phi", "b", "alpha")})
        return out

    def dense(layer):
        mlp = layer["mlp"]
        return dict(shared(layer), w_gate=mlp["w_gate"]["kernel"], w_up=mlp["w_up"]["kernel"],
                    w_down=mlp["w_down"]["kernel"])

    leading = [dense(params[k]) for k in sorted((k for k in params if k.startswith("dense_")),
                                                key=lambda k: int(k.partition("_")[2]))]
    stack, moe = params["layers"], params["layers"]["moe"]
    return {
        "embed_in": params["embed"]["embedding"], "embed_out": params["lm_head"]["kernel"],
        "final_norm": params["final_norm"]["scale"],
        "dense": {name: jnp.stack([layer[name] for layer in leading]) for name in leading[0]},
        "routed": dict(
            shared(stack), router=moe["gate"]["wg"]["kernel"], router_bias=moe["gate"]["e_bias"],
            w_gate=moe["experts"]["w_gate"], w_up=moe["experts"]["w_up"],
            w_down=moe["experts"]["w_down"], shared_gate=moe["shared"]["w_gate"]["kernel"],
            shared_up=moe["shared"]["w_up"]["kernel"], shared_down=moe["shared"]["w_down"]["kernel"]),
    }


# --- what the hyper-connections' work costs, by the mathematics alone ---------

def mhc_cost(cfg: dict, tokens: float, layers_: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of the hyper-connections of ``tokens`` tokens through
    ``layers_`` layers, two sublayers each, in the streams' dtype (bf16). A
    sublayer reads the ``n C`` streams twice (the statistic and the product
    with ``phi`` in one pass, the mixed read and the write-back's mix in
    another) and writes them once, and reads the sublayer's output and writes
    its input, ``C`` each; the weights' bytes (``phi``, 0.7 MB a sublayer) are
    a call's, not a token's, and are left out. FLOPs: the statistic ``2 n C``,
    the product ``2 n C (n^2 + 2n)``, the mixed read ``2 n C``, the write-back
    ``2 n^2 C + 2 n C``, and the Sinkhorn rounds' ``4 n^2`` a round."""
    n, C = cfg["hc_mult"], cfg["hidden_size"]
    sublayers = 2.0 * tokens * layers_
    bytes_ = sublayers * (3 * n * C + 2 * C) * BF16
    flops = sublayers * (2 * n * C + 2 * n * C * (n * n + 2 * n) + 2 * n * C + 2 * n * n * C + 2 * n * C
                         + cfg["hc_sinkhorn_iters"] * 4 * n * n)
    return flops, bytes_
