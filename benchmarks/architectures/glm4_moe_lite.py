"""What the benchmark knows about how the PROGRAM lays ``glm4_moe_lite`` out
and how the architecture's work is counted: its parameter tree under the names
``benchmarks/reference/glm4_moe_lite.py`` reads, which keys of a published
config are widths, parameter counts and attention shapes from such a config,
and, because the architecture is ROUTED, how the program's own expert picks
come out of the ``put`` path and of the decode chain (PERF.md, section 7), and
what its two new kernels' work costs by the mathematics alone.
"""

from __future__ import annotations

from typing import Tuple

# never cut: a configuration whose ``reduced`` names one of these is refused
WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
              "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
              "routed_scaling_factor", "norm_topk_prob")
BF16 = 2


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]


def kv_heads(cfg: dict) -> int:
    return 1  # what is cached of a token is one latent row, shared by every head


def head_dim(cfg: dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def routed_layers(cfg: dict) -> int:
    """More than 0 says: decide ``correct`` at the program's own expert picks."""
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def routed_experts(cfg: dict) -> int:
    return cfg["n_routed_experts"]


def experts_per_token(cfg: dict) -> int:
    return cfg["num_experts_per_tok"]


def attention_params(cfg: dict) -> int:
    """One layer's latent attention, its two latent norms among them."""
    h, H, rq, r = cfg["hidden_size"], heads(cfg), cfg["q_lora_rank"], cfg["kv_lora_rank"]
    rope, nope, v = cfg["qk_rope_head_dim"], cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    return (h * rq + rq + rq * H * (nope + rope) + h * (r + rope) + r + r * H * (nope + v)
            + H * v * h)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _params(cfg: dict, experts: int) -> int:
    h = cfg["hidden_size"]
    dense, routed = cfg["first_k_dense_replace"], routed_layers(cfg)
    return (layers(cfg) * attention_params(cfg) + dense * 3 * h * cfg["intermediate_size"]
            + routed * (h * cfg["n_routed_experts"]
                        + (experts + cfg["n_shared_experts"]) * expert_params(cfg)))


def matmul_params(cfg: dict) -> int:
    """What one token meets in a matrix product: attention, the dense layers'
    MLP, the router, its own experts and the shared one, and the output head."""
    return _params(cfg, cfg["num_experts_per_tok"]) + cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    small = layers(cfg) * 2 * h + h + routed_layers(cfg) * cfg["n_routed_experts"]  # norms, correction bias
    return _params(cfg, cfg["n_routed_experts"]) + small + 2 * cfg["vocab_size"] * h


def reference_weights(params) -> dict:
    """The program's parameter tree (leading dense layers ``dense_<i>``, the
    routed stack scan-stacked under ``layers``) under the names the plain
    reference reads. Relabelling only: the routed stack's arrays, the
    embedding and the head are the program's own, whatever their dtype and
    placement; only the leading dense layers, which the program keeps apart,
    are stacked here (0.17 GB each at the cell's size)."""
    import jax.numpy as jnp

    def shared(layer):
        attn = layer["attn"]
        return {"norm1": layer["attn_norm"]["scale"], "norm2": layer["mlp_norm"]["scale"],
                "wq_a": attn["wq_a"]["kernel"], "q_norm": attn["q_norm"]["scale"],
                "wq_b": attn["wq_b"]["kernel"], "wkv_a": attn["wkv_a"]["kernel"],
                "kv_norm": attn["kv_norm"]["scale"], "wkv_b": attn["wkv_b"]["kernel"],
                "wo": attn["wo"]["kernel"]}

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


def put_with_picks(engine, uids, fed):
    """``engine.put`` itself, and the picks its compiled step wrote beside the
    logits: ``picks[i]`` int32 ``[len(fed[i]), routed_layers, k]``."""
    return engine.put_with_picks(uids, fed)


def generate_with_picks(engine, prompts, max_new_tokens):
    """``engine.generate`` itself, and the picks its fused prefill and decode
    chains wrote beside the tokens, fetched after it has returned."""
    return engine.generate_with_picks(prompts, max_new_tokens=max_new_tokens)


# --- what the two new kernels' work costs, by the mathematics alone ---------

def latent_decode_cost(cfg: dict, context_tokens: float, row_steps: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's single-token latent attention, absorbed:
    ``context_tokens`` the sum over every row of every decode step of the
    positions it attends to, ``row_steps`` the number of such rows. A position
    is read ONCE for all heads, latent + rotary key in bf16 (not once a head,
    and not the lane padding an implementation adds); a head scores over
    rank + rope columns and sums values over rank; each row brings a query and
    takes an output a head."""
    rank, rope, H = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"], heads(cfg)
    flops = 2.0 * context_tokens * H * ((rank + rope) + rank)
    bytes_ = context_tokens * (rank + rope) * BF16 + row_steps * H * ((rank + rope) + rank) * BF16
    return flops, bytes_


def routed_decode_cost(cfg: dict, experts_read: float, token_steps: float,
                       layer_steps: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of the routed feed-forward layers of decode steps:
    ``experts_read`` the sum over steps and routed layers of the DISTINCT
    experts the live rows picked (each read once a step, whoever shares it),
    ``token_steps`` the live rows summed over steps and routed layers,
    ``layer_steps`` the (step, routed layer) pairs. Beside the experts each
    pair reads the shared expert and the router once; a token does the
    products of its own experts, the shared one and the router."""
    h, E = cfg["hidden_size"], cfg["n_routed_experts"]
    expert = expert_params(cfg)
    always = cfg["n_shared_experts"] * expert + h * E + E
    flops = 2.0 * token_steps * (cfg["num_experts_per_tok"] * expert + always)
    bytes_ = (experts_read * expert + layer_steps * always) * BF16
    return flops, bytes_
