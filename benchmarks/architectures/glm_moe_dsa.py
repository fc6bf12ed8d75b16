"""What the benchmark knows about how the PROGRAM lays ``glm_moe_dsa`` (GLM-5)
out and how the architecture's work is counted: ``glm4_moe_lite``'s parameter
tree with, in every layer's attention, the learned indexer's leaves, under the
names ``benchmarks/reference/glm_moe_dsa.py`` reads; which keys of a published
config are widths; parameter counts from such a config, of a chip's share where
the config states one (``expert_parallel: {size, rank}``: ``n_routed_experts``
are the experts HELD, the router scores ``size`` times as many); how the
program's own expert picks, and its own SELECTION, come out of the ``put``
path and of the decode chain; and what its two new kernels' work costs by the
mathematics alone.
"""

from __future__ import annotations

from typing import Tuple

# never cut: a configuration whose ``reduced`` names one of these is refused.
# ``first_k_dense_replace`` is DEPTH here (the guide counts leading dense layers
# once) and is not among them.
WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
              "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "index_n_heads", "index_head_dim", "index_topk", "num_experts_per_tok", "n_shared_experts",
              "routed_scaling_factor", "norm_topk_prob", "expert_parallel")
BF16 = 2


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]


def kv_heads(cfg: dict) -> int:
    return 1  # what is cached of a token is one latent row and one index key, shared by every head


def head_dim(cfg: dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def routed_layers(cfg: dict) -> int:
    """More than 0 says: decide ``correct`` at the program's own expert picks."""
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def held_experts(cfg: dict) -> int:
    return cfg["n_routed_experts"]


def routed_experts(cfg: dict) -> int:
    """Experts the router scores and numbers its picks by: those held here times the chips that share a layer."""
    return cfg["n_routed_experts"] * int((cfg.get("expert_parallel") or {"size": 1})["size"])


def experts_per_token(cfg: dict) -> int:
    return cfg["num_experts_per_tok"]


def indexer_params(cfg: dict) -> int:
    """One layer's indexer: query and key projections, the key's LayerNorm (weight and bias), the heads' weights."""
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * Hi * Di + cfg["hidden_size"] * (Di + Hi) + 2 * Di


def attention_params(cfg: dict) -> int:
    """One layer's latent attention, its two latent norms and its indexer among them."""
    h, H, rq, r = cfg["hidden_size"], heads(cfg), cfg["q_lora_rank"], cfg["kv_lora_rank"]
    rope, nope, v = cfg["qk_rope_head_dim"], cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    return (h * rq + rq + rq * H * (nope + rope) + h * (r + rope) + r + r * H * (nope + v)
            + H * v * h + indexer_params(cfg))


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _params(cfg: dict, experts: float) -> float:
    h = cfg["hidden_size"]
    dense, routed = cfg["first_k_dense_replace"], routed_layers(cfg)
    return (layers(cfg) * attention_params(cfg) + dense * 3 * h * cfg["intermediate_size"]
            + routed * (h * routed_experts(cfg) + (experts + cfg["n_shared_experts"]) * expert_params(cfg)))


def matmul_params(cfg: dict) -> float:
    """What one token meets in a matrix product HERE: attention and the
    indexer's projections, the dense layers' MLP, the router, the shared expert,
    its picks among the experts held (``k`` times held over scored, on
    average), and the output head."""
    here = cfg["num_experts_per_tok"] * held_experts(cfg) / routed_experts(cfg)
    return _params(cfg, here) + cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    small = layers(cfg) * 2 * h + h + routed_layers(cfg) * routed_experts(cfg)  # norms, correction bias
    return int(_params(cfg, held_experts(cfg))) + small + 2 * cfg["vocab_size"] * h


def reference_weights(params) -> dict:
    """The program's parameter tree (leading dense layers ``dense_<i>``, the
    routed stack scan-stacked under ``layers``) under the names the plain
    reference reads. Relabelling only: the routed stack's arrays, the
    embedding and the head are the program's own, whatever their dtype and
    placement; only the leading dense layers, which the program keeps apart,
    are stacked here."""
    import jax.numpy as jnp

    def shared(layer):
        attn = layer["attn"]
        return {"norm1": layer["attn_norm"]["scale"], "norm2": layer["mlp_norm"]["scale"],
                "wq_a": attn["wq_a"]["kernel"], "q_norm": attn["q_norm"]["scale"],
                "wq_b": attn["wq_b"]["kernel"], "wkv_a": attn["wkv_a"]["kernel"],
                "kv_norm": attn["kv_norm"]["scale"], "wkv_b": attn["wkv_b"]["kernel"],
                "wo": attn["wo"]["kernel"],
                "idx_wq": attn["idx_wq"]["kernel"], "idx_wk": attn["idx_wk"]["kernel"],
                "idx_k_scale": attn["idx_k_norm"]["scale"], "idx_k_bias": attn["idx_k_norm"]["bias"],
                "idx_w": attn["idx_w"]["kernel"]}

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


def put_with_selected(engine, uids, fed):
    """``engine.put`` and, beside the logits and the picks, what every query
    fed KEPT: ``selected[i]`` int32 ``[len(fed[i]), layers, ceil(positions /
    32)]``, the mask over the row's positions packed 32 a word, as
    ``benchmarks/reference/glm_moe_dsa.py::forward(selected=)`` takes it (None
    where the engine's block table holds no more than a query keeps)."""
    return engine.put_with_selected(uids, fed)


# --- what the new mechanisms' work costs, by the mathematics alone ----------

def routed_decode_cost(cfg: dict, experts_read: float, token_steps: float,
                       layer_steps: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of the routed feed-forward layers of decode steps, as
    THIS CHIP's share of them (``qwen3_next.py``'s count at this router):
    ``experts_read`` the sum over steps and routed layers of the DISTINCT HELD
    experts the live rows picked (each read once a step, whoever shares it),
    ``token_steps`` the live rows summed over steps and routed layers,
    ``layer_steps`` the (step, routed layer) pairs. Beside the experts each
    pair reads the shared expert, the router (all its columns) and its
    correction bias once; a token does the router's and the shared expert's
    products and those of its visits to HELD experts, ``num_experts_per_tok``
    over the chips that share a layer on average."""
    h, E = cfg["hidden_size"], routed_experts(cfg)
    expert = expert_params(cfg)
    always = cfg["n_shared_experts"] * expert + h * E + E
    visits = cfg["num_experts_per_tok"] * held_experts(cfg) / E
    flops = 2.0 * token_steps * (visits * expert + always)
    bytes_ = (experts_read * expert + layer_steps * always) * BF16
    return flops, bytes_


def _kept(cfg: dict, start: int, n: int) -> Tuple[float, float]:
    """(cached tokens scored, cached tokens kept) by the ``n`` queries at positions ``start ...``: a query at
    ``t`` scores ``t + 1`` and keeps ``min(t + 1, index_topk)``."""
    k = cfg["index_topk"]
    first, last = start + 1, start + n  # the candidates of the first and of the last query
    scored = (first + last) * n / 2.0
    under = max(0, min(last, k) - first + 1)  # queries that keep every candidate
    kept = (first + first + under - 1) * under / 2.0 + (n - under) * k
    return scored, kept


def dsa_index_cost(cfg: dict, spans) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's index scores for rows of queries ``spans``
    = ``[(first position, queries), ...]``: a query at position ``t`` scores
    ``t + 1`` cached keys, each at ``2 x index_n_heads x index_head_dim`` FLOPs
    (the products; the ``relu``, the weights and the sum over heads are not
    counted). Bytes: a row's cached keys ONCE for all the queries the call
    feeds it (``index_head_dim`` bf16 values a key, 256 B: one query at ``t``
    reads ``t + 1`` of them), and each query's heads once. Whatever implements
    it; the scores are not counted as traffic (a form that chooses as it scores
    writes none)."""
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    scored = sum(_kept(cfg, start, n)[0] for start, n in spans)
    keys, queries = sum(start + n for start, n in spans), sum(n for _, n in spans)
    return 2.0 * Hi * Di * scored, (keys * Di + queries * Hi * Di) * BF16


def dsa_attend_cost(cfg: dict, spans) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's attention over the selected tokens for rows
    of queries ``spans`` (as ``dsa_index_cost`` takes them): a query at ``t``
    attends ``min(t + 1, index_topk)`` tokens, each at ``2 x heads x ((rank +
    rope) + rank)`` FLOPs (absorbed: a head scores over latent + rotary key and
    sums values over the latent). Bytes: a kept token's latent + rotary key in
    bf16 (1,152 B: not the lane padding an implementation adds, as
    ``glm4_moe_lite.py::latent_decode_cost`` counts it), once a query but no
    more than a row's cached tokens once (a tile of queries shares what it
    keeps: one query at ``t`` reads its ``min(t + 1, index_topk)``), and each
    query's absorbed heads in and attended latents out. A chunk that walks all
    ``t + 1`` positions under a mask does up to ``(t + 1) / index_topk`` times
    these FLOPs, so at 8k it reads under half its roofline, and says so."""
    rank, rope, H = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"], heads(cfg)
    kept = sum(_kept(cfg, start, n)[1] for start, n in spans)
    read = sum(min(_kept(cfg, start, n)[1], start + n) for start, n in spans)
    queries = sum(n for _, n in spans)
    return (2.0 * H * ((rank + rope) + rank) * kept,
            (read * (rank + rope) + queries * H * ((rank + rope) + rank)) * BF16)
