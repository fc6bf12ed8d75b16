"""What the benchmark knows of how the program lays a ``routed_toy`` out, how
its work is counted and, because the architecture is ROUTED, how the program's
own expert picks come out of the ``put`` path (PERF.md, section 7). The tests
copy this file into a copy of ``benchmarks/`` as
``architectures/routed_toy.py``; the "program" it faces is the stand-in engine
beside it."""

from __future__ import annotations

# never cut; the number of experts per token is a width too
WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
              "num_experts_per_tok")


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]


def kv_heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def routed_layers(cfg: dict) -> int:
    """More than 0 says: decide ``correct`` at the program's own expert picks."""
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def routed_experts(cfg: dict) -> int:
    return cfg["n_routed_experts"]


def experts_per_token(cfg: dict) -> int:
    return cfg["num_experts_per_tok"]


def _params(cfg: dict, experts: int) -> int:
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    dense, routed = cfg["first_k_dense_replace"], routed_layers(cfg)
    attention = 4 * h * h
    return (layers(cfg) * attention + dense * 3 * h * cfg["intermediate_size"]
            + routed * (h * cfg["n_routed_experts"] + (experts + cfg["n_shared_experts"]) * 3 * h * f))


def matmul_params(cfg: dict) -> int:
    """What one token meets in a matrix product: attention, the dense layer's
    MLP, the router, its own experts and the shared one, and the output head."""
    return _params(cfg, cfg["num_experts_per_tok"]) + cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    small = layers(cfg) * 2 * h + h + routed_layers(cfg) * cfg["n_routed_experts"]  # norms, correction bias
    return _params(cfg, cfg["n_routed_experts"]) + small + 2 * cfg["vocab_size"] * h


def reference_weights(params) -> dict:
    """The stand-in's list of per-layer trees under the names the plain
    reference reads, each group stacked over its layers."""
    import jax.numpy as jnp

    names = {"norm1": "ln_a", "norm2": "ln_m", "wq": "q", "wk": "k", "wv": "v", "wo": "o"}
    dense_names = dict(names, w_gate="gate", w_up="up", w_down="down")
    routed_names = dict(names, router="route_w", router_bias="route_b", w_gate="e_gate", w_up="e_up",
                        w_down="e_down", shared_gate="sh_gate", shared_up="sh_up", shared_down="sh_down")

    def stacked(blocks, naming):
        return {ours: jnp.stack([b[theirs] for b in blocks]) for ours, theirs in naming.items()}

    blocks = params["blocks"]
    return {"embed_in": params["tok_emb"], "embed_out": params["head"], "final_norm": params["ln_f"],
            "dense": stacked([b for b in blocks if "route_w" not in b], dense_names),
            "routed": stacked([b for b in blocks if "route_w" in b], routed_names)}


def put_with_picks(engine, uids, fed):
    """``engine.put`` itself, and the picks its compiled step wrote beside the
    logits: ``picks[i]`` int32 ``[len(fed[i]), routed_layers, k]``."""
    logits = engine.put(uids, fed)
    return logits, engine.last_picks


def generate_with_picks(engine, prompts, max_new_tokens):
    outs = engine.generate(prompts, max_new_tokens=max_new_tokens)
    return outs, engine.last_picks
