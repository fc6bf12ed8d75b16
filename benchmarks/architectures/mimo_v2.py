"""What the benchmark knows about how the PROGRAM lays ``mimo_v2`` (MiMo-V2.5's
language model) out and how the architecture's work is counted: its parameter
tree under the names ``benchmarks/reference/mimo_v2.py`` reads, which keys of a
published config are widths, parameter counts and attention shapes BY KIND from
such a config, because the architecture is ROUTED how the program's own expert
picks come out of the ``put`` path and of the decode chain (PERF.md, section
7), and what its mechanisms cost by the mathematics alone
(``benchmarks/lib/two_width.py``: the key's 192 columns and the value's 128
counted apart, GQA's keys and values read once a key-value head, the band's
keys, the sink's scalars): a decode step's read of a ring and of a global table
(``paged_decode_cost``, ``swa_decode_cost``), a fresh prompt's attention under
the band and under the causal mask alone (``swa_prefill_cost``,
``full_prefill_cost``), and the routed layers of a decode step as ONE CHIP'S
SHARE reads them (``routed_decode_cost``).

``n_routed_experts`` in a configuration is the number of experts HELD by the
chip (``reduced``); with ``expert_parallel: {size, rank}`` the router scores
``size`` times as many, and the picks are numbered over all of them. There is
no shared expert.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from benchmarks.lib import two_width

BF16 = 2

# never cut: a configuration whose ``reduced`` names one of these is refused
WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "v_head_dim", "swa_num_attention_heads", "swa_num_key_value_heads",
              "swa_head_dim", "swa_v_head_dim", "sliding_window", "partial_rotary_factor", "attention_value_scale",
              "num_experts_per_tok", "n_shared_experts", "norm_topk_prob", "routed_scaling_factor",
              "expert_parallel")


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def layer_kinds(cfg: dict) -> Tuple[str, ...]:
    return tuple("sliding" if kind else "global" for kind in cfg["hybrid_layer_pattern"])


def sliding_layers(cfg: dict) -> int:
    return layer_kinds(cfg).count("sliding")


def full_layers(cfg: dict) -> int:
    return layers(cfg) - sliding_layers(cfg)


def dense_layers(cfg: dict) -> int:
    freq = list(cfg["moe_layer_freq"])
    return next((i for i, f in enumerate(freq) if f), len(freq))


def heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]


def kv_heads(cfg: dict, kind: str = "global") -> int:
    return cfg["swa_num_key_value_heads" if kind == "sliding" else "num_key_value_heads"]


def head_dim(cfg: dict, kind: str = "global") -> int:
    """The width of a query and of a key."""
    return cfg["swa_head_dim" if kind == "sliding" else "head_dim"]


def value_dim(cfg: dict, kind: str = "global") -> int:
    return cfg["swa_v_head_dim" if kind == "sliding" else "v_head_dim"]


def window(cfg: dict) -> int:
    return int(cfg["sliding_window"])


def has_sink(cfg: dict, kind: str) -> bool:
    return bool(cfg["add_swa_attention_sink_bias" if kind == "sliding" else "add_full_attention_sink_bias"])


def routed_layers(cfg: dict) -> int:
    """More than 0 says: decide ``correct`` at the program's own expert picks."""
    return layers(cfg) - dense_layers(cfg)


def held_experts(cfg: dict) -> int:
    return cfg["n_routed_experts"]


def routed_experts(cfg: dict) -> int:
    """The router's width, the published numbering of the picks: the experts
    held here times the chips that share a layer."""
    return cfg["n_routed_experts"] * int((cfg.get("expert_parallel") or {"size": 1})["size"])


def experts_per_token(cfg: dict) -> int:
    return cfg["num_experts_per_tok"]


def attention_params(cfg: dict, kind: str) -> int:
    """One attention mixer of ``kind``: q and k at the key's width, v and o at the value's; the sliding kind's
    sinks; no bias, no qk-norm."""
    h, H, Hkv = cfg["hidden_size"], heads(cfg), kv_heads(cfg, kind)
    return (h * head_dim(cfg, kind) * (H + Hkv) + h * value_dim(cfg, kind) * (Hkv + H)
            + (H if has_sink(cfg, kind) else 0))


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _params(cfg: dict, experts: float) -> float:
    """The layers' matrices (and sinks) with ``experts`` routed experts a routed layer."""
    mixers = sum(attention_params(cfg, kind) for kind in layer_kinds(cfg))
    return (mixers + dense_layers(cfg) * dense_mlp_params(cfg)
            + routed_layers(cfg) * (cfg["hidden_size"] * routed_experts(cfg) + experts * expert_params(cfg)))


def matmul_params(cfg: dict) -> int:
    """What one token meets in a matrix product HERE: every mixer, the leading dense MLP, the router, its own
    experts' share of this chip (``experts_per_token`` over the chips that share a layer) and the untied head."""
    here = cfg["num_experts_per_tok"] * held_experts(cfg) / routed_experts(cfg)
    return int(_params(cfg, here) - heads(cfg) * sum(has_sink(cfg, k) for k in layer_kinds(cfg))
               + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    """Every parameter the program holds: the layers (two RMSNorms each, a correction bias a routed layer), the
    final norm, the embedding and the untied head."""
    h = cfg["hidden_size"]
    small = layers(cfg) * 2 * h + h + routed_layers(cfg) * routed_experts(cfg)
    return int(_params(cfg, held_experts(cfg))) + small + 2 * cfg["vocab_size"] * h


parameter_count = total_params  # (the issue's name for it)


def cache_bytes_per_token_layer(cfg: dict, kind: str) -> int:
    """A token's key and value in one layer of ``kind``, bf16."""
    return kv_heads(cfg, kind) * (head_dim(cfg, kind) + value_dim(cfg, kind)) * BF16


def reference_weights(params) -> dict:
    """The program's parameter tree (leading dense layers ``dense_<i>``; ``layers/layer_<j>``: the ``j``-th layer
    of a period, its leaves stacked over the periods) under the names the plain reference reads. Relabelling,
    but for the norms: the program keeps a norm's weight as its OFFSET FROM ONE (drawn off zero, so that none
    sits at a constant; ``1 + scale`` in float32 where it is used), and the reference's ``g`` is that sum, made
    here in float32 (a few vectors of the hidden width). Every other array is the program's own, whatever its
    dtype and placement."""
    import jax.numpy as jnp

    def weight(norm):
        return 1.0 + norm["scale"].astype(jnp.float32)

    def mixer(layer):
        a = layer["attn"]
        out = {"norm1": weight(layer["attn_norm"]), "norm2": weight(layer["mlp_norm"]), "wq": a["wq"]["kernel"],
               "wk": a["wk"]["kernel"], "wv": a["wv"]["kernel"], "wo": a["wo"]["kernel"]}
        if "sink" in a:
            out["sink"] = a["sink"]
        return out

    def dense(layer):
        mlp = layer["mlp"]
        return dict(mixer(layer), w_gate=mlp["w_gate"]["kernel"], w_up=mlp["w_up"]["kernel"],
                    w_down=mlp["w_down"]["kernel"])

    def routed(layer):
        moe = layer["moe"]
        return dict(mixer(layer), router=moe["gate"]["wg"]["kernel"], router_bias=moe["gate"]["e_bias"],
                    w_gate=moe["experts"]["w_gate"], w_up=moe["experts"]["w_up"], w_down=moe["experts"]["w_down"])

    by_number = lambda keys: sorted(keys, key=lambda k: int(k.rpartition("_")[2]))  # noqa: E731
    stack = params["layers"]
    return {"embed": params["embed"]["embedding"], "head": params["lm_head"]["kernel"],
            "final_norm": weight(params["final_norm"]),
            "dense": [dense(params[k]) for k in by_number(k for k in params if k.startswith("dense_"))],
            "period": [routed(stack[k]) for k in by_number(stack)]}


def put_with_picks(engine, uids, fed):
    """``engine.put`` itself, and the picks its compiled step wrote beside the
    logits: ``picks[i]`` int32 ``[len(fed[i]), routed_layers, k]``, in the
    router's numbering (every chip's experts)."""
    return engine.put_with_picks(uids, fed)


def generate_with_picks(engine, prompts, max_new_tokens):
    """``engine.generate`` itself, and the picks its fused prefill and decode
    chains wrote beside the tokens, fetched after it has returned."""
    return engine.generate_with_picks(prompts, max_new_tokens=max_new_tokens)


# --- what the mechanisms' work costs, by the mathematics alone ----------------

def _shape(cfg: dict, kind: str) -> dict:
    return dict(heads=heads(cfg), kv_heads=kv_heads(cfg, kind), key_dim=head_dim(cfg, kind),
                value_dim=value_dim(cfg, kind), sink=has_sink(cfg, kind))


def paged_decode_cost(cfg: dict, kind: str, keys_read: float, row_steps: float, calls: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer of ``kind``'s single-token attention: ``keys_read`` the sum over rows and steps
    of the keys a query sees (``min(position + 1, sliding_window)`` in a sliding layer, ``position + 1`` in a
    global one), ``row_steps`` the (row, step) pairs, ``calls`` the steps (``lib/two_width.py::decode_cost``)."""
    return two_width.decode_cost(keys_read, row_steps, calls, **_shape(cfg, kind))


def swa_decode_cost(cfg: dict, contexts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE sliding layer's attention of one decode step a row, the rows at the given contexts
    (the query's own position counted): the keys' and values' part alone, as ``lib/swa.py`` scales it by a
    chain's ``ring_tokens``."""
    seen = sum(min(int(c), window(cfg)) for c in contexts)
    shape = _shape(cfg, "sliding")
    return (2.0 * shape["heads"] * (shape["key_dim"] + shape["value_dim"]) * seen,
            float(seen * cache_bytes_per_token_layer(cfg, "sliding")))


def swa_prefill_cost(cfg: dict, prompts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE sliding layer's attention over fresh prompts: THE BAND'S WORK and not the square's."""
    return two_width.prefill_cost(prompts, window(cfg), **_shape(cfg, "sliding"))


def full_prefill_cost(cfg: dict, prompts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE global layer's attention over fresh prompts: a query at ``t`` attends ``t + 1`` keys."""
    return two_width.prefill_cost(prompts, None, **_shape(cfg, "global"))


def routed_decode_cost(cfg: dict, experts_read: float, token_steps: float,
                       layer_steps: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of the routed feed-forward layers of decode steps, as THIS CHIP's share of them:
    ``experts_read`` the sum over steps and routed layers of the DISTINCT HELD experts the live rows picked (each
    read once a step), ``token_steps`` the live rows summed over steps and routed layers, ``layer_steps`` the
    (step, routed layer) pairs. Beside the experts each pair reads the router (all its columns) once; a token
    does the router's products and those of its visits to HELD experts, ``experts_per_token`` over the chips
    that share a layer on average. No shared expert."""
    expert, router = expert_params(cfg), cfg["hidden_size"] * routed_experts(cfg)
    visits = cfg["num_experts_per_tok"] * held_experts(cfg) / routed_experts(cfg)
    flops = 2.0 * token_steps * (visits * expert + router)
    return flops, (experts_read * expert + layer_steps * router) * BF16


def one_class_pages(cfg: dict, contexts: Iterable[int], block_size: int) -> int:
    """Pages the rows at the given contexts would hold if every layer kept a page a block of positions."""
    return layers(cfg) * sum(-(-int(c) // block_size) for c in contexts)


def two_class_pages(cfg: dict, contexts: Iterable[int], block_size: int) -> Tuple[int, int]:
    """(global pages, ring pages) they hold in two classes: the global layers' as many as the context has, the
    sliding layers' a ring of ``ceil(window / block) + 1`` at most."""
    ring = -(-window(cfg) // block_size) + 1
    blocks = [-(-int(c) // block_size) for c in contexts]
    return full_layers(cfg) * sum(blocks), sliding_layers(cfg) * sum(min(b, ring) for b in blocks)


def page_bytes(cfg: dict, kind: str, block_size: int) -> int:
    """One page of one layer of ``kind``: ``block_size`` slots of a token's key and value (80 KiB a ring page,
    40 KiB a global one at the published widths and 16 slots)."""
    return block_size * cache_bytes_per_token_layer(cfg, kind)
