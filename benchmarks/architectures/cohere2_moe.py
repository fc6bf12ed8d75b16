"""What the benchmark knows about how the PROGRAM lays ``cohere2_moe`` (Command
A+) out and how the architecture's work is counted: its parameter tree under
the names ``benchmarks/reference/cohere2_moe.py`` reads, which keys of a
published config are widths, parameter counts and attention shapes from such a
config, because the architecture is ROUTED how the program's own expert picks
come out of the ``put`` path and of the decode chain (PERF.md, section 7), and
what its mechanisms cost by the mathematics alone: a fresh prompt's attention
under a band (``swa_prefill_cost``) and under the causal mask alone
(``full_prefill_cost``), a decode step's read of a ring of pages
(``swa_decode_cost``), and the routed layers of a decode step as ONE CHIP'S
SHARE reads them (``routed_decode_cost``).

``num_experts`` in a configuration is the number of experts HELD by the chip
(``reduced``); with ``expert_parallel: {size, rank}`` the router scores ``size``
times as many, and the picks are numbered over all of them. ``intermediate_size``
is the width of ONE expert, routed or shared.
"""

from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2

# never cut: a configuration whose ``reduced`` names one of these is refused
WIDTH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
              "num_experts_per_tok", "num_shared_experts", "sliding_window", "layer_switch", "norm_topk_prob",
              "expert_parallel", "rotary_pct")


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def layer_kinds(cfg: dict) -> Tuple[str, ...]:
    kinds = cfg.get("layer_types")
    if kinds is None:
        n = cfg.get("layer_switch", 4)
        kinds = ["full_attention" if (i + 1) % n == 0 else "sliding_attention" for i in range(layers(cfg))]
    return tuple(kinds)


def sliding_layers(cfg: dict) -> int:
    return layer_kinds(cfg).count("sliding_attention")


def full_layers(cfg: dict) -> int:
    return layers(cfg) - sliding_layers(cfg)


def heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]


def kv_heads(cfg: dict) -> int:
    return cfg["num_key_value_heads"]


def head_dim(cfg: dict) -> int:
    return cfg["head_dim"]


def window(cfg: dict) -> int:
    return int(cfg["sliding_window"])


def routed_layers(cfg: dict) -> int:
    """More than 0 says: decide ``correct`` at the program's own expert picks."""
    return cfg["num_hidden_layers"]


def held_experts(cfg: dict) -> int:
    return cfg["num_experts"]


def routed_experts(cfg: dict) -> int:
    """The router's width, the published numbering of the picks: the experts
    held here times the chips that share a layer."""
    return cfg["num_experts"] * int((cfg.get("expert_parallel") or {"size": 1})["size"])


def experts_per_token(cfg: dict) -> int:
    return cfg["num_experts_per_tok"]


def attention_params(cfg: dict) -> int:
    """One attention mixer of either kind: q, k, v, o; no bias, no qk-norm."""
    return cfg["hidden_size"] * head_dim(cfg) * (2 * heads(cfg) + 2 * kv_heads(cfg))


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def shared_params(cfg: dict) -> int:
    """The shared experts: one GLU of ``num_shared_experts`` times an expert's width."""
    return cfg.get("num_shared_experts", 0) * expert_params(cfg)


def _params(cfg: dict, experts: float) -> float:
    """The layers' matrices with ``experts`` routed experts a layer."""
    per_layer = (attention_params(cfg) + cfg["hidden_size"] * routed_experts(cfg) + shared_params(cfg)
                 + experts * expert_params(cfg))
    return layers(cfg) * per_layer


def matmul_params(cfg: dict) -> int:
    """What one token meets in a matrix product HERE: every mixer, the router,
    the shared experts, its own experts' share of this chip (``experts_per_token``
    over the chips that share a layer) and the tied head."""
    here = cfg["num_experts_per_tok"] * held_experts(cfg) / routed_experts(cfg)
    return int(_params(cfg, here) + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    """Every parameter the program holds: the layers (one bias-free LayerNorm
    each: a parallel block), the final norm, the tied embedding once."""
    h = cfg["hidden_size"]
    return int(_params(cfg, held_experts(cfg))) + layers(cfg) * h + h + cfg["vocab_size"] * h


parameter_count = total_params  # (the issue's name for it)


def cache_bytes_per_token_layer(cfg: dict) -> int:
    """A token's key and value in one layer, bf16."""
    return 2 * kv_heads(cfg) * head_dim(cfg) * BF16


def reference_weights(params) -> dict:
    """The program's parameter tree (``layers/layer_<j>``: the ``j``-th layer
    of a period, its leaves stacked over the periods) under the names the plain
    reference reads. Relabelling only: the arrays are the program's own,
    whatever their dtype and placement."""

    def one(layer):
        a, moe = layer["attn"], layer["moe"]
        return {"norm": layer["attn_norm"]["scale"], "wq": a["wq"]["kernel"], "wk": a["wk"]["kernel"],
                "wv": a["wv"]["kernel"], "wo": a["wo"]["kernel"], "router": moe["gate"]["wg"]["kernel"],
                "w_gate": moe["experts"]["w_gate"], "w_up": moe["experts"]["w_up"],
                "w_down": moe["experts"]["w_down"], "shared_gate": moe["shared"]["w_gate"]["kernel"],
                "shared_up": moe["shared"]["w_up"]["kernel"], "shared_down": moe["shared"]["w_down"]["kernel"]}

    stack = params["layers"]
    period = [one(stack[k]) for k in sorted(stack, key=lambda k: int(k.rpartition("_")[2]))]
    return {"embed": params["embed"]["embedding"], "final_norm": params["final_norm"]["scale"], "period": period}


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

def _attended(tokens: int, width) -> int:
    """Sum over the queries ``t = 0 .. tokens - 1`` of the keys each sees:
    ``min(t + 1, width)`` (None: every key up to its own)."""
    if width is None or tokens <= width:
        return tokens * (tokens + 1) // 2
    return width * (width + 1) // 2 + (tokens - width) * width


def _prefill_cost(cfg: dict, prompts: Iterable[int], width) -> Tuple[float, float]:
    H, d = heads(cfg), head_dim(cfg)
    pairs = sum(_attended(int(n), width) for n in prompts)
    tokens = sum(int(n) for n in prompts)
    # q . k and p . v: two products of 2 d FLOPs a (query, key) pair a head
    flops = 4.0 * H * d * pairs
    # q read and o written a query head, k and v read a key-value head, each once
    bytes_ = float(tokens * (2 * H + 2 * kv_heads(cfg)) * d * BF16)
    return flops, bytes_


def swa_prefill_cost(cfg: dict, prompts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE sliding layer's attention over fresh prompts of
    the given lengths: a query at ``t`` attends ``min(t + 1, sliding_window)``
    keys, THE BAND'S WORK and not the square's; q, k, v read and o written
    once."""
    return _prefill_cost(cfg, prompts, window(cfg))


def full_prefill_cost(cfg: dict, prompts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE full layer's attention over fresh prompts: a query
    at ``t`` attends ``t + 1`` keys."""
    return _prefill_cost(cfg, prompts, None)


def swa_decode_cost(cfg: dict, contexts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE sliding layer's attention of one decode step a
    row, the rows at the given contexts (the query's own position counted): a
    row reads ``min(context, sliding_window)`` tokens' keys and values, 2 x kv
    heads x head_dim x 2 B each, once for all the heads of a group."""
    seen = sum(min(int(c), window(cfg)) for c in contexts)
    return 4.0 * heads(cfg) * head_dim(cfg) * seen, float(seen * cache_bytes_per_token_layer(cfg))


def routed_decode_cost(cfg: dict, experts_read: float, token_steps: float,
                       layer_steps: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of the routed feed-forward layers of decode steps, as
    THIS CHIP's share of them: ``experts_read`` the sum over steps and routed
    layers of the DISTINCT HELD experts the live rows picked (each read once a
    step), ``token_steps`` the live rows summed over steps and routed layers,
    ``layer_steps`` the (step, routed layer) pairs. Beside the experts each
    pair reads the shared experts and the router (all its columns) once; a
    token does the router's and the shared experts' products and those of its
    visits to HELD experts, ``experts_per_token`` over the chips that share a
    layer on average."""
    h = cfg["hidden_size"]
    expert = expert_params(cfg)
    always = shared_params(cfg) + h * routed_experts(cfg)
    visits = cfg["num_experts_per_tok"] * held_experts(cfg) / routed_experts(cfg)
    flops = 2.0 * token_steps * (visits * expert + always)
    bytes_ = (experts_read * expert + layer_steps * always) * BF16
    return flops, bytes_


def one_class_pages(cfg: dict, contexts: Iterable[int], block_size: int) -> int:
    """Pages the rows at the given contexts would hold if every layer kept a
    page a block of positions (one class of page)."""
    return layers(cfg) * sum(-(-int(c) // block_size) for c in contexts)


def two_class_pages(cfg: dict, contexts: Iterable[int], block_size: int) -> int:
    """Pages they hold in two classes: the full layers' as many as the context
    has, the sliding layers' a ring of ``ceil(window / block) + 1`` at most."""
    ring = -(-window(cfg) // block_size) + 1
    blocks = [-(-int(c) // block_size) for c in contexts]
    return full_layers(cfg) * sum(blocks) + sliding_layers(cfg) * sum(min(b, ring) for b in blocks)
