"""What the benchmark knows about how the PROGRAM lays ``qwen3_next`` out and
how the architecture's work is counted: its parameter tree under the names
``benchmarks/reference/qwen3_next.py`` reads, which keys of a published config
are widths, parameter counts and attention shapes from such a config, because
the architecture is ROUTED how the program's own expert picks come out of the
``put`` path and of the decode chain (PERF.md, section 7), and what its new
mechanisms cost by the mathematics alone: the routed layers of a decode step
as ONE CHIP'S SHARE reads them (``routed_decode_cost``), the Gated DeltaNet
mixers of a decode step (``gdn_decode_cost``) and a chunked layer-call of a
prompt (``gdn_chunk_cost``).

``num_experts`` in a configuration is the number of experts HELD by the chip
(``reduced``); with ``expert_parallel: {size, rank}`` the router scores ``size``
times as many, and the picks are numbered over all of them.
"""

from __future__ import annotations

from typing import Tuple

BF16, F32 = 2, 4

# never cut: a configuration whose ``reduced`` names one of these is refused
WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "shared_expert_intermediate_size",
              "num_attention_heads", "num_key_value_heads", "head_dim", "linear_conv_kernel_dim",
              "linear_key_head_dim", "linear_num_key_heads", "linear_num_value_heads", "linear_value_head_dim",
              "num_experts_per_tok", "partial_rotary_factor", "full_attention_interval", "norm_topk_prob",
              "expert_parallel")


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def attention_layers(cfg: dict) -> int:
    return sum((i + 1) % cfg["full_attention_interval"] == 0 for i in range(layers(cfg)))


def gdn_layers(cfg: dict) -> int:
    return layers(cfg) - attention_layers(cfg)


def heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]


def kv_heads(cfg: dict) -> int:
    return cfg["num_key_value_heads"]


def head_dim(cfg: dict) -> int:
    return cfg["head_dim"]


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


def _gdn_sizes(cfg: dict) -> Tuple[int, int, int]:
    """(keys' width ``Hk Dk``, values' width ``Hv Dv``, the convolution's channels ``[q | k | v]``)."""
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return keys, values, 2 * keys + values


def gdn_matmul_params(cfg: dict) -> int:
    """One DeltaNet mixer's three projections."""
    keys, values, conv = _gdn_sizes(cfg)
    return cfg["hidden_size"] * (conv + values + 2 * cfg["linear_num_value_heads"] + values)


def gdn_params(cfg: dict) -> int:
    """One mixer whole: the projections, the convolution, ``A_log``,
    ``dt_bias`` a value head, the gated norm."""
    _, _, conv = _gdn_sizes(cfg)
    return (gdn_matmul_params(cfg) + cfg["linear_conv_kernel_dim"] * conv + 2 * cfg["linear_num_value_heads"]
            + cfg["linear_value_head_dim"])


def attention_params(cfg: dict) -> int:
    """One gated attention mixer: ``[q | gate]``, k, v, o and the two head norms."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    return h * d * (3 * heads(cfg) + 2 * kv_heads(cfg)) + 2 * d


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    """The shared expert and its gate."""
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"] + cfg["hidden_size"]


def _params(cfg: dict, experts: int) -> int:
    per_layer = cfg["hidden_size"] * routed_experts(cfg) + experts * expert_params(cfg) + shared_params(cfg)
    return (gdn_layers(cfg) * gdn_params(cfg) + attention_layers(cfg) * attention_params(cfg)
            + layers(cfg) * per_layer)


def matmul_params(cfg: dict) -> int:
    """What one token meets in a matrix product HERE: every mixer, the router,
    the shared expert, its own experts' share of this chip (``experts_per_token``
    over the chips that share a layer) and the output head."""
    here = cfg["num_experts_per_tok"] * held_experts(cfg) / routed_experts(cfg)
    return int(_params(cfg, 0) + layers(cfg) * here * expert_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    return _params(cfg, held_experts(cfg)) + layers(cfg) * 2 * h + h + 2 * cfg["vocab_size"] * h


def reference_weights(params) -> dict:
    """The program's parameter tree (``layers/layer_<j>``: the ``j``-th layer
    of a period, its leaves stacked over the periods) under the names the plain
    reference reads. Relabelling only: the arrays are the program's own,
    whatever their dtype and placement."""

    def one(layer):
        moe = layer["moe"]
        out = {"norm2": layer["mlp_norm"]["scale"], "router": moe["gate"]["wg"]["kernel"],
               "w_gate": moe["experts"]["w_gate"], "w_up": moe["experts"]["w_up"],
               "w_down": moe["experts"]["w_down"], "shared_gate": moe["shared"]["w_gate"]["kernel"],
               "shared_up": moe["shared"]["w_up"]["kernel"], "shared_down": moe["shared"]["w_down"]["kernel"],
               "shared_w": moe["shared_gate"]["kernel"]}
        if "gdn" in layer:
            m = layer["gdn"]
            return dict(out, norm1=layer["gdn_pre_norm"]["scale"], w_qkvz=m["gdn_in_proj"]["kernel"],
                        w_ba=m["gdn_ba_proj"]["kernel"], conv_w=m["gdn_conv"], A_log=m["A_log"],
                        dt_bias=m["dt_bias"], norm_w=m["gdn_norm"]["scale"], w_out=m["gdn_out_proj"]["kernel"])
        a = layer["attn"]
        return dict(out, norm1=layer["attn_norm"]["scale"], wq=a["wq"]["kernel"], wk=a["wk"]["kernel"],
                    wv=a["wv"]["kernel"], q_norm=a["q_norm"]["scale"], k_norm=a["k_norm"]["scale"],
                    wo=a["wo"]["kernel"])

    stack = params["layers"]
    period = [one(stack[k]) for k in sorted(stack, key=lambda k: int(k.rpartition("_")[2]))]
    return {"embed": params["embed"]["embedding"], "head": params["lm_head"]["kernel"],
            "final_norm": params["final_norm"]["scale"], "period": period}


def put_with_picks(engine, uids, fed):
    """``engine.put`` itself, and the picks its compiled step wrote beside the
    logits: ``picks[i]`` int32 ``[len(fed[i]), routed_layers, k]``, in the
    router's numbering (every chip's experts)."""
    return engine.put_with_picks(uids, fed)


def generate_with_picks(engine, prompts, max_new_tokens):
    """``engine.generate`` itself, and the picks its fused prefill and decode
    chains wrote beside the tokens, fetched after it has returned."""
    return engine.generate_with_picks(prompts, max_new_tokens=max_new_tokens)


# --- what the new mechanisms' work costs, by the mathematics alone ------------

def routed_decode_cost(cfg: dict, experts_read: float, token_steps: float,
                       layer_steps: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of the routed feed-forward layers of decode steps, as
    THIS CHIP's share of them: ``experts_read`` the sum over steps and routed
    layers of the DISTINCT HELD experts the live rows picked (each read once a
    step, whoever shares it; the program's ``experts_touched`` counts held
    experts), ``token_steps`` the live rows summed over steps and routed
    layers, ``layer_steps`` the (step, routed layer) pairs. Beside the experts
    each pair reads the shared expert, its gate and the router (all its
    columns) once; a token does the router's and the shared expert's products
    and those of its visits to HELD experts, ``experts_per_token`` over the
    chips that share a layer on average."""
    h = cfg["hidden_size"]
    expert = expert_params(cfg)
    always = shared_params(cfg) + h * routed_experts(cfg)
    visits = cfg["num_experts_per_tok"] * held_experts(cfg) / routed_experts(cfg)
    flops = 2.0 * token_steps * (visits * expert + always)
    bytes_ = (experts_read * expert + layer_steps * always) * BF16
    return flops, bytes_


def state_bytes(cfg: dict) -> int:
    """What one sequence keeps in one DeltaNet layer: the float32 state a value
    head ``[Dk, Dv]`` and the convolution's last ``K - 1`` inputs in bf16."""
    _, _, conv = _gdn_sizes(cfg)
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"] * F32
            + (cfg["linear_conv_kernel_dim"] - 1) * conv * BF16)


def gdn_decode_cost(cfg: dict, state_rows: float, steps: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of the DeltaNet mixers of decode steps: ``state_rows`` is
    the sum over the steps of the rows live at each, ``steps`` their number. A
    live row's state and convolution tail are read once and written once in
    every DeltaNet layer; the mixers' weights (bf16) are read once a step.
    FLOPs: the projections a row, and of the recurrence a value head ``Dk Dv``
    elements' decay, ``S^T k``, the rank-one update and ``S^T q`` (7 each)."""
    n = gdn_layers(cfg)
    bytes_ = state_rows * n * 2 * state_bytes(cfg) + steps * n * gdn_params(cfg) * BF16
    flops = state_rows * n * (2 * gdn_matmul_params(cfg) + 7 * cfg["linear_num_value_heads"]
                              * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"])
    return float(flops), float(bytes_)


def gdn_chunk_cost(cfg: dict, rows: float, tokens: int, chunk: int = 64) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer-call of the chunked delta rule over ``rows``
    sequences of ``tokens`` tokens (whole chunks of ``chunk``, the last one as
    long as is left), between the convolution and the gated norm. A chunk of
    ``Q`` tokens a value head: ``k k^T`` and ``q k^T`` (the causal half of ``2
    Q^2 Dk`` each: ``Q^2 Dk``), the unit-triangular solve for ``[v | k]`` (``Q^2
    (Dv + Dk)``), the corrections against the incoming state and the read-out
    of it (``2 Q Dk Dv`` each), ``q k^T`` times the new values (``Q^2 Dv``), and
    the chunk's own addition to the state (``2 Q Dk Dv``). Bytes: ``q``, ``k``,
    ``v`` read and ``o`` written in bf16, ``g`` and ``beta`` float32, and the
    state a row read and written once."""
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    Dk, Dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    flops = 0.0
    left = tokens
    while left > 0:
        q = min(chunk, left)
        flops += Hv * (2 * q * q * Dk + q * q * (Dv + Dk) + q * q * Dv + 6 * q * Dk * Dv)
        left -= q
    bytes_ = tokens * (2 * Hk * Dk * BF16 + 2 * Hv * Dv * BF16 + 2 * Hv * F32) + 2 * Hv * Dk * Dv * F32
    return rows * flops, rows * float(bytes_)
