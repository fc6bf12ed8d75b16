"""What the benchmark knows about how the PROGRAM lays ``granitemoehybrid`` out
and how the architecture's work is counted: its parameter tree under the names
``benchmarks/reference/granitemoehybrid.py`` reads, which keys of a published
config are widths, parameter counts and attention shapes from such a config,
and what the state-space layers cost by the mathematics alone
(``ssm_decode_cost`` a decode step, ``ssd_scan_cost`` a chunked layer-call).
The dense variant only (``num_local_experts`` 0): it says nothing of routing.
"""

from __future__ import annotations

from typing import Tuple

BF16, F32 = 2, 4

# never cut: a configuration whose ``reduced`` names one of these is refused
WIDTH_KEYS = ("hidden_size", "intermediate_size", "shared_intermediate_size", "num_attention_heads",
              "num_key_value_heads", "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
              "mamba_expand", "mamba_n_groups", "mamba_chunk_size")


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def ssm_layers(cfg: dict) -> int:
    return list(cfg["layer_types"]).count("mamba")


def attention_layers(cfg: dict) -> int:
    return list(cfg["layer_types"]).count("attention")


def heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]


def kv_heads(cfg: dict) -> int:
    return cfg["num_key_value_heads"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _ssm_sizes(cfg: dict) -> Tuple[int, int, int]:
    """(inner width ``H P``, the convolution's channels ``H P + 2 G N``, the
    in-projection's columns ``[z | xBC | dt]``)."""
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    conv = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return inner, conv, inner + conv + cfg["mamba_n_heads"]


def ssm_matmul_params(cfg: dict) -> int:
    """One mixer's two projections."""
    inner, _, proj = _ssm_sizes(cfg)
    return cfg["hidden_size"] * (proj + inner)


def ssm_params(cfg: dict) -> int:
    """One mixer whole: the projections, the convolution and its bias,
    ``A_log``, ``dt_bias``, ``D`` a head, the gated norm."""
    inner, conv, _ = _ssm_sizes(cfg)
    return ssm_matmul_params(cfg) + (cfg["mamba_d_conv"] + 1) * conv + 3 * cfg["mamba_n_heads"] + inner


def attention_params(cfg: dict) -> int:
    h, d = cfg["hidden_size"], head_dim(cfg)
    return 2 * h * d * (heads(cfg) + kv_heads(cfg))


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def matmul_params(cfg: dict) -> int:
    """What one token meets in a matrix product: every mixer's projections,
    every MLP, and the (tied) head."""
    return (ssm_layers(cfg) * ssm_matmul_params(cfg) + attention_layers(cfg) * attention_params(cfg)
            + layers(cfg) * mlp_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    embed = (1 if cfg.get("tie_word_embeddings") else 2) * cfg["vocab_size"] * h
    return (ssm_layers(cfg) * ssm_params(cfg) + attention_layers(cfg) * attention_params(cfg)
            + layers(cfg) * (mlp_params(cfg) + 2 * h) + embed + h)


def reference_weights(params) -> dict:
    """The program's parameter tree (``layers/layer_<j>``: the ``j``-th layer
    of a period, its leaves stacked over the periods) under the names the plain
    reference reads. Relabelling only: the arrays are the program's own,
    whatever their dtype and placement."""

    def one(layer):
        mlp = layer["mlp"]
        out = {"norm2": layer["mlp_norm"]["scale"], "w_gate": mlp["w_gate"]["kernel"],
               "w_up": mlp["w_up"]["kernel"], "w_down": mlp["w_down"]["kernel"]}
        if "ssm" in layer:
            m = layer["ssm"]
            return dict(out, norm1=layer["ssm_pre_norm"]["scale"], w_in=m["ssm_in_proj"]["kernel"],
                        conv_w=m["ssm_conv"]["kernel"], conv_b=m["ssm_conv"]["bias"], A_log=m["A_log"],
                        dt_bias=m["dt_bias"], D=m["D"], norm_w=m["ssm_norm"]["scale"],
                        w_out=m["ssm_out_proj"]["kernel"])
        a = layer["attn"]
        return dict(out, norm1=layer["attn_norm"]["scale"], wq=a["wq"]["kernel"], wk=a["wk"]["kernel"],
                    wv=a["wv"]["kernel"], wo=a["wo"]["kernel"])

    stack = params["layers"]
    period = [one(stack[k]) for k in sorted(stack, key=lambda k: int(k.rpartition("_")[2]))]
    return {"embed": params["embed"]["embedding"], "final_norm": params["final_norm"]["scale"],
            "period": period}


# --- what the state-space layers' work costs, by the mathematics alone --------

def state_bytes(cfg: dict) -> int:
    """What one sequence keeps in one state-space layer: the float32 state a
    head ``[P, N]`` and the convolution's last ``K - 1`` inputs in bf16."""
    _, conv, _ = _ssm_sizes(cfg)
    return (cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"] * F32
            + (cfg["mamba_d_conv"] - 1) * conv * BF16)


def ssm_decode_cost(cfg: dict, state_rows: float, steps: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of the state-space mixers of decode steps: ``state_rows``
    is the sum over the steps of the rows live at each, ``steps`` their number.
    A live row's state and convolution tail are read once and written once in
    every state-space layer; the mixers' weights (bf16) are read once a step.
    FLOPs: the two projections a row, and of the recurrence a head ``P N``
    elements' decay, outer product, add and the read-out ``S C`` (6 each)."""
    n = ssm_layers(cfg)
    bytes_ = state_rows * n * 2 * state_bytes(cfg) + steps * n * ssm_params(cfg) * BF16
    flops = state_rows * n * (2 * ssm_matmul_params(cfg)
                              + 6 * cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"])
    return float(flops), float(bytes_)


def ssd_scan_cost(cfg: dict, rows: float, tokens: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer-call of the chunked scan over ``rows``
    sequences of ``tokens`` tokens (whole chunks of ``mamba_chunk_size``, the
    last one as long as is left), between the convolution and the gated norm.
    A chunk of ``Q`` tokens: the scores ``C B^T`` a group (``2 Q^2 N``, the
    causal half counted: ``Q^2 N``), a head the masked product with ``x``
    (``Q^2 P``), the chunk's own state (``2 Q P N``) and the read-out of the
    state it started from (``2 Q P N``). Bytes: ``x``, ``B``, ``C`` and ``dt``
    read and ``y`` written in bf16 (``dt`` float32), and the state a row read
    and written once."""
    H, P, G, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"], cfg["mamba_d_state"]
    chunk = cfg["mamba_chunk_size"]
    flops = 0.0
    left = tokens
    while left > 0:
        q = min(chunk, left)
        flops += G * q * q * N + H * (q * q * P + 4 * q * P * N)
        left -= q
    bytes_ = tokens * (2 * H * P * BF16 + 2 * G * N * BF16 + H * F32) + 2 * H * P * N * F32
    return rows * flops, rows * float(bytes_)
