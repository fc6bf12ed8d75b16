"""What the benchmark knows about how the PROGRAM lays ``evabyte`` out and how
the architecture's work is counted: its parameter tree under the names
``benchmarks/reference/evabyte.py`` reads, which keys of a published config
are widths, parameter counts and attention shapes from such a config, and what
EVA attention's three pieces of work cost by the mathematics alone: a decode
step's read of summaries and window rows, a prompt's windows computed at once,
and a window's closing into summaries.
"""

from __future__ import annotations

from typing import Sequence, Tuple

# never cut: a configuration whose ``reduced`` names one of these is refused
WIDTH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
              "window_size", "chunk_size", "num_pred_heads", "vocab_size")
BF16 = 2


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]


def kv_heads(cfg: dict) -> int:
    return cfg.get("num_key_value_heads") or cfg["num_attention_heads"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_params(cfg: dict) -> int:
    """Four attention projections, the GLU's three, two norms, phi and mu."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    kv = kv_heads(cfg) * head_dim(cfg)
    return 2 * h * h + 2 * h * kv + 3 * h * f + 2 * h + 2 * kv


def matmul_params(cfg: dict) -> int:
    """What one token meets in a matrix product: every layer's projections
    and GLU, and head 0 of the output head (the one serving computes)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    kv = kv_heads(cfg) * head_dim(cfg)
    return layers(cfg) * (2 * h * h + 2 * h * kv + 3 * h * f) + h * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    return layers(cfg) * layer_params(cfg) + h + V * h + h * V * cfg["num_pred_heads"]


def row_bytes(cfg: dict) -> int:
    """One cache row of one layer, an exact token's or a summary's alike: K and V."""
    return 2 * kv_heads(cfg) * head_dim(cfg) * BF16


def reference_weights(params) -> dict:
    """The program's (scan-stacked) parameter tree under the names
    ``benchmarks/reference/evabyte.py`` reads. Relabelling only: the arrays
    are the program's own, whatever their dtype and placement."""
    stack, attn, mlp = params["layers"], params["layers"]["attn"], params["layers"]["mlp"]
    return {
        "embed_in": params["embed"]["embedding"], "head": params["lm_head"]["kernel"],
        "final_norm": params["final_norm"]["scale"],
        "layers": {
            "norm1": stack["attn_norm"]["scale"], "norm2": stack["mlp_norm"]["scale"],
            "wq": attn["wq"]["kernel"], "wk": attn["wk"]["kernel"], "wv": attn["wv"]["kernel"],
            "wo": attn["wo"]["kernel"], "phi": attn["phi"], "mu": attn["mu"],
            "w_gate": mlp["w_gate"]["kernel"], "w_up": mlp["w_up"]["kernel"],
            "w_down": mlp["w_down"]["kernel"],
        },
    }


# --- what EVA attention's work costs, by the mathematics alone -------------

def attended_rows(cfg: dict, position: int) -> int:
    """Rows the token at ``position`` attends to: one summary a chunk of every
    window before its own, and its own window's exact rows up to itself."""
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    return position // window * (window // chunk) + position % window + 1


def eva_decode_cost(cfg: dict, attended: float, row_steps: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's single-token EVA attention: ``attended``
    the sum over every row of every decode step of the cache rows it reads
    (``attended_rows``), ``row_steps`` the number of such rows. A cache row
    costs two products a head and one read of its K and V; each query brings
    itself and takes its output."""
    H, d = heads(cfg), head_dim(cfg)
    flops = 4.0 * attended * H * d
    bytes_ = attended * row_bytes(cfg) + 2.0 * row_steps * H * d * BF16
    return flops, bytes_


def eva_prefill_cost(cfg: dict, lengths: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's EVA attention over prompts of ``lengths``
    computed whole: every (query, key) pair the mask keeps, which is the
    causal half of each window plus each later window's queries against the
    summaries before it, two products a head a pair; plus forming the
    summaries (``eva_close_cost``). Reads q, k, v and writes the output once."""
    H, d = heads(cfg), head_dim(cfg)
    window = cfg["window_size"]
    pairs = tokens = 0.0
    closed = 0
    for n in lengths:
        full, rest = divmod(int(n), window)
        per_window = window * (window + 1) / 2.0
        pairs += full * per_window + rest * (rest + 1) / 2.0
        # window w's queries see w * (window / chunk) summaries
        per = window // cfg["chunk_size"]
        pairs += per * (window * full * (full - 1) / 2.0 + rest * full)
        tokens += n
        closed += full
    close_flops, close_bytes = eva_close_cost(cfg, closed)
    flops = 4.0 * pairs * H * d + close_flops
    bytes_ = tokens * (2 * H + 2 * kv_heads(cfg)) * d * BF16
    return flops, bytes_


def eva_close_cost(cfg: dict, windows: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's closing of ``windows`` windows: a key's
    product with phi, and the weighted sums of keys and values (two flops a
    number each); the window's exact rows are read, its summaries written."""
    window, per = cfg["window_size"], cfg["window_size"] // cfg["chunk_size"]
    kv = kv_heads(cfg) * head_dim(cfg)
    flops = windows * window * kv * (2.0 + 4.0)
    bytes_ = windows * (window + per) * row_bytes(cfg)
    return flops, bytes_
