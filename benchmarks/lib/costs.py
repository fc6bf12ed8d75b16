"""Operations and bytes that the algorithms need, from shapes alone.

These are the yardstick for utilisation and roofline shares, so they count
what the mathematics requires and nothing an implementation adds: no
recomputation, no padding, no masked-out half of a causal score matrix.
``cfg`` is a published ``gpt_neox`` config dict (``benchmarks/configs``).
"""

from __future__ import annotations

from typing import Tuple

from benchmarks.lib.peaks import Peaks


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def matmul_params(cfg: dict) -> int:
    """Parameters that sit in a matrix multiplication once per token: the
    four attention projections and the two MLP matrices of every layer, and
    the output head. The embedding table is a lookup and is left out."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * h * h + 2 * h * f) + h * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    h, f, L, V = (cfg["hidden_size"], cfg["intermediate_size"],
                  cfg["num_hidden_layers"], cfg["vocab_size"])
    per_layer = 4 * h * h + 4 * h + 2 * h * f + f + h + 4 * h  # weights, biases, two norms
    return L * per_layer + 2 * h + (1 if cfg.get("tie_word_embeddings") else 2) * V * h


def attention_flops_per_token(cfg: dict, seq: int, causal: bool = True) -> float:
    """Forward score and value products of all layers, per token of a
    sequence of ``seq``: 2*seq*h each, halved by the causal mask."""
    per_layer = 4.0 * seq * cfg["hidden_size"] * (0.5 if causal else 1.0)
    return cfg["num_hidden_layers"] * per_layer


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward), per trained token."""
    return 3.0 * (2.0 * matmul_params(cfg) + attention_flops_per_token(cfg, seq))


def flash_forward_cost(batch: int, heads: int, seq: int, dim: int, itemsize: int = 2,
                       causal: bool = True) -> Tuple[float, float]:
    """(FLOPs, bytes) of one causal attention forward: QK^T and PV, reading
    q, k, v and writing the output once."""
    flops = 4.0 * batch * heads * seq * seq * dim * (0.5 if causal else 1.0)
    return flops, 4.0 * batch * heads * seq * dim * itemsize


def flash_backward_cost(batch: int, heads: int, seq: int, dim: int, itemsize: int = 2,
                        causal: bool = True) -> Tuple[float, float]:
    """(FLOPs, bytes) of its backward: five products of the forward's two
    sizes (scores again, dP, dV, dQ, dK; a kernel that forms the scores twice
    gets no credit for the second time), reading q, k, v, o, do and writing
    dq, dk, dv."""
    flops = 10.0 * batch * heads * seq * seq * dim * (0.5 if causal else 1.0)
    return flops, 8.0 * batch * heads * seq * dim * itemsize


def paged_decode_cost(context_tokens: float, rows_steps: float, heads: int, kv_heads: int,
                      dim: int, itemsize: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of single-token attention over paged keys and values:
    ``context_tokens`` is the sum, over every row of every decode step, of the
    positions that row attends to; ``rows_steps`` the number of such rows.
    Each position costs two products per head and one read of its K and V."""
    flops = 4.0 * context_tokens * heads * dim
    bytes_ = 2.0 * context_tokens * kv_heads * dim * itemsize \
        + 2.0 * rows_steps * heads * dim * itemsize
    return flops, bytes_


def roofline_seconds(flops: float, bytes_: float, peaks: Peaks) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_compute = flops / peaks.bf16_flops_per_s
    t_memory = bytes_ / peaks.hbm_bytes_per_s
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
