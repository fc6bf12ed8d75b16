"""Operations and bytes that the algorithms need, from shapes alone.

These are the yardstick for utilisation and roofline shares, so they count
what the mathematics requires and nothing an implementation adds: no
recomputation, no padding, no masked-out half of a causal score matrix.
Nothing here reads a configuration: layers, heads, head size and parameter
counts come in as numbers, from the configuration's architecture file
(``benchmarks/architectures/<architecture>.py``).
"""

from __future__ import annotations

from typing import Tuple

from benchmarks.lib.peaks import Peaks


def attention_flops_per_token(layers: int, heads: int, dim: int, seq: int,
                              causal: bool = True) -> float:
    """Forward score and value products of all layers, per token of a
    sequence of ``seq``: 2*seq*heads*dim each, halved by the causal mask."""
    return layers * 4.0 * seq * heads * dim * (0.5 if causal else 1.0)


def train_flops_per_token(matmul_params: int, layers: int, heads: int, dim: int, seq: int) -> float:
    """Forward plus backward (twice the forward), per trained token;
    ``matmul_params`` are the parameters a token meets in a matrix product."""
    return 3.0 * (2.0 * matmul_params + attention_flops_per_token(layers, heads, dim, seq))


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
