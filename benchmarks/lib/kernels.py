"""Telling the program's Pallas kernels apart in a trace. The trace carries
no kernel names (each is a ``custom-call`` to ``tpu_custom_call``, named
after its flax scope), so the readers pick a kernel by its operand shapes."""

from __future__ import annotations

import re

from benchmarks.lib import costs, xplane

PREFILL_PROGRAM = "step"   # the jitted functions' own names in engine_v2.py,
CHAIN_PROGRAM = "chain"    # which is all the trace knows them by


def is_pallas(op) -> bool:
    return xplane.PALLAS_TARGET in op.text


def flash_seconds(run, trace) -> float:
    """Device seconds of the flash forward and backward kernels."""
    cfg = run["config"]
    shape = "bf16[%d,%d,%d,%d]" % (run["micro_batch"], cfg["num_attention_heads"],
                                   run["seq_len"], costs.head_dim(cfg))
    return trace.op_seconds(lambda op: is_pallas(op) and shape in op.text)


def paged_seconds(run, trace) -> float:
    """Device seconds of the paged decode kernel in the decode-chain program."""
    pages = re.compile(r"bf16\[\d+,%d,%d\]" % (run["kv_block_size"], run["config"]["hidden_size"]))
    return trace.op_seconds(
        lambda op: is_pallas(op) and op.module == CHAIN_PROGRAM and pages.search(op.text))
