"""Telling the program's Pallas kernels apart in a trace. Since PR 25 every
``pallas_call`` of the program carries ``name=``, so a kernel is an
instruction of its own name (``%paged_attn.12 = ... custom-call(...)``) and
the readers pick it by that name: no shape, so no key of any configuration.
In a trace of a program without such names they find nothing."""

from __future__ import annotations

from benchmarks.lib import xplane

PREFILL_PROGRAM = "step"   # the jitted functions' own names in engine_v2.py,
CHAIN_PROGRAM = "chain"    # which is all the trace knows them by
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
PAGED_KERNEL = "paged_attn"


def kernel_name(op) -> str:
    """``paged_attn`` for the instruction ``%paged_attn.12``; "" for an
    instruction that is no Pallas kernel."""
    if xplane.PALLAS_TARGET not in op.text:
        return ""
    return xplane.split_instruction(op.text)[0].partition(".")[0]


def flash_seconds(run, trace) -> float:
    """Device seconds of the flash forward and backward kernels."""
    return trace.op_seconds(lambda op: kernel_name(op) in FLASH_KERNELS)


def paged_seconds(run, trace) -> float:
    """Device seconds of the paged decode kernel in the decode-chain program."""
    return trace.op_seconds(
        lambda op: op.module == CHAIN_PROGRAM and kernel_name(op) == PAGED_KERNEL)
