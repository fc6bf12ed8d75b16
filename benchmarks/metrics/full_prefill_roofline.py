"""Least time by the roofline for the full layers' attention of the traced
window's prefills (the architecture file's ``full_prefill_cost``: a query at
``t`` attends ``t + 1`` keys) over the device time of the kernel ``flash_fwd``
in those calls' own runs, in a program that has sliding layers beside it."""

from benchmarks.lib import swa


def read(run, trace):
    return swa.prefill_roofline(run, "full_prefill_cost", swa.FULL_PREFILL_KERNEL, "full_layers")
