"""Median device time of one run of the fused prefill program, by module name."""

from benchmarks.lib import kernels, stats


def read(run, trace):
    runs = trace.modules.get(kernels.PREFILL_PROGRAM)
    return 1e3 * stats.median(runs) if runs else None
