"""Device time of the flash forward and backward kernels over device busy time."""

from benchmarks.lib import kernels


def read(run, trace):
    seconds = kernels.flash_seconds(run, trace)
    return 100.0 * seconds / trace.busy_s if seconds else None
