"""Device time of the paged decode kernel in the decode-chain program over device busy time."""

from benchmarks.lib import kernels


def read(run, trace):
    seconds = kernels.paged_seconds(run, trace)
    return 100.0 * seconds / trace.busy_s if seconds else None
