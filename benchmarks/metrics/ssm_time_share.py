"""Device time under the program's scope ``ssm`` (the state-space mixers
whole: both projections, the convolution, the scan or the one-token update, the
gated norm) in the two serving programs over device busy time."""

from benchmarks.lib import ssm


def read(run, trace):
    seconds = ssm.seconds(run, trace)
    return 100.0 * seconds / trace.busy_s if seconds else None
