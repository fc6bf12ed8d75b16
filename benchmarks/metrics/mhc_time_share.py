"""Device time under the program's scope ``mhc`` (the hyper-connections' mix,
their read and their write-back around every sublayer) in the two serving
programs over device busy time."""

from benchmarks.lib import mhc


def read(run, trace):
    seconds = mhc.seconds(run, trace)
    return 100.0 * seconds / trace.busy_s if seconds else None
