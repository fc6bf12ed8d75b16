"""Device time under the program's scope ``moe`` (router, routed experts,
shared expert) in the two serving programs over device busy time."""

from benchmarks.lib import routed


def read(run, trace):
    seconds = routed.seconds_under(run, trace, (routed.MOE_SCOPE,))
    return 100.0 * seconds / trace.busy_s if seconds else None
