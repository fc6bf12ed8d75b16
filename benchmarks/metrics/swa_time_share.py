"""Device time under the program's scope ``swa`` (a sliding layer's attention:
the banded flash forward or the paged kernel over the ring, and its page
writes) in the two serving programs over device busy time."""

from benchmarks.lib import swa


def read(run, trace):
    seconds = swa.seconds(run, trace, swa.SWA_SCOPE)
    return 100.0 * seconds / trace.busy_s if seconds else None
