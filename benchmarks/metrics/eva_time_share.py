"""Device time under the program's scope ``eva`` (the rotary embedding, the
write of the new rows, the decode kernel or the chunk's windows, a window's
closing into summaries) in the two serving programs over device busy time."""

from benchmarks.lib import eva, routed


def read(run, trace):
    seconds = routed.seconds_under(run, trace, (eva.EVA_SCOPE,))
    return 100.0 * seconds / trace.busy_s if seconds else None
