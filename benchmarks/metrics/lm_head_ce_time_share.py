"""Device time under the program's scope ``lm_head_ce`` (head matmul and
cross-entropy, forward and transpose) over device busy time."""

from benchmarks.lib import scopes


def read(run, trace):
    seconds = scopes.seconds_under(run, trace, "lm_head_ce")
    return 100.0 * seconds / trace.busy_s if seconds else None
