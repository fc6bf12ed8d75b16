"""Device time under the program's scope ``optimizer`` (clip, update, apply,
the 16-bit recast) over device busy time."""

from benchmarks.lib import scopes


def read(run, trace):
    seconds = scopes.seconds_under(run, trace, "optimizer")
    return 100.0 * seconds / trace.busy_s if seconds else None
