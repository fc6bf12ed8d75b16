"""Device time under the program's scope ``attn_full`` (the full layer's
attention beside sliding ones: the causal flash forward or the paged kernel over
the global columns, and its page writes) in the two serving programs over
device busy time."""

from benchmarks.lib import swa


def read(run, trace):
    seconds = swa.seconds(run, trace, swa.FULL_SCOPE)
    return 100.0 * seconds / trace.busy_s if seconds else None
