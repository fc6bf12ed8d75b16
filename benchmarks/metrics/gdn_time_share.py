"""Device time under the program's scope ``gdn`` (the Gated DeltaNet mixers
whole: the projections, the convolution, the chunked delta rule or the
one-token update, the gated norm) in the two serving programs over device busy
time."""

from benchmarks.lib import paired

GDN_SCOPE = "gdn"


def read(run, trace):
    seconds = paired.seconds(run, trace, GDN_SCOPE)
    return 100.0 * seconds / trace.busy_s if seconds else None
