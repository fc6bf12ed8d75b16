"""Device time under the program's scopes ``dsa_index`` (the indexer's products
and scores), ``dsa_select`` (the choice of the kept tokens) and ``dsa_attend``
(attention over them) in the two serving programs over device busy time."""

from benchmarks.lib import dsa


def read(run, trace):
    seconds = dsa.seconds(run, trace)
    return 100.0 * seconds / trace.busy_s if seconds else None
