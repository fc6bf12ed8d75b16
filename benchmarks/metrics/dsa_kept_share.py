"""Cached tokens the traced window's queries KEPT over the ones they scored,
from the program's counters (``tokens_kept`` and ``tokens_scored`` on the
``dstpu:serve:accept`` spans of its prefills and decode chains, each the mean
over a call's queries and layers, counted off the selection itself and weighed
here by the call's queries): a prompt of 6,144 tokens keeps 56% (its first
2,048 queries keep every candidate), a decode step at 6,200 a third; 100 would
say the selection is off."""

from benchmarks.lib import dsa


def read(run, trace):
    counters = dsa.counters(run)
    scored = sum(n * s for n, s, _ in counters)
    return 100.0 * sum(n * k for n, _, k in counters) / scored if scored else None
