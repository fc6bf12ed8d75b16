"""Least time by the roofline for the index scores of the traced window's
prefills (the architecture file's ``dsa_index_cost``: a query at position ``t``
scores ``t + 1`` cached keys, in every layer) over the device time of the
kernel ``dsa_index`` in those calls' own runs (``lib/dsa.py`` pairs them)."""

from benchmarks.lib import dsa


def read(run, trace):
    return dsa.roofline_share(run, "dsa_index_cost", "index_s")
