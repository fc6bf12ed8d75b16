"""Least time by the roofline for attention over the SELECTED tokens of the
traced window's prefills (the architecture file's ``dsa_attend_cost``: a query
at ``t`` attends ``min(t + 1, index_topk)`` tokens, in every layer) over the
device time of the kernel ``dsa_paged_attn`` in those calls' own runs. The
kernel walks every page up to a query under its mask, so at 8k it reads under
half; a form that gathers the kept tokens would read more."""

from benchmarks.lib import dsa


def read(run, trace):
    return dsa.roofline_share(run, "dsa_attend_cost", "attend_s")
