"""Least time by the roofline for the global layers' attention of the traced
window's whole decode chains (``paged_decode_cost``: 4 kv heads, groups of 16
query heads, keys of 192 beside values of 128, every key up to the query:
``global_tokens`` on the chain's ``serve:dispatch`` span) over the device time
of the kernel ``paged_attn`` in those chains' own runs, in a program that has
sliding layers beside it."""

from benchmarks.lib import two_width


def read(run, trace):
    return two_width.decode_roofline(run, "global")
