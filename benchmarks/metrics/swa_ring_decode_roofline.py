"""Least time by the roofline for the sliding layers' attention of the traced
window's whole decode chains (the architecture file's ``paged_decode_cost``: 8
kv heads, keys of 192 beside values of 128 counted apart and read once a
key-value head, a sink a head, a ring whose query sees ``min(position + 1,
window)`` keys: ``ring_tokens`` on the chain's ``serve:dispatch`` span) over
the device time of the kernel ``swa_paged_attn`` in those chains' own runs."""

from benchmarks.lib import two_width


def read(run, trace):
    return two_width.decode_roofline(run, "sliding")
