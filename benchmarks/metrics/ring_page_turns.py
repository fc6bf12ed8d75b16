"""Median over the traced decode chains of the ring pages a sliding layer of a
live row starts writing over in the chain (``ring_turns`` over ``live`` on the
``dstpu:serve:dispatch`` spans: the blocks the chain opens past a ring's first
round). 0 would say the cell never wraps a ring."""

from benchmarks.lib import stats, two_width


def read(run, trace):
    turns = two_width.ring_turns(run)
    return stats.median(turns) if turns else None
