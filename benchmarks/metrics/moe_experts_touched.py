"""Median over the traced decode chains of ``experts_touched`` on the
``dstpu:serve:accept`` spans: how many distinct experts the live rows of a
step picked in a routed layer, the mean over the chain's steps and layers:
what a decode step has to read of a layer's experts."""

from benchmarks.lib import routed, stats


def read(run, trace):
    touched = [c["experts_touched"] for c in routed.chains(run)]
    return stats.median(touched) if touched else None
