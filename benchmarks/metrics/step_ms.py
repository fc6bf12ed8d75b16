"""Median host time of one train_batch call, ended by block_until_ready on its loss."""

from benchmarks.lib import stats


def read(run, trace):
    return 1e3 * stats.median(run["step_s"]) if run["step_s"] else None
