"""Median duration of the ``dstpu:data`` span (input placement) per step."""

from benchmarks.lib import spans, stats


def read(run, trace):
    seconds = [s.seconds for s in spans.named(spans.of_run(run), "data")]
    return 1e3 * stats.median(seconds) if seconds else None
