"""Median wall time of ONE decode step over every decode chain of the run
(``lib/swa.py::decode_step_ms``: the benchmark's own span around
``engine.decode_chain``, kept from the window's first call to its last and not
only under the profiler): a step over a ring of 257 pages a sliding layer, a
global table and the held experts its rows picked. The window's own readers of
the chain (``decode_chain_ms``, the unlisted ``swa_decode_roofline``) see it
only where the three traced seconds catch a chain, which five prefills of 0.6 s
can fill; this one reads every run."""

from benchmarks.lib import swa


def read(run, trace):
    return swa.decode_step_ms(run)
