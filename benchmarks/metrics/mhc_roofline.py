"""Least time by the roofline for the hyper-connections of the traced window's
prefills (their ``serve:dispatch`` spans' live ``tokens``) and decode chains
(their emitted tokens) through every layer (the architecture file's
``mhc_cost``: the streams read twice and written once a sublayer) over the
device time under the scope ``mhc`` in those calls' own runs (``lib/mhc.py``
pairs them, so that a call half inside the window is on neither side)."""

from benchmarks.lib import costs, harness, mhc, peaks


def read(run, trace):
    arch, cfg = run["architecture"], run["config"]
    if not hasattr(arch, "mhc_cost"):
        return None
    calls = mhc.paired_calls(run)
    seconds = sum(c["mhc_s"] for c in calls)
    if not seconds:
        return None
    flops, bytes_ = arch.mhc_cost(cfg, sum(c["tokens"] for c in calls), arch.layers(cfg))
    least, bound = costs.roofline_seconds(flops, bytes_, peaks.device_peaks(run["device_kind"]))
    harness.say(mhc_roofline_least_s=least, bound=bound, scope_s=seconds, calls=len(calls))
    return 100.0 * least / seconds
