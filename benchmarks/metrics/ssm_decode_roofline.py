"""Least time by the roofline for the state-space mixers of the traced
window's decode chains (the architecture file's ``ssm_decode_cost``: a live
row's state and convolution tail read once and written once a layer, at the
chains' ``state_rows`` from their ``serve:dispatch`` spans, and the mixers'
weights read once a step) over the device time under the WHOLE scope ``ssm`` in
those chains' own runs (``lib/ssm.py`` pairs them, so that a chain half inside
the window is on neither side). Over the whole scope and not ``ssm_update``
alone: a fusion carries its root's ``op_name``, so the state's update may ride
in the instruction that makes ``y`` or the gated norm, and seconds lost to a
sibling name would read as a share over 100."""

from benchmarks.lib import costs, harness, peaks, ssm


def read(run, trace):
    arch, cfg = run["architecture"], run["config"]
    if not hasattr(arch, "ssm_decode_cost"):
        return None
    chains = ssm.paired_chains(run)
    seconds = sum(c["ssm_s"] for c in chains)
    if not seconds:
        return None
    flops, bytes_ = arch.ssm_decode_cost(cfg, sum(c["state_rows"] for c in chains),
                                         sum(c["steps"] for c in chains))
    least, bound = costs.roofline_seconds(flops, bytes_, peaks.device_peaks(run["device_kind"]))
    harness.say(ssm_decode_roofline_least_s=least, bound=bound, scope_s=seconds, chains=len(chains))
    return 100.0 * least / seconds
