"""Least time by the roofline for the Gated DeltaNet mixers of the traced
window's decode chains (the architecture file's ``gdn_decode_cost``: a live
row's state and convolution tail read once and written once a layer, at the
chains' ``state_rows`` from their ``serve:dispatch`` spans, and the mixers'
weights read once a step) over the device time under the WHOLE scope ``gdn`` in
those chains' own runs (``lib/paired.py`` pairs them, so that a chain half
inside the window is on neither side). Over the whole scope and not
``gdn_update`` alone: what prepares the kernel's operands and the gated norm
ride in fusions of their roots' names, and seconds lost to a sibling name would
read as a share over 100. It reads low by construction."""

from benchmarks.lib import costs, harness, paired, peaks

GDN_SCOPE = "gdn"


def read(run, trace):
    arch, cfg = run["architecture"], run["config"]
    if not hasattr(arch, "gdn_decode_cost"):
        return None
    chains = paired.paired_chains(run, GDN_SCOPE)
    seconds = sum(c["scope_s"] for c in chains)
    if not seconds:
        return None
    flops, bytes_ = arch.gdn_decode_cost(cfg, sum(c["state_rows"] for c in chains),
                                         sum(c["steps"] for c in chains))
    least, bound = costs.roofline_seconds(flops, bytes_, peaks.device_peaks(run["device_kind"]))
    harness.say(gdn_decode_roofline_least_s=least, bound=bound, scope_s=seconds, chains=len(chains))
    return 100.0 * least / seconds
