"""Least time by the roofline to read, for every row of every traced decode
step, the cache rows EVA attention reads there (the closed windows' summaries
and the open window: ``attended_rows`` of the chains' ``serve:dispatch``
spans, by the architecture file's ``eva_decode_cost``), every layer its own,
over the paged kernel's device time in the decode-chain program."""

from benchmarks.lib import costs, eva, harness, kernels, peaks


def read(run, trace):
    arch, cfg = run["architecture"], run["config"]
    seconds = kernels.paged_seconds(run, trace)
    chains = eva.chains(run)
    if not seconds or not chains or not hasattr(arch, "eva_decode_cost"):
        return None
    flops, bytes_ = arch.eva_decode_cost(cfg, sum(c["attended_rows"] for c in chains),
                                         sum(c["row_steps"] for c in chains))
    least, bound = costs.roofline_seconds(flops, bytes_, peaks.device_peaks(run["device_kind"]))
    least *= arch.layers(cfg)
    harness.say(eva_paged_roofline_least_s=least, bound=bound, kernel_s=seconds, chains=len(chains))
    return 100.0 * least / seconds
