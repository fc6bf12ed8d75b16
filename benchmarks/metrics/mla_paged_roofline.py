"""Least time by the roofline to attend over the context of every row of
every traced decode step, every layer reading its own latent rows ONCE for all
heads (the architecture file's ``latent_decode_cost``), over the latent paged
kernel's device time in the decode-chain program."""

from benchmarks.lib import costs, harness, peaks, routed


def read(run, trace):
    arch, cfg = run["architecture"], run["config"]
    seconds = routed.mla_seconds(run, trace)
    traced = [c for c in run["calls"] if c["kind"] == "decode_chain" and c["traced"]]
    if not seconds or not traced or not hasattr(arch, "latent_decode_cost"):
        return None
    flops, bytes_ = arch.latent_decode_cost(
        cfg, sum(c["context_tokens"] for c in traced), sum(c["row_steps"] for c in traced))
    least, bound = costs.roofline_seconds(flops, bytes_, peaks.device_peaks(run["device_kind"]))
    least *= arch.layers(cfg)
    harness.say(mla_paged_roofline_least_s=least, bound=bound, kernel_s=seconds, chains=len(traced))
    return 100.0 * least / seconds
