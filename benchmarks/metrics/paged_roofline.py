"""Least time by the roofline to attend over the context of every row of every traced
decode step (every layer reads its own keys and values) over the paged kernel's
device time in the decode-chain program. Heads, KV heads, head size and layers
are the architecture file's reading of the configuration."""

from benchmarks.lib import costs, kernels, peaks


def read(run, trace):
    seconds = kernels.paged_seconds(run, trace)
    traced = [c for c in run["calls"] if c["kind"] == "decode_chain" and c["traced"]]
    if not seconds or not traced:
        return None
    cfg, arch, peak = run["config"], run["architecture"], peaks.device_peaks(run["device_kind"])
    flops, bytes_ = costs.paged_decode_cost(
        sum(c["context_tokens"] for c in traced), sum(c["row_steps"] for c in traced),
        arch.heads(cfg), arch.kv_heads(cfg), arch.head_dim(cfg))
    least, bound = costs.roofline_seconds(flops, bytes_, peak)
    least *= arch.layers(cfg)
    print(f"paged roofline: {len(traced)} traced chains, least {least:.4f} s ({bound}-bound), "
          f"kernel {seconds:.4f} s")
    return 100.0 * least / seconds
