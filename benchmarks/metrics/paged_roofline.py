"""Least time by the roofline to attend over the context of every row of every traced
decode step (every layer reads its own keys and values) over the paged kernel's
device time in the decode-chain program."""

from benchmarks.lib import costs, kernels, peaks


def read(run, trace):
    seconds = kernels.paged_seconds(run, trace)
    traced = [c for c in run["calls"] if c["kind"] == "decode_chain" and c["traced"]]
    if not seconds or not traced:
        return None
    cfg, peak = run["config"], peaks.device_peaks(run["device_kind"])
    heads = cfg["num_attention_heads"]
    flops, bytes_ = costs.paged_decode_cost(
        sum(c["context_tokens"] for c in traced), sum(c["row_steps"] for c in traced),
        heads, heads, costs.head_dim(cfg))
    least, bound = costs.roofline_seconds(flops, bytes_, peak)
    least *= cfg["num_hidden_layers"]
    print(f"paged roofline: {len(traced)} traced chains, least {least:.4f} s ({bound}-bound), "
          f"kernel {seconds:.4f} s")
    return 100.0 * least / seconds
