"""Least time by the roofline for the attention forwards and backwards of the traced
steps (one of each per layer and micro-batch; FLOPs and bytes from shapes) over
the flash kernels' device time. Heads, head size and layers are the
architecture file's reading of the configuration."""

from benchmarks.lib import costs, kernels, peaks


def read(run, trace):
    seconds = kernels.flash_seconds(run, trace)
    if not seconds:
        return None
    cfg, arch, peak = run["config"], run["architecture"], peaks.device_peaks(run["device_kind"])
    shape = (run["micro_batch"], arch.heads(cfg), run["seq_len"], arch.head_dim(cfg))
    fwd, bound_f = costs.roofline_seconds(*costs.flash_forward_cost(*shape), peak)
    bwd, bound_b = costs.roofline_seconds(*costs.flash_backward_cost(*shape), peak)
    calls = arch.layers(cfg) * run["micro_batches_per_step"] * run["traced_steps"]
    print(f"flash roofline: {calls} forward+backward pairs, least {1e3 * (fwd + bwd):.4f} ms "
          f"a pair ({bound_f}-bound forward, {bound_b}-bound backward), kernels {seconds:.4f} s")
    return 100.0 * calls * (fwd + bwd) / seconds
