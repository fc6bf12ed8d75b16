"""Least time by the roofline for the routed layers of the traced decode
chains (each expert that a step's live rows picked read once, the shared
expert and the router once a step and layer, the visits' products; the
architecture file's ``routed_decode_cost``) over the device time under the
scopes ``moe_experts`` + ``moe_shared`` + ``moe_router`` in the chain program."""

from benchmarks.lib import costs, harness, kernels, peaks, routed


def read(run, trace):
    arch, cfg = run["architecture"], run["config"]
    if not hasattr(arch, "routed_decode_cost"):
        return None
    seconds = routed.seconds_under(run, trace, routed.MOE_PARTS, (kernels.CHAIN_PROGRAM,))
    experts, tokens, pairs = routed.decode_totals(run, arch.routed_layers(cfg))
    if not seconds or not pairs:
        return None
    flops, bytes_ = arch.routed_decode_cost(cfg, experts, tokens, pairs)
    least, bound = costs.roofline_seconds(flops, bytes_, peaks.device_peaks(run["device_kind"]))
    harness.say(moe_experts_roofline_least_s=least, bound=bound, scopes_s=seconds,
                experts_read=experts, token_steps=tokens, layer_steps=pairs)
    return 100.0 * least / seconds
