"""Least time by the roofline for the layers' weight matmuls over the device
time of the instructions those weights name (``lib/sublayers.py``).

A serving cell (``.batch``), in the decode-chain program: each named weight
read once a layer-step, its bytes from the operand shapes of its own
instruction (``sublayers.weight_bytes``), times the layer-steps the DEVICE ran (the instruction's
occurrences in the trace: no wrapper's count, so no one-chain edge), against
the chip's memory bandwidth. The time side is the named instructions' seconds
AND the layer scan's own slices of the stacked weights: a slice the scan copies
is read from memory by the copy, and the product then reads the copy, so the
products' seconds alone would charge those bytes to an instruction that does
not fetch them (the share over the products alone is printed beside it). The
bytes found a layer must be the architecture file's ``matmul_params`` less
the head to within ``TOLERANCE``, else the names have drifted from the weights
and the metric is left out. A training cell (``.train``): 6 FLOPs a layer
matmul parameter a token of the traced steps, against the bf16 peak, a chip."""

from benchmarks.lib import harness, kernels, peaks, sublayers

TOLERANCE = 0.01  # of the architecture's bytes a layer


def read(run, trace):
    arch, cfg, peak = run["architecture"], run["config"], peaks.device_peaks(run["device_kind"])
    params = sublayers.layer_matmul_params(arch, cfg)
    if not sublayers.is_serving(run):
        seconds = sublayers.seconds_of(run, trace, sublayers.weight_of)
        if not seconds:
            return None
        tokens = (run["traced_steps"] * run["micro_batch"] * run["micro_batches_per_step"]
                  * run["seq_len"])  # a chip
        least = 6.0 * params * tokens / peak.bf16_flops_per_s
        harness.say(layer_matmul_roofline_least_s=least, bound="compute", weights_s=seconds,
                    layer_matmul_params=params, tokens_a_chip=tokens)
        return 100.0 * least / seconds
    traffic = sublayers.weight_traffic(run, trace, arch.layers(cfg))
    seconds = sum(s for s, _, _ in traffic.values()) / trace.n_devices
    if not seconds:
        return None
    a_layer = 0.0
    for weight, (s, bytes_, count) in sorted(traffic.items(), key=lambda kv: -kv[1][0]):
        a_layer += bytes_ / count
        harness.say(sublayer=weight, program="chain", device_s=s / trace.n_devices,
                    share=s / trace.n_devices / trace.busy_s, bytes=bytes_ / count, layer_steps=count,
                    gb_per_s=1e-9 * bytes_ / s)
    least = sum(b for _, b, _ in traffic.values()) / trace.n_devices / peak.hbm_bytes_per_s
    wanted = 2.0 * params / arch.layers(cfg)  # bf16
    slices = sum(i.seconds for i in sublayers.instructions(run) if i.program == kernels.CHAIN_PROGRAM
                 and sublayers.is_scan_slicing(i.op_name)) / trace.n_devices
    harness.say(layer_matmul_roofline_least_s=least, bound="memory", weights_s=seconds,
                found_bytes_a_layer=a_layer, architecture_bytes_a_layer=wanted,
                found_of_architecture=a_layer / wanted, scan_slices_s=slices,
                products_alone_pct=100.0 * least / seconds)
    if abs(a_layer / wanted - 1.0) > TOLERANCE:
        harness.say(layer_matmul_roofline="left_out", why="the_named_weights_are_not_the_architecture_s")
        return None
    return 100.0 * least / (seconds + slices)
