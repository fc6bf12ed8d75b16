#!/usr/bin/env python3
"""A traced run of one benchmark cell through ``benchmarks/run.py``'s own
``main``, with two things the benchmark does not print (since PR 37):

    python3 tools/traced_cell.py --workload glm-4.7-flash.serve.batch --seed <n> --seconds 50 --trace 1

- what tracing costs to read: the size of the ``.xplane.pb`` and the seconds
  xprof's ``hlo_stats`` takes over it, one ``trace_cost=`` line a trace;
- in a serving cell, the three serving readers of the sub-layer names
  (``layer_matmul_time_share.batch``, ``layer_matmul_roofline.batch``,
  ``unnamed_time_share.batch``) whether or not ``BENCHMARK.json`` lists the
  cell for them. The glm and EVA cells are not listed (``PERF.md`` section 7
  says which two pinned counts keep them off the lists), so their readings
  in ``PERF.md`` section 5 are this tool's and no driver's run checks them;
- in a cell of a routed, latent-attention architecture that is not listed for
  them, the five readers of ``benchmarks/lib/routed.py`` (``ROUTED``) likewise;
- in a cell with a routed prefill, the two halves around its grouped matmuls
  by the scopes PR 40 gave them (``moe_dispatch``, ``moe_combine`` under
  ``moe_experts``): a ``moe_half=`` line each (device seconds, layer-calls,
  ms a layer-call) and a ``moe_half_op=`` line for each of its five largest
  instructions, which the ten ``named_op=`` lines of a run are too few to
  reach. No metric reads the two names; ``PERF.md`` section 5 reads these.

- in a cell with state-space layers (an architecture file with
  ``ssd_scan_cost``), the mixer's pieces by the scopes under ``ssm``: an
  ``ssm_part=`` line a program and piece (``ssm_in_proj``, ``ssm_conv``,
  ``ssm_scan`` in a prefill, ``ssm_update`` in a chain, ``ssm_norm``,
  ``ssm_out_proj``; device seconds, layer-calls, ms a layer-call, and for
  ``ssm_update`` the GB/s on its own bytes, a live row's state read once and
  written once), an ``ssm_op=`` line for each of the five largest instructions
  under ``ssm`` in each program, the chunked scan's share of its roofline in the
  prefills that the window happens to hold (``ssd_scan_roofline=``: no listed
  metric, because a window may hold none), and a ``state_pool_copy=`` line for
  every instruction that makes an array of the state pool's whole shape, or of
  the conv pool's, and is not its in-place update, and for every instruction of
  program ``chain`` that makes a layer's row of the conv pool (``pool_copies``:
  there has to be none).

- in a cell with Gated DeltaNet layers (an architecture file with
  ``gdn_chunk_cost``), the same of the scopes under ``gdn``: a ``gdn_part=``
  line a program and piece (``gdn_in_proj``, ``gdn_ba_proj``, ``gdn_conv``,
  ``gdn_chunk`` in a prefill, ``gdn_update`` in a chain with the GB/s on its own
  bytes, ``gdn_norm``, ``gdn_out_proj``), ``gdn_op=`` lines, the chunked delta
  rule's share of its roofline in the prefills the window happens to hold
  (``gdn_chunk_roofline=``) and the ``state_pool_copy=`` lines;
- in a cell that is one chip's share of a routed layer, a ``held_visits=``
  line: the median over the traced chains of the visits a step and routed
  layer that the experts held here got (the ``serve:accept`` spans' arg);
- in a routed cell (since PR 50), an ``experts_read=`` line: the median over
  the traced chains of the experts a step and routed layer that the decode
  product read (the count the kernel ``moe_decode`` was handed: every row's
  picks, dead or alive), beside the live rows' ``experts_touched``.

- in a cell with hyper-connections (an instruction under the scope ``mhc``;
  since PR 54), an ``mhc_part=`` line a program and piece (``mhc_mix``,
  ``mhc_pre``, ``mhc_post``: device seconds, instructions, and the ms a
  run of the program, the runs being the occurrences of an instruction
  outside the layer scan), an ``mhc_kernel=`` line for each of the kernels
  ``mhc_mix_read`` and ``mhc_write`` (instructions, calls, ms a call: 14 of each
  a prefill of the xing cell's 7 layers) and an ``mhc_op=`` line for each of the
  five largest instructions under ``mhc`` in each program;

All are wrapped OUTSIDE the benchmark, before ``run.main`` runs; nothing
here is read by the program or the benchmark, and a cell's listed metrics
read what they read without it.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SERVING = ("layer_matmul_time_share.batch", "layer_matmul_roofline.batch", "unnamed_time_share.batch")
# the routed, latent-attention readers, for a cell whose architecture file has their costs and which
# is not listed for them (PR 39's cell: ``tests/benchmarks/test_routed_readers.py`` takes a metric for
# the glm cell's own only while its ``workloads`` is that cell alone; PERF.md, section 7)
ROUTED = ("moe_time_share.batch", "moe_experts_roofline.batch", "moe_experts_touched.batch",
          "mla_paged_time_share.batch", "mla_paged_roofline.batch")
MOE_HALVES = ("moe_dispatch", "moe_combine")


def moe_halves(rows):
    """The ``moe_half=`` and ``moe_half_op=`` lines of ``hlo_stats``' rows."""
    for half in MOE_HALVES:
        mine = sorted((r for r in rows if half in r["tf_op_name"].rstrip(":").split("/")),
                      key=lambda r: -float(r["total_self_time"]))
        if mine:
            seconds, calls = 1e-6 * sum(float(r["total_self_time"]) for r in mine), int(mine[0]["occurrences"])
            yield (f"moe_half={half} device_s={seconds} layer_calls={calls} "
                   f"ms_a_layer_call={1e3 * seconds / calls} instructions={len(mine)}")
        for r in mine[:5]:
            yield (f"moe_half_op={half} instruction={r['hlo_op_name']} device_s={1e-6 * float(r['total_self_time'])} "
                   f"count={r['occurrences']} op_name={r['tf_op_name']} expression={r['hlo_op_expression'][:300]}")


def pool_copies(rows, state, conv, kernels):
    """The ``state_pool_copy=`` lines and their count: every instruction that
    makes an array of the state pool's whole shape ``state`` or of the conv
    pool's ``conv`` = (layers, slots, K - 1, X) and is not its in-place update
    (a ``dynamic-update-slice``, or one of ``kernels`` by its name), and in
    program ``chain`` every instruction of a layer's ROW of the conv pool,
    sliced out or as ``[rows, K - 1, X]``: a decode step's convolution is the
    kernel ``conv_update`` on the pool itself, so there has to be none (a
    prompt slices its rows' tails out and writes them back)."""
    import re

    layers, slots, taps, X = conv
    whole = r"(?:%s|bf16\[%d,%d,%d\])" % (state, layers, slots, taps * X)
    row = r"(?:bf16|f32)\[(?:1,%d,%d|%d,%d|%d,%d,%d)\]" % (slots, taps * X, slots, taps * X, slots, taps, X)
    copies = 0
    for r in rows:
        made = re.match(r"\s*%?[\w.\-]+ = (?:\()?" + whole, r["hlo_op_expression"])
        in_place = "dynamic-update-slice" in r["hlo_op_expression"] or r["hlo_op_name"].startswith(kernels)
        in_chain = r["tf_op_name"].startswith("jit(chain)")
        of_a_row = in_chain and re.match(r"\s*%?[\w.\-]+ = (?:\()?" + row, r["hlo_op_expression"])
        if (made and not in_place or of_a_row) and r["category"] != "while":
            copies += 1
            yield (f"state_pool_copy={r['hlo_op_name']} category={r['category']} device_s="
                   f"{1e-6 * float(r['total_self_time'])} count={r['occurrences']} expression={r['hlo_op_expression'][:300]}")
    yield f"state_pool_copies={copies} of_shape={whole} or_in_chain={row}"


SSM_PARTS = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_update", "ssm_norm", "ssm_out_proj")


def ssm_parts(rows, workload_name):
    """The ``ssm_part=``, ``ssm_op=``, ``ssd_scan_roofline=`` and ``state_pool_copy=`` lines."""
    import re

    from benchmarks.lib import costs, harness, peaks, program

    workload = harness.load_workload(workload_name)
    config = harness.load_config(workload["config"])
    arch, cfg = harness.load_architecture(config["architecture"]), program.published(config)
    if not hasattr(arch, "ssd_scan_cost"):
        return
    rows_, chunk = workload["engine"]["max_seqs"], workload["engine"]["chunk_bucket"]
    # a period's state-space layers are unrolled in the scan's body: each has instructions of its own
    unrolled = harness.load_reference(config["architecture"]).period_of(cfg["layer_types"]).count("mamba")
    state = rows_ * cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"] * 4
    under = [(r, r["tf_op_name"].rstrip(":").split("/")) for r in rows]
    under = [(r, path, path[0][4:-1] if path[0].startswith("jit(") else "?") for r, path in under if "ssm" in path]
    for prog in sorted({p for _, _, p in under}):
        mine = [(r, path) for r, path, p in under if p == prog]
        # a layer-call makes ONE in-projection: its instruction's occurrences count the layer-calls
        calls = unrolled * max((int(float(r["occurrences"])) for r, path in mine if "ssm_in_proj" in path), default=1)
        for part in SSM_PARTS + ("(ssm alone)",):
            of = [r for r, path in mine if (part in path if part in SSM_PARTS else not set(SSM_PARTS) & set(path))]
            if not of:
                continue
            seconds = 1e-6 * sum(float(r["total_self_time"]) for r in of)
            line = (f"ssm_part={part} program={prog} device_s={seconds} layer_calls={calls} "
                    f"ms_a_layer_call={1e3 * seconds / calls} instructions={len(of)}")
            if part == "ssm_update":
                line += f" own_gb_per_s={2e-9 * state * calls / seconds} of_state_bytes={2 * state}"
            if part == "ssm_scan":
                flops, bytes_ = arch.ssd_scan_cost(cfg, rows_, chunk)
                least, bound = costs.roofline_seconds(flops, bytes_, peaks.device_peaks("TPU v5 lite"))
                yield (f"ssd_scan_roofline={100 * least * calls / seconds} bound={bound} least_ms_a_layer_call="
                       f"{1e3 * least} rows={rows_} tokens={chunk} (every layer-call counted at the full shape)")
            yield line
        for r, _ in sorted(mine, key=lambda rp: -float(rp[0]["total_self_time"]))[:5]:
            yield (f"ssm_op={r['hlo_op_name']} program={prog} device_s={1e-6 * float(r['total_self_time'])} "
                   f"count={r['occurrences']} op_name={r['tf_op_name']} expression={r['hlo_op_expression'][:400]}")
    channels = cfg["mamba_n_heads"] * cfg["mamba_d_head"]  # the pool keeps 128 of them a tile, on the lanes
    whole = r"f32\[%d,%d,%d,%d,128\]" % (arch.ssm_layers(cfg), rows_, channels // 128, cfg["mamba_d_state"])
    sizes = program.model_config(config, None).ssm
    yield from pool_copies(rows, whole, (arch.ssm_layers(cfg), rows_, sizes.d_conv - 1, sizes.conv_dim),
                           ("ssm_update", "ssm_rows_in", "conv_update"))


GDN_PARTS = ("gdn_in_proj", "gdn_ba_proj", "gdn_conv", "gdn_chunk", "gdn_update", "gdn_norm", "gdn_out_proj")


def gdn_parts(rows, workload_name):
    """The ``gdn_part=``, ``gdn_op=``, ``gdn_chunk_roofline=`` and ``state_pool_copy=`` lines."""
    import re

    from benchmarks.lib import costs, harness, peaks, program
    from deepspeed_tpu.ops import gdn

    workload = harness.load_workload(workload_name)
    config = harness.load_config(workload["config"])
    arch, cfg = harness.load_architecture(config["architecture"]), program.published(config)
    if not hasattr(arch, "gdn_chunk_cost"):
        return
    gdn_sizes = program.model_config(config, None).gdn
    rows_, chunk = workload["engine"]["max_seqs"], workload["engine"]["chunk_bucket"]
    # a period's DeltaNet layers are unrolled in the scan's body: each has instructions of its own
    unrolled = cfg["full_attention_interval"] - 1
    Hv, Dk, Dv = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    state = rows_ * Hv * Dk * Dv * 4
    under = [(r, r["tf_op_name"].rstrip(":").split("/")) for r in rows]
    under = [(r, path, path[0][4:-1] if path[0].startswith("jit(") else "?") for r, path in under if "gdn" in path]
    for prog in sorted({p for _, _, p in under}):
        mine = [(r, path) for r, path, p in under if p == prog]
        # a layer-call makes ONE in-projection (a prefill's one a GROUP of its rows, ``ops/gdn.py::group_rows``):
        # its instruction's occurrences count the layer-calls
        calls = unrolled * max((int(float(r["occurrences"])) for r, path in mine if "gdn_in_proj" in path), default=1)
        if prog == "step":
            calls = max(calls // (rows_ // gdn.group_rows(rows_, chunk, gdn_sizes.chunk_size, Hv)), 1)
        for part in GDN_PARTS + ("(gdn alone)",):
            of = [r for r, path in mine if (part in path if part in GDN_PARTS else not set(GDN_PARTS) & set(path))]
            if not of:
                continue
            seconds = 1e-6 * sum(float(r["total_self_time"]) for r in of)
            line = (f"gdn_part={part} program={prog} device_s={seconds} layer_calls={calls} "
                    f"ms_a_layer_call={1e3 * seconds / calls} instructions={len(of)}")
            if part == "gdn_update":
                line += f" own_gb_per_s={2e-9 * state * calls / seconds} of_state_bytes={2 * state}"
            if part == "gdn_chunk":
                flops, bytes_ = arch.gdn_chunk_cost(cfg, rows_, chunk)
                least, bound = costs.roofline_seconds(flops, bytes_, peaks.device_peaks("TPU v5 lite"))
                yield (f"gdn_chunk_roofline={100 * least * calls / seconds} bound={bound} least_ms_a_layer_call="
                       f"{1e3 * least} rows={rows_} tokens={chunk} (every layer-call counted at the full shape)")
            yield line
        for r, _ in sorted(mine, key=lambda rp: -float(rp[0]["total_self_time"]))[:5]:
            yield (f"gdn_op={r['hlo_op_name']} program={prog} device_s={1e-6 * float(r['total_self_time'])} "
                   f"count={r['occurrences']} op_name={r['tf_op_name']} expression={r['hlo_op_expression'][:400]}")
    whole = r"f32\[%d,%d,%d,%d,%d\]" % (arch.gdn_layers(cfg), rows_, Hv, Dk, Dv)  # values on the lanes
    yield from pool_copies(rows, whole, (arch.gdn_layers(cfg), rows_, gdn_sizes.d_conv - 1, gdn_sizes.conv_dim),
                           ("gdn_update", "conv_update"))


MHC_PARTS = ("mhc_mix", "mhc_pre", "mhc_post")
MHC_KERNELS = ("mhc_mix_read", "mhc_write")


def mhc_parts(rows):
    """The ``mhc_part=``, ``mhc_kernel=`` and ``mhc_op=`` lines of ``hlo_stats``' rows."""
    for prog in ("step", "chain"):
        mine = [(r, r["tf_op_name"].rstrip(":").split("/")) for r in rows
                if r["tf_op_name"].startswith(f"jit({prog})") and r["category"] != "while"
                and "mhc" in r["tf_op_name"].split("/")]
        # an instruction outside the layer scan (a leading dense layer's) runs once a run of the program
        runs = min((int(r["occurrences"]) for r, _ in mine), default=0)
        for part in MHC_PARTS:
            of = [r for r, path in mine if part in path]
            if of:
                seconds = 1e-6 * sum(float(r["total_self_time"]) for r in of)
                yield (f"mhc_part={part} program={prog} device_s={seconds} runs={runs} "
                       f"ms_a_run={1e3 * seconds / runs} instructions={len(of)}")
        for kernel in MHC_KERNELS:
            of = [r for r, _ in mine if r["hlo_op_name"].startswith(kernel)]
            if of:
                seconds, calls = 1e-6 * sum(float(r["total_self_time"]) for r in of), sum(int(r["occurrences"]) for r in of)
                yield (f"mhc_kernel={kernel} program={prog} device_s={seconds} instructions={len(of)} calls={calls} "
                       f"ms_a_call={1e3 * seconds / calls}")
        for r, _ in sorted(mine, key=lambda rp: -float(rp[0]["total_self_time"]))[:5]:
            yield (f"mhc_op={r['hlo_op_name']} program={prog} device_s={1e-6 * float(r['total_self_time'])} "
                   f"count={r['occurrences']} op_name={r['tf_op_name']} expression={r['hlo_op_expression'][:300]}")


def main(argv=None) -> int:
    from benchmarks import run
    from benchmarks.lib import harness, scopes

    hlo_stats, cell_metrics = scopes._hlo_stats, harness.cell_metrics

    def timed(path):
        start = time.perf_counter()
        rows = hlo_stats(path)
        print(f"trace_cost= xplane_bytes={os.path.getsize(path)} "
              f"hlo_stats_s={time.perf_counter() - start:.3f} rows={len(rows)}", flush=True)
        for line in list(moe_halves(rows)) + list(mhc_parts(rows)):
            print(line, flush=True)
        for parts in (ssm_parts, gdn_parts):
            for line in parts(rows, argv[argv.index("--workload") + 1]):
                print(line, flush=True)
        return rows

    def with_serving_readers(bench, group, workload_name):
        wanted = cell_metrics(bench, group, workload_name)
        if group == "per_layer" and harness.load_workload(workload_name).get("kind") == "serve":
            names = {m["name"] for m in wanted}
            wanted = wanted + [m for m in bench["per_layer"] if m["name"] in SERVING and m["name"] not in names]
            config = harness.load_config(harness.load_workload(workload_name)["config"])
            if hasattr(harness.load_architecture(config["architecture"]), "routed_decode_cost"):
                wanted = wanted + [m for m in bench["per_layer"] if m["name"] in ROUTED and m["name"] not in names]
        return wanted

    read_metrics = harness.read_metrics

    def with_held_visits(entries, run_, trace, *rest):
        from benchmarks.lib import spans, stats

        accepts = spans.named(spans.of_run(run_), "serve:accept", kind="chain")
        visits = [float(s.args["held_visits"]) for s in accepts if "held_visits" in s.args]
        if visits:  # a chip's share of a routed layer: the load its experts got (``moe_held_visits.ep`` is its median)
            print(f"held_visits= median={stats.median(visits)} least={min(visits)} most={max(visits)} "
                  f"chains={len(visits)} (visits a step and routed layer to the experts held here)", flush=True)
        read = [float(s.args["experts_read"]) for s in accepts if "experts_read" in s.args]
        if read:  # what the decode product read: the count its kernel was handed, dead and pad rows' picks too
            touched = [float(s.args["experts_touched"]) for s in accepts if "experts_read" in s.args]
            print(f"experts_read= median={stats.median(read)} least={min(read)} most={max(read)} chains={len(read)} "
                  f"experts_touched_median={stats.median(touched)} (experts a step and routed layer)", flush=True)
        return read_metrics(entries, run_, trace, *rest)

    argv = list(sys.argv[1:] if argv is None else argv)
    scopes._hlo_stats, harness.cell_metrics, harness.read_metrics = timed, with_serving_readers, with_held_visits
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
