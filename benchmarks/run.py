#!/usr/bin/env python3
"""Run one cell of the benchmark: ``python benchmarks/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.

``<name>`` is a cell of ``BENCHMARK.json``, which says what the cell reports
and in which units. Its file is ``benchmarks/workloads/<name>.json``, which
names its configuration (``benchmarks/configs/<config>.json``) and its
``kind``, whose runner is ``benchmarks/runners/<kind>.py``. The runner builds the system under
test from ``--seed``, checks it against the configuration's plain reference
(``benchmarks/reference/<architecture>.py``, given the program's weights
relabelled by ``benchmarks/architectures/<architecture>.py``) within the
tolerances of the configuration's own ``check`` block, warms the cell's own shapes up
and measures for ``--seconds``. Human-readable lines come first; the last line
of stdout is one JSON object: with ``--trace 0`` the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics (``benchmarks/metrics/``),
the device's busy seconds and a breakdown from the profiler's trace. Every
runner returns, beside its readings, ``compared``: each number that decided
``correct`` as ``name: [found, limit]``, printed last in that line and as the
last lines of standard error, where the record of a run that is not correct
keeps it.

It runs on the TPU only: with no TPU, or fewer chips than the cell asks for,
it exits with a code other than 0 and prints nothing that looks like a result.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if importlib.util.find_spec("deepspeed_tpu") is None:
        print("benchmarks/run.py: the system under test, deepspeed_tpu/, is not in this checkout",
              file=sys.stderr)
        return 4
    bench = harness.load_benchmark()
    try:
        wanted = harness.cell_metrics(bench, "per_layer" if args.trace else "end_to_end",
                                      args.workload)
    except KeyError as e:
        print(f"benchmarks/run.py: {e.args[0]}", file=sys.stderr)
        return 5
    workload = harness.load_workload(args.workload)
    config = harness.load_config(workload["config"])
    runner = harness.load_runner(workload["kind"])
    try:
        devices = harness.require_devices(int(workload["chips"]))
    except harness.NoDevice as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 3
    harness.say(workload=args.workload, config=workload["config"], seed=args.seed,
                seconds=args.seconds, trace=args.trace, device=devices[0].device_kind,
                chips=len(devices), compile_cache=harness.enable_compile_cache())

    trace_dir = None
    if args.trace:
        # inside the checkout, at a fixed path, emptied before use
        trace_dir = os.path.join(harness.BENCH_DIR, os.pardir, ".bench_trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    architecture = harness.load_architecture(config["architecture"])
    run = runner.run(
        workload=workload, config=config, reference=harness.load_reference(config["architecture"]),
        architecture=architecture, seed=args.seed, seconds=args.seconds, devices=devices, trace_dir=trace_dir,
        compiles=harness.CompileCounter(), t_process_start=T_PROCESS_START)
    run["workload"], run["config"], run["architecture"] = workload, config, architecture
    run["device_kind"] = devices[0].device_kind

    device = harness.device_report(devices, run["memory"])
    breakdown = None
    if args.trace:
        from benchmarks.lib import xplane

        trace = xplane.reduce_trace(xplane.find_xplane(trace_dir))
        metrics = harness.read_metrics(wanted, run, trace)
        device["busy_s"], device["window_s"] = trace.busy_s, trace.window_s
        breakdown = {"device_ops": [[n, s] for n, s in trace.top_ops(10)],
                     "idle_gaps": [[n, s] for n, s in trace.idle_gaps]}
        for op in trace.ops:
            if xplane.PALLAS_TARGET in op.text:
                harness.say(pallas_kernel=op.label.replace(" ", "_"), program=op.module,
                            calls=op.count, device_s=op.seconds / trace.n_devices)
        harness.say(traced_window_s=trace.window_s, busy_s=trace.busy_s,
                    idle_share=1 - trace.busy_s / trace.window_s)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": float(run["end_to_end"][m["name"]]), "unit": m["unit"]}
                   for m in wanted}
    print(harness.last_line(run["correct"], run["attempted"], run["failed"], metrics,
                            device, run["compared"], breakdown), flush=True)
    # the end of standard error is what the driver keeps of a run that is not correct
    for name, (found, limit) in run["compared"].items():
        print(f"compared {name}={found} limit={limit}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
