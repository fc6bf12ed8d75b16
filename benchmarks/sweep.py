#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest arrival rate the
system sustains. ``python benchmarks/sweep.py --workload <name> --rates 4,6,8
--seconds 30`` builds the cell's engine once, warms it up as ``run.py`` does,
then offers the cell's traffic at each rate in turn and prints one row a rate.

A rate is sustained when at least 0.97 of the requests due in the window
finish within it plus one median request time of the lowest rate, and the
queue (due, not yet admitted; averaged over time, since a request waits for
the next chain boundary even in an idle server) is no deeper in the window's
last quarter than in its second, give or take one request. The cell's
file then carries 0.8 x the knee as a plain number; nothing searches for a
rate at run time. Needs a TPU, like ``run.py``; its rows are not results.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import harness, stats, traffic  # noqa: E402


def queue_depth(rows, t0: float, t1: float) -> float:
    """Mean number of requests due and not yet admitted over [t0, t1]."""
    waiting = 0.0
    for r in rows:
        admitted = float("inf") if r["queue_wait_s"] is None else r["due_s"] + r["queue_wait_s"]
        waiting += max(0.0, min(admitted, t1) - max(r["due_s"], t0))
    return waiting / (t1 - t0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    workload = harness.load_workload(args.workload)
    config = harness.load_config(workload["config"])
    serve = harness.load_runner(workload["kind"])
    try:
        devices = harness.require_devices(int(workload["chips"]))
    except harness.NoDevice as e:
        print(f"benchmarks/sweep.py: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    compiles = harness.CompileCounter()

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.topology.mesh import build_mesh

    model_cfg = serve.program.model_config(config, jnp.bfloat16)
    engine = InferenceEngineV2(
        model_cfg, serve.make_weights(model_cfg, args.seed), dict(workload["engine"]),
        mesh=build_mesh(devices=devices, axis_sizes={"tp": 1, "dp": len(devices)}))
    serve.warm(engine, workload, config["vocab_size"])

    table = []
    unloaded_request_s = None
    for rate in [float(r) for r in args.rates.split(",")]:
        tr = dict(workload["traffic"], rate_per_s=rate)
        reqs = traffic.open_loop(tr, config["vocab_size"], args.seed, args.seconds)
        compiles.mark()
        t0 = time.perf_counter()
        outs = engine.generate(reqs.prompts, max_new_tokens=reqs.output_tokens,
                               arrival_times=list(reqs.arrival_s))
        elapsed = time.perf_counter() - t0
        rows = serve.request_rows(engine.lifecycle.records(), outs, reqs.output_tokens, t0)
        ok = [r for r in rows if r["ok"]]
        e2e = stats.median([r["finish_s"] - r["due_s"] for r in ok])
        if unloaded_request_s is None:
            unloaded_request_s = e2e
        done = sum(1 for r in ok if r["finish_s"] <= args.seconds + unloaded_request_s)
        row = {
            "rate_per_s": rate, "offered": len(rows), "done_in_window_share": done / len(rows),
            "queue_second_quarter": queue_depth(rows, args.seconds / 4, args.seconds / 2),
            "queue_last_quarter": queue_depth(rows, 3 * args.seconds / 4, args.seconds),
            "drain_s": elapsed - args.seconds, "request_s_median": e2e,
            "ttft_p50_ms": 1e3 * stats.median([r["ttft_s"] for r in ok]),
            "ttft_p95_ms": 1e3 * stats.percentile([r["ttft_s"] for r in ok], 95),
            "tpot_p95_ms": 1e3 * stats.percentile([r["tpot_s"] for r in ok], 95),
            "out_tokens_per_s": sum(r["tokens"] for r in ok) / elapsed,
            "preemptions": sum(r["preemptions"] for r in rows),
            "compiles_in_window": compiles.since_mark(),
        }
        row["sustained"] = bool(row["done_in_window_share"] >= 0.97
                                and row["queue_last_quarter"] <= row["queue_second_quarter"] + 1)
        table.append(row)
        print(json.dumps(row), flush=True)
    sustained = [r["rate_per_s"] for r in table if r["sustained"]]
    print(json.dumps({"knee_rate_per_s": max(sustained) if sustained else None,
                      "device": devices[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
