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


def main(argv=None) -> int:
    from benchmarks import run
    from benchmarks.lib import harness, scopes

    hlo_stats, cell_metrics = scopes._hlo_stats, harness.cell_metrics

    def timed(path):
        start = time.perf_counter()
        rows = hlo_stats(path)
        print(f"trace_cost= xplane_bytes={os.path.getsize(path)} "
              f"hlo_stats_s={time.perf_counter() - start:.3f} rows={len(rows)}", flush=True)
        for line in moe_halves(rows):
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

    scopes._hlo_stats, harness.cell_metrics = timed, with_serving_readers
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
