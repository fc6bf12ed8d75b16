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
  them, the five readers of ``benchmarks/lib/routed.py`` (``ROUTED``) likewise.

Both are wrapped OUTSIDE the benchmark, before ``run.main`` runs; nothing
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


def main(argv=None) -> int:
    from benchmarks import run
    from benchmarks.lib import harness, scopes

    hlo_stats, cell_metrics = scopes._hlo_stats, harness.cell_metrics

    def timed(path):
        start = time.perf_counter()
        rows = hlo_stats(path)
        print(f"trace_cost= xplane_bytes={os.path.getsize(path)} "
              f"hlo_stats_s={time.perf_counter() - start:.3f} rows={len(rows)}", flush=True)
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
