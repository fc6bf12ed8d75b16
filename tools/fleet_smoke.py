#!/usr/bin/env python
"""Fleet telemetry smoke: collector + N worker PROCESSES, exit-gated.

The multi-process proof of ISSUE 13's federation semantics, run by the
tier-1 integration test (``tests/unit/test_fleet.py``). Three processes on CPU:

  parent   role=router: starts an in-process :class:`FleetCollector`,
           mints one ``fleet.TraceContext`` per synthetic request, emits
           each request's admission span + flow START on its own tracer,
           then spawns the workers with the contexts on their argv.
  workers  role=replica, process_index 1..N (separate ``python``
           processes): observe deterministic counters/histograms, wrap a
           fake dispatch of every received context in
           ``fleet.dispatch_span`` (the ``serve:dispatch`` span + in-span
           flow STEP), push their registry dump + heartbeat to the
           collector over HTTP, and export their tracer stream as JSONL.

CPU-ONLY: the parent touches jax and then starts children that do too, so
every process here pins ``JAX_PLATFORMS=cpu``. On a TPU a chip belongs to one
process at a time — a fleet on chips is one daemon process per chip with a
parent that stays off jax; this tool never asks for a device.

Exit gates (any failure => exit 1):
  1. federated counters BIT-EXACTLY equal the sum of the per-process
     dumps the collector holds (counters sum, histogram counts add);
  2. ``tools/trace_merge.py`` joins the parent + worker JSONL streams into
     ONE trace in which at least one flow id links events from >= 2
     distinct pids, and every worker contributed a ``serve:dispatch`` span;
  3. every worker registered (ledger rows with heartbeats + clock offsets).

Prints one JSON line of evidence.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# deterministic per-worker workload: counters/histogram samples a verifier
# can predict, chosen so float sums are exact (integers)
REQUESTS_PER_WORKER = 5
TOKENS_PER_WORKER = 40.0
HIST_SAMPLES = [1.5, 3.0, 12.0, 55.0, 130.0]


def worker_main(args) -> int:
    """One replica process: metrics + dispatch spans + push + JSONL."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry import fleet
    from deepspeed_tpu.telemetry.collector import FleetClient

    idx = int(args.index)
    ident = fleet.configure_identity(run_id=args.run_id, process_index=idx,
                                     role="replica")
    tr = telemetry.get_tracer()
    tr.configure(enabled=True)
    reg = tr.registry
    for _ in range(REQUESTS_PER_WORKER):
        reg.counter("serving/requests").add(1.0)
    reg.counter("serving/tokens", replica=idx).add(TOKENS_PER_WORKER)
    for v in HIST_SAMPLES:
        reg.histogram("serving/ttft_ms").observe(v)
    fleet.note_step(idx * 100 + 7)
    for wire in json.loads(args.contexts):
        ctx = fleet.TraceContext.from_wire(wire)
        with fleet.dispatch_span(ctx, replica=idx):
            time.sleep(0.002)
    client = FleetClient(args.collector, identity=ident, registry=reg)
    ack = client.register()
    if not (ack and ack.get("ok")):
        print(json.dumps({"ok": False, "error": "register failed"}))
        return 1
    client.push()
    out = os.path.join(args.out, f"events.p{idx}.jsonl")
    telemetry.export_jsonl(out, tracer=tr)
    print(json.dumps({"ok": True, "index": idx, "events": out}))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--out", default=None)
    ap.add_argument("--timeout", type=float, default=240.0)
    # worker mode (internal): spawned with the shared run id + contexts
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--index", type=int, default=1)
    ap.add_argument("--run-id", dest="run_id", default=None)
    ap.add_argument("--collector", default=None)
    ap.add_argument("--contexts", default="[]")
    args = ap.parse_args()
    if args.worker:
        return worker_main(args)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry import fleet
    from deepspeed_tpu.telemetry.collector import FleetCollector

    out_dir = args.out or tempfile.mkdtemp(prefix="fleet_smoke_")
    os.makedirs(out_dir, exist_ok=True)
    run_id = f"fleet-smoke-{os.getpid():x}"
    fleet.configure_identity(run_id=run_id, process_index=0, role="router")
    tr = telemetry.get_tracer()
    tr.configure(enabled=True)
    collector = FleetCollector(stale_after_s=60.0).start()

    # router side: one trace context per request, admission span + flow
    # START on the request's track — the arrow the workers' dispatch steps
    # must bind to in the merged trace
    contexts = [fleet.TraceContext.mint(i, run_id=run_id)
                for i in range(args.requests)]
    for ctx in contexts:
        with tr.span("admit", cat="router", request_id=ctx.request_id):
            tr.flow(ctx.flow_name, ctx.flow_id, "start")
    wire = json.dumps([c.to_wire() for c in contexts])

    procs = []
    for i in range(1, args.workers + 1):
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--index", str(i), "--run-id", run_id,
             "--collector", collector.url, "--contexts", wire,
             "--out", out_dir],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO))
    worker_fail = []
    for p in procs:
        try:
            stdout, stderr = p.communicate(timeout=args.timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            worker_fail.append("timeout")
            continue
        if p.returncode != 0:
            worker_fail.append(stderr.decode()[-400:])

    gates = {}
    # gate 1: federated counters == bit-exact sum of the stored dumps
    expected: dict = {}
    for d in collector.dumps().values():
        for k, v in d["counters"].items():
            expected[k] = expected.get(k, 0.0) + float(v)
    fed = collector.federated_registry().counters()
    gates["counters_bit_exact"] = (
        bool(expected)
        and all(fed.get(k) == v for k, v in expected.items()))
    gates["federated_requests"] = fed.get("serving/requests")
    gates["expected_requests"] = float(args.workers * REQUESTS_PER_WORKER)

    # gate 2: merged trace with cross-process flow links + worker dispatches
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_merge

    parent_stream = os.path.join(out_dir, "events.p0.jsonl")
    telemetry.export_jsonl(parent_stream, tracer=tr)
    streams = [parent_stream] + [
        os.path.join(out_dir, f"events.p{i}.jsonl")
        for i in range(1, args.workers + 1)]
    streams = [s for s in streams if os.path.exists(s)]
    merged = trace_merge.merge_streams(streams)
    merged_path = os.path.join(out_dir, "merged_trace.json")
    with open(merged_path, "w") as f:
        json.dump(merged, f)
    links = {f: p for f, p in trace_merge.linked_flow_pids(merged).items()
             if len(p) > 1}
    dispatch_pids = sorted({ev["pid"] for ev in merged["traceEvents"]
                            if ev.get("name") == "serve:dispatch"})
    gates["cross_process_flow_links"] = len(links)
    gates["dispatch_pids"] = dispatch_pids
    gates["trace_linked"] = bool(links) and len(dispatch_pids) >= args.workers

    # gate 3: ledger saw every worker (heartbeat + clock offset)
    ledger = collector.ledger()
    replica_rows = [r for r in ledger["processes"]
                    if r["identity"]["role"] == "replica"]
    gates["ledger_replicas"] = len(replica_rows)
    gates["ledger_ok"] = (
        len(replica_rows) == args.workers
        and all(r["heartbeat"] is not None and r["clock_offset_s"] is not None
                and not r["stale"] for r in replica_rows))

    collector.stop()
    ok = (not worker_fail and gates["counters_bit_exact"]
          and gates["trace_linked"] and gates["ledger_ok"])
    print(json.dumps({"ok": ok, "workers": args.workers,
                      "worker_failures": worker_fail, **gates,
                      "merged_trace": merged_path, "out_dir": out_dir}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
