"""Fleet telemetry plane tests (ISSUE 13).

Contract under test:
  - process identity: env/config resolution, stamps on expositions, JSONL
    streams, flight-recorder dumps
  - metric federation is EXACT: merging K sharded registries equals
    observing the concatenated sample stream (property test — quantiles
    and bucket counts bit-identical; counter sum + gauge last-per-proc
    rules pinned alongside)
  - FleetCollector: push/scrape ingestion, federated render, fleet/*
    rollups, cross-process straggler flags, health ledger
  - distributed tracing: TraceContext wire round-trip, stable flow ids,
    dispatch_span emission, trace_merge joining per-process JSONL into one
    flow-linked Perfetto trace
  - /healthz liveness endpoint (identity + last-step age + registry size)
  - the 3-process CPU integration smoke (tools/fleet_smoke.py): collector
    + 2 real worker processes, every exit gate green
"""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu.telemetry import exposition, fleet
from deepspeed_tpu.telemetry.collector import FleetClient, FleetCollector
from deepspeed_tpu.telemetry.registry import MetricsRegistry, decode_key
from deepspeed_tpu.telemetry.tracer import Tracer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _pinned_identity():
    """Deterministic identity per test; restore the lazy default after."""
    fleet.reset_identity()
    fleet.configure_identity(run_id="testrun", process_index=0,
                             host="testhost", role="train")
    yield
    fleet.reset_identity()


# ------------------------------------------------------------- identity
def test_identity_defaults_and_overrides(monkeypatch):
    fleet.reset_identity()
    monkeypatch.setenv("DSTPU_RUN_ID", "envrun")
    monkeypatch.setenv("DSTPU_PROCESS_INDEX", "3")
    monkeypatch.setenv("DSTPU_ROLE", "replica")
    ident = fleet.get_identity()
    assert (ident.run_id, ident.process_index, ident.role) == (
        "envrun", 3, "replica")
    assert ident.proc == "p3" and ident.key() == "envrun/p3"
    fleet.configure_identity(role="router")
    assert fleet.get_identity().role == "router"
    # wire round-trip
    back = fleet.ProcessIdentity.from_dict(
        json.loads(json.dumps(ident.to_dict())))
    assert back == ident


def test_identity_stamped_on_expositions():
    reg = MetricsRegistry()
    reg.counter("serving/requests").add(1)
    text = exposition.render_prometheus(reg)
    assert 'dstpu_process_info{' in text and 'run_id="testrun"' in text
    doc = json.loads(exposition.render_json_snapshot(reg))
    assert doc["identity"]["run_id"] == "testrun"
    # the collector's federated render suppresses the single-process stamp
    assert "process_info" not in exposition.render_prometheus(
        reg, identity=False)


def test_identity_stamped_on_flight_record(tmp_path):
    from deepspeed_tpu.diagnostics.flight_recorder import FlightRecorder

    rec = FlightRecorder(capacity=4, dump_dir=str(tmp_path))
    rec.record(1, {"loss": 1.0})
    path = rec.dump(reason="test")
    header = json.loads(open(path).readline())
    assert header["identity"]["run_id"] == "testrun"
    assert header["identity"]["process_index"] == 0
    # per-process default filename: proc 0 keeps the historical name,
    # proc 2 gets a distinguishable one
    fleet.configure_identity(process_index=2)
    assert os.path.basename(rec._resolve_path(None)) == "flight_record.p2.jsonl"
    fleet.configure_identity(process_index=0)
    assert os.path.basename(rec._resolve_path(None)) == "flight_record.jsonl"


# ----------------------------------------------------- federation (exact)
def test_histogram_merge_is_exact_property():
    """Merging K sharded registries == observing the concatenated stream:
    bucket counts and quantiles BIT-identical, counters sum, gauges keep
    last-per-process under {proc=}."""
    rng = np.random.default_rng(7)
    samples = np.concatenate([
        rng.lognormal(2.0, 1.8, 4000),
        [0.0, -3.0, 1e-12, 1e9],  # underflow + extreme buckets
    ])
    order = rng.permutation(len(samples))
    shards = [MetricsRegistry() for _ in range(4)]
    whole = MetricsRegistry()
    for j, i in enumerate(order):
        v = float(samples[i])
        shards[j % 4].histogram("serving/ttft_ms", k=8).observe(v)
        whole.histogram("serving/ttft_ms", k=8).observe(v)
        shards[j % 4].counter("serving/requests").add(1.0)
    for k, sh in enumerate(shards):
        sh.gauge("serving/queue_depth").set(float(10 + k))
    merged = MetricsRegistry()
    for k, sh in enumerate(shards):
        dump = fleet.registry_dump(
            sh, fleet.ProcessIdentity("testrun", k))
        dump = json.loads(json.dumps(dump))  # the real wire round-trip
        fleet.merge_dump_into(merged, dump)
    hm = merged.histogram("serving/ttft_ms", k=8)
    hw = whole.histogram("serving/ttft_ms", k=8)
    assert hm.count == hw.count
    assert dict(hm.buckets()) == dict(hw.buckets())  # bucket-wise identical
    assert (hm.min, hm.max) == (hw.min, hw.max)
    for q in (0.0, 0.5, 0.9, 0.95, 0.99, 1.0):
        assert hm.quantile(q) == hw.quantile(q), q  # bit-identical
    # counters: arithmetic sum (integers — exact)
    assert merged.counter("serving/requests").value == float(len(samples))
    # gauges: one child per process, no cross-process fold
    for k in range(4):
        assert merged.gauge("serving/queue_depth",
                            proc=f"p{k}").value == float(10 + k)


def test_decode_key_round_trip():
    from deepspeed_tpu.telemetry.registry import encode_labels

    for labels in ({}, {"k": "8"}, {"proc": "p1", "op": "all_reduce"}):
        key = "serving/x" + encode_labels(labels)
        name, back = decode_key(key)
        assert name == "serving/x" and back == labels


# ------------------------------------------------------------ collector
def _push_worker(collector, k, step_rate=10.0, requests=3):
    reg = MetricsRegistry()
    for _ in range(requests):
        reg.counter("serving/requests").add(1.0)
    reg.histogram("serving/ttft_ms").observe(5.0 * (k + 1))
    reg.gauge("serving/tokens_per_s").set(100.0)
    ident = fleet.ProcessIdentity("testrun", k, host="h", role="replica")
    client = FleetClient(collector.url, identity=ident, registry=reg)
    assert client.register()["ok"]
    ack = client.push(heartbeat_extra={"step_rate": step_rate})
    assert ack["ok"]
    return reg, client


def test_collector_federates_and_rolls_up():
    col = FleetCollector().start()
    try:
        regs = [_push_worker(col, k)[0] for k in range(3)]
        fed = col.federated_registry()
        # counters: bit-exact sum of the per-process registries
        expected = sum(r.counter("serving/requests").value for r in regs)
        assert fed.counter("serving/requests").value == expected
        # histogram: merged count
        assert fed.histogram("serving/ttft_ms").count == 3
        # gauges: per-proc children + rollup
        assert fed.gauge("serving/tokens_per_s", proc="p1").value == 100.0
        assert fed.gauge("fleet/tokens_per_s").value == 300.0
        assert fed.gauge("fleet/processes").value == 3.0
        assert fed.gauge("fleet/step_rate_min").value == 10.0
        text = col.render_prometheus()
        assert "dstpu_fleet_processes" in text
        assert 'dstpu_serving_tokens_per_s{proc="p2"}' in text
        # federated view carries no single-process info stamp
        assert "dstpu_process_info" not in text
    finally:
        col.stop()


def test_collector_per_role_rollups():
    """Disagg topology rollups (ISSUE 14): role membership and role-summed
    serving rates read as labelled children of the federated view."""
    col = FleetCollector().start()
    try:
        for k, role in enumerate(("prefill", "decode", "decode")):
            reg = MetricsRegistry()
            reg.gauge("serving/tokens_per_s").set(100.0 * (k + 1))
            ident = fleet.ProcessIdentity("testrun", k, host="h", role=role)
            client = FleetClient(col.url, identity=ident, registry=reg)
            assert client.register()["ok"]
            assert client.push(heartbeat_extra={"step_rate": 10.0 * (k + 1)})["ok"]
        fed = col.federated_registry()
        assert fed.gauge("fleet/role_processes", role="prefill").value == 1.0
        assert fed.gauge("fleet/role_processes", role="decode").value == 2.0
        # role-summed tokens/s: decode pool = procs 1+2 = 200+300
        assert fed.gauge("fleet/tokens_per_s", role="decode").value == 500.0
        assert fed.gauge("fleet/tokens_per_s", role="prefill").value == 100.0
        # unlabelled rollup unchanged (the whole fleet)
        assert fed.gauge("fleet/tokens_per_s").value == 600.0
        assert fed.gauge("fleet/step_rate_min", role="decode").value == 20.0
        assert fed.gauge("fleet/step_rate_min").value == 10.0
        ledger = col.ledger()
        assert {r["identity"]["role"] for r in ledger["processes"]} == \
            {"prefill", "decode"}
    finally:
        col.stop()


def test_collector_http_endpoints_and_ledger():
    col = FleetCollector(stale_after_s=30.0).start()
    try:
        _push_worker(col, 1, step_rate=10.0)
        _push_worker(col, 2, step_rate=9.8)
        _push_worker(col, 3, step_rate=1.0)  # the straggler
        led = json.loads(urllib.request.urlopen(
            col.url + "/fleet", timeout=5).read())
        rows = {r["identity"]["process_index"]: r for r in led["processes"]}
        assert rows[3]["straggler"] and not rows[1]["straggler"]
        assert all(not r["stale"] for r in led["processes"])
        assert all(r["clock_offset_s"] is not None for r in led["processes"])
        text = urllib.request.urlopen(
            col.url + "/metrics", timeout=5).read().decode()
        assert "dstpu_fleet_straggler" in text
        doc = json.loads(urllib.request.urlopen(
            col.url + "/metrics.json", timeout=5).read())
        assert doc["metrics"]["serving/requests"] == 9.0
        hz = json.loads(urllib.request.urlopen(
            col.url + "/healthz", timeout=5).read())
        assert hz["ok"] and hz["processes"] == 3
    finally:
        col.stop()


def test_collector_replaces_not_adds_on_repush():
    """Pushes carry cumulative snapshots: a re-push must REPLACE the
    process's prior contribution (and a worker restart's reset counters
    must not go backwards at the collector)."""
    col = FleetCollector().start()
    try:
        reg, client = _push_worker(col, 0, requests=3)
        reg.counter("serving/requests").add(2.0)  # now 5 cumulative
        client.push()
        fed = col.federated_registry()
        assert fed.counter("serving/requests").value == 5.0  # not 8
    finally:
        col.stop()


def test_collector_scrape_mode():
    """Collector-initiated federation: GET the worker's /metrics.fleet."""
    reg = MetricsRegistry()
    reg.counter("serving/requests").add(4.0)
    srv = exposition.serve_metrics(registry=reg)
    col = FleetCollector().start()
    try:
        ack = col.scrape(f"http://127.0.0.1:{srv.port}")
        assert ack["ok"]
        assert col.federated_registry().counter(
            "serving/requests").value == 4.0
    finally:
        col.stop()
        srv.stop()


def test_straggler_threshold_consistent_between_gauge_and_ledger():
    """The fleet/straggler gauge and GET /fleet must agree on who is
    straggling: both consult the collector's configured straggler_mads."""
    col = FleetCollector(straggler_mads=3.0).start()
    try:
        # p3 sits ~4 MADs below the median: straggler at 3.0, not at 6.0
        for k, rate in ((0, 10.0), (1, 10.2), (2, 9.9), (3, 9.0)):
            _push_worker(col, k, step_rate=rate)
        led = {r["identity"]["process_index"]: r["straggler"]
               for r in col.ledger()["processes"]}
        gauges = {k: v for k, v in
                  col.federated_registry().gauges().items()
                  if k.startswith("fleet/straggler")}
        assert led[3] and not led[0]
        assert gauges['fleet/straggler{proc="p3"}'] == 1.0
        assert gauges['fleet/straggler{proc="p0"}'] == 0.0
    finally:
        col.stop()


def test_flow_name_matches_across_serve_generations(tmp_path):
    """Chrome binds flow arrows on (cat, name, id): the lifecycle track's
    flow NAME must be the context's (request-id-derived) spelling, not the
    local rid's — a second serve() call's rid 0 maps to a fleet request id
    > 0 and the remote dispatch step must still bind."""
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import trace_merge

    from deepspeed_tpu.inference.lifecycle import LifecycleTracker
    from deepspeed_tpu.telemetry import export_jsonl

    # router side: local rid 0, fleet request id 7 (second-generation)
    ctx = fleet.TraceContext.mint(7, run_id="testrun")
    tr_a = Tracer(enabled=True)
    tracker = LifecycleTracker(tr_a)
    tracker.arrive(0)
    tracker.admit(0, uid=0)
    tracker.set_trace_context(0, ctx)
    tracker.mark_dispatch([0], "prefill")
    tracker.emitted(0, 1)
    tracker.finish(0)
    pa = str(tmp_path / "a.jsonl")
    export_jsonl(pa, tracer=tr_a)
    # replica side: dispatch span from the wire context
    tr_b = Tracer(enabled=True)
    with fleet.dispatch_span(fleet.TraceContext.from_wire(ctx.to_wire()),
                             tracer=tr_b):
        pass
    fleet.configure_identity(process_index=1)
    pb = str(tmp_path / "b.jsonl")
    export_jsonl(pb, tracer=tr_b)
    merged = trace_merge.merge_streams([pa, pb])
    # linked_flow_pids binds on (cat, name, id) like the viewer: both
    # processes must land under ONE bindable key
    assert trace_merge.linked_flow_pids(merged)[ctx.flow_id] == [0, 1]
    names = {e["name"] for e in merged["traceEvents"]
             if e.get("ph") in ("s", "t", "f")}
    assert names == {ctx.flow_name}


def test_engine_fleet_client_is_process_global_per_url():
    """Two engines with the same fleet_url share ONE push client/thread."""
    from deepspeed_tpu.runtime.engine import _FLEET_CLIENTS, _get_fleet_client

    col = FleetCollector().start()
    try:
        _FLEET_CLIENTS.clear()
        a = _get_fleet_client(col.url, 60.0)
        b = _get_fleet_client(col.url, 60.0)
        assert a is b
    finally:
        _FLEET_CLIENTS.clear()
        col.stop()


def test_colliding_process_indices_get_distinct_labels():
    """Two standalone workers that both defaulted to process_index 0
    (distinct minted run_ids) must not clobber each other: gauges land
    under run_id-qualified {proc=} labels and the straggler math keeps
    both rates; fleet/processes counts ALL registered members, heartbeat
    or not, matching the ledger's row count."""
    col = FleetCollector().start()
    try:
        for run, rate in (("runA", 10.0), ("runB", 10.1), ("runC", 1.0)):
            reg = MetricsRegistry()
            reg.gauge("serving/queue_depth").set(ord(run[-1]) * 1.0)
            ident = fleet.ProcessIdentity(run, 0, host="h", role="worker")
            client = FleetClient(col.url, identity=ident, registry=reg)
            client.push(heartbeat_extra={"step_rate": rate})
        # a registered-but-never-heartbeating member still counts
        col.ingest({"identity": {"run_id": "runD", "process_index": 0}})
        fed = col.federated_registry()
        gauges = fed.gauges()
        for run in ("runA", "runB", "runC"):
            key = f'serving/queue_depth{{proc="{run}/p0"}}'
            assert gauges[key] == ord(run[-1]) * 1.0, (key, gauges)
        assert gauges["fleet/processes"] == 4.0
        led = col.ledger()
        assert len(led["processes"]) == 4
        flags = {r["proc"]: r["straggler"] for r in led["processes"]}
        assert flags["runC/p0"] and not flags["runA/p0"]
        assert gauges['fleet/straggler{proc="runC/p0"}'] == 1.0
    finally:
        col.stop()


def test_cross_process_straggler_median_mad():
    rates = {"p0": 10.0, "p1": 10.2, "p2": 9.9, "p3": 1.0}
    flags = fleet.straggler_flags(rates)
    assert flags == {"p0": False, "p1": False, "p2": False, "p3": True}
    # identical healthy rates never flag on jitter (MAD floor)
    assert not any(fleet.straggler_flags(
        {f"p{i}": 10.0 for i in range(4)}).values())
    # below quorum: never flags
    assert fleet.straggler_flags({"p0": 10.0, "p1": 0.1}) == {
        "p0": False, "p1": False}


def test_push_async_latest_wins_and_flushes():
    """Hot-path pushes snapshot synchronously but pay HTTP on the worker;
    the single pending slot keeps the LATEST snapshot (cumulative dumps
    supersede), and flush() drains it."""
    col = FleetCollector().start()
    try:
        reg = MetricsRegistry()
        ident = fleet.ProcessIdentity("testrun", 1)
        client = FleetClient(col.url, identity=ident, registry=reg)
        for i in range(5):
            reg.counter("serving/requests").add(1.0)
            client.push_async()
        client.flush()
        fed = col.federated_registry()
        # the LAST snapshot (5 cumulative) landed, whatever was dropped
        assert fed.counter("serving/requests").value == 5.0
        assert client.pushes >= 1
    finally:
        col.stop()


def test_fleet_client_failures_never_raise():
    client = FleetClient("http://127.0.0.1:1", timeout_s=0.2)
    assert client.push() is None
    assert client.push_failures >= 1


# ----------------------------------------------------- distributed traces
def test_trace_context_stable_flow_id():
    a = fleet.TraceContext.mint(5, run_id="runA")
    b = fleet.TraceContext.from_wire(json.loads(json.dumps(a.to_wire())))
    assert b.flow_id == a.flow_id == fleet.flow_id_for("runA", 5)
    assert fleet.flow_id_for("runA", 6) != a.flow_id
    assert fleet.flow_id_for("runB", 5) != a.flow_id


def test_dispatch_span_emits_span_and_flow_step():
    tr = Tracer(enabled=True)
    ctx = fleet.TraceContext.mint(9, run_id="testrun")
    with fleet.dispatch_span(ctx, tracer=tr, replica=1):
        pass
    evs = tr.events()
    flow = next(e for e in evs if e["kind"] == "flow")
    span = next(e for e in evs if e["kind"] == "span")
    assert flow["id"] == ctx.flow_id and flow["ph"] == "t"
    assert span["name"] == "serve:dispatch"
    assert span["args"]["request_id"] == 9
    # the flow step is INSIDE the span (the arrow binds to the slice)
    assert span["ts"] <= flow["ts"] <= span["ts"] + span["dur"]
    # disabled tracer: no-op, no events
    tr2 = Tracer(enabled=False)
    with fleet.dispatch_span(ctx, tracer=tr2):
        pass
    assert tr2.events() == []


def test_trace_merge_joins_streams(tmp_path):
    """Two tracers (distinct identities, offset origins) -> one merged
    trace: distinct pids, aligned timeline, flow linked across pids."""
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import trace_merge

    from deepspeed_tpu.telemetry import export_jsonl

    ctx = fleet.TraceContext.mint(3, run_id="testrun")
    # router process: admission + flow start
    tr_a = Tracer(enabled=True)
    with tr_a.span("admit", cat="router"):
        tr_a.flow(f"req-{ctx.request_id}", ctx.flow_id, "start")
    fleet.configure_identity(process_index=0, role="router")
    pa = str(tmp_path / "a.jsonl")
    export_jsonl(pa, tracer=tr_a)
    # replica process: dispatch span + flow step (identity switched to p1
    # before ITS export — each stream carries its own meta line)
    tr_b = Tracer(enabled=True)
    tr_b._origin_unix = tr_a.origin_unix() + 0.5  # skewed origin
    with fleet.dispatch_span(ctx, tracer=tr_b):
        pass
    fleet.configure_identity(process_index=1, role="replica")
    pb = str(tmp_path / "b.jsonl")
    export_jsonl(pb, tracer=tr_b)

    merged = trace_merge.merge_streams([pa, pb])
    evs = merged["traceEvents"]
    pids = {e["pid"] for e in evs if e.get("ph") not in ("M",)}
    assert pids == {0, 1}
    links = trace_merge.linked_flow_pids(merged)
    assert links[ctx.flow_id] == [0, 1]  # the cross-process arrow
    # the replica's dispatch span landed 0.5s later on the merged timeline
    disp = next(e for e in evs if e.get("name") == "serve:dispatch")
    admit = next(e for e in evs if e.get("name") == "admit")
    assert disp["ts"] >= admit["ts"] + 0.4e6  # us
    # process metadata names both roles
    names = {e["pid"]: e["args"]["name"] for e in evs
             if e.get("name") == "process_name"}
    assert "router" in names[0] and "replica" in names[1]


def test_trace_merge_applies_ledger_offsets(tmp_path):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import trace_merge

    from deepspeed_tpu.telemetry import export_jsonl

    tr = Tracer(enabled=True)
    tr.instant("x")
    pa = str(tmp_path / "a.jsonl")
    export_jsonl(pa, tracer=tr)
    ledger = {"processes": [{"proc": "testrun/p0", "clock_offset_s": 2.0}]}
    lp = tmp_path / "fleet.json"
    lp.write_text(json.dumps(ledger))
    m0 = trace_merge.merge_streams([pa])
    m1 = trace_merge.merge_streams([pa], ledger=str(lp))
    # single stream: offset shifts the base too, timeline unchanged — but
    # the offset must parse and apply without error
    assert len(m1["traceEvents"]) == len(m0["traceEvents"])


# ------------------------------------------------------------- /healthz
def test_healthz_reports_identity_step_age_and_size():
    reg = MetricsRegistry()
    reg.counter("serving/requests").add(1)
    reg.gauge("serving/queue_depth").set(2)
    fleet.note_step(42)
    srv = exposition.serve_metrics(registry=reg)
    try:
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz", timeout=5).read())
        assert doc["ok"] and doc["identity"]["run_id"] == "testrun"
        assert doc["step"] == 42 and doc["age_s"] is not None
        assert doc["registry_size"] == 2
        dump = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics.fleet", timeout=5).read())
        assert dump["counters"]["serving/requests"] == 1.0
    finally:
        srv.stop()


def test_last_step_info_before_any_step():
    fleet.reset_identity()
    assert fleet.last_step_info() == {"step": None, "age_s": None}


# ---------------------------------------------------- engine config wiring
def test_engine_fleet_url_config_wires_client_and_heartbeat():
    """`telemetry.fleet_url` builds a FleetClient on the engine, the
    per-step note_step feeds the heartbeat, and the collector's ledger sees
    the training process after a couple of steps."""
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    col = FleetCollector().start()
    try:
        cfg = TransformerConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=2, max_seq_len=32)
        eng, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(cfg, example_seq_len=16),
            config={
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1},
                "steps_per_print": 10_000,
                "telemetry": {"enabled": True, "fleet_url": col.url,
                              "fleet_push_interval_s": 60.0,
                              "fleet_role": "train"},
            })
        assert eng._fleet_client is not None
        batch = {"input_ids": np.zeros((eng.train_batch_size, 16), np.int32)}
        for _ in range(2):
            eng.train_batch(batch)
        # the interval is long; push explicitly (what the daemon would do)
        ack = eng._fleet_client.push()
        assert ack["ok"]
        led = col.ledger()
        row = next(r for r in led["processes"]
                   if r["identity"]["role"] == "train")
        assert row["heartbeat"]["step"] == 2
        assert row["heartbeat"]["last_step_age_s"] is not None
        assert row["clock_offset_s"] is not None
        fed = col.federated_registry()
        # the training registry federated: span histograms made it across
        assert fed.histogram("span/train_batch").count >= 2
    finally:
        col.stop()


# -------------------------------------------- 3-process integration smoke
def test_three_process_fleet_smoke(tmp_path):
    """The acceptance gate: collector + 2 real CPU worker processes.
    Federated counters bit-exactly equal the per-process sums; the merged
    trace links router admission flows into both workers' serve:dispatch
    spans."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "fleet_smoke.py"),
         "--out", str(tmp_path), "--workers", "2", "--requests", "2"],
        capture_output=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO_ROOT)
    assert out.returncode == 0, out.stdout.decode() + out.stderr.decode()[-800:]
    doc = json.loads(out.stdout.decode().strip().splitlines()[-1])
    assert doc["ok"]
    assert doc["counters_bit_exact"]
    assert doc["federated_requests"] == doc["expected_requests"] == 10.0
    assert doc["trace_linked"] and doc["cross_process_flow_links"] >= 1
    assert doc["dispatch_pids"] == [1, 2]
    assert doc["ledger_ok"] and doc["ledger_replicas"] == 2
    # the merged trace artifact is a loadable Chrome trace with 3 processes
    merged = json.load(open(doc["merged_trace"]))
    pnames = [e["args"]["name"] for e in merged["traceEvents"]
              if e.get("name") == "process_name"]
    assert len(pnames) == 3
