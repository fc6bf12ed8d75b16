"""Incident plane (telemetry/events.py + telemetry/alerts.py) — ISSUE 20.

Pinned here:
  - the structured event stream: bounded ring + monotonic seq, dedup-window
    folding onto the first occurrence, severity validation, subscriber
    failures counted but never raised, JSONL export/load round trip
  - the shared warn-once helper: logs exactly once per key AND emits one
    typed event (the dedup of the former per-module ``_warn_once`` copies)
  - the alert state machine on a FAKE clock: inactive -> pending (for_s) ->
    firing -> resolved (resolve_s flap damper), refire suppression,
    absence rules (missing AND stalled), event-rate rules, rule-error
    isolation, ``alerts/firing{rule=}`` gauges
  - sink discipline: a raising sink and a dead-receiver webhook are counted,
    never propagated into the evaluation path
  - cross-process incident correlation over real collector ingestion:
    two processes' events fold into ONE incident with a stable id; a
    re-pushed tail is idempotent (per-proc seq high-watermark); the
    ``incident_key`` label bridges events across the time window
  - program identity: the engine update jaxpr is identical with the event
    plane absent, enabled, and disabled — emission is host-side only
"""

import json
import logging

import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.telemetry import alerts as alerts_mod
from deepspeed_tpu.telemetry import events as events_mod
from deepspeed_tpu.telemetry import fleet, get_tracer
from deepspeed_tpu.telemetry.alerts import (
    AlertEngine,
    AlertRule,
    JsonlSink,
    WebhookSink,
)
from deepspeed_tpu.telemetry.collector import FleetCollector, correlate_events
from deepspeed_tpu.telemetry.events import Event, EventStream, WarnOnceSet
from deepspeed_tpu.telemetry.registry import MetricsRegistry
from tests.unit.simple_model import simple_model_spec


@pytest.fixture(autouse=True)
def _reset():
    fleet.reset_identity()
    fleet.configure_identity(run_id="testrun", process_index=0,
                             host="testhost", role="train")
    events_mod.reset_warn_once()
    events_mod.configure_events(capacity=2048, dedup_window_s=300.0,
                                jsonl_path="", enabled=True)
    events_mod.get_event_stream().clear()
    tr = get_tracer()
    tr.configure(enabled=False)
    tr.reset()
    yield
    events_mod.reset_warn_once()
    events_mod.get_event_stream().clear()
    fleet.reset_identity()
    get_tracer().configure(enabled=False)
    get_tracer().reset()


@pytest.fixture
def dslog():
    lg = logging.getLogger("deepspeed_tpu")
    prev = lg.propagate
    lg.propagate = True
    yield lg
    lg.propagate = prev


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t


def _stream(clock=None, capacity=64, **kw):
    return EventStream(capacity=capacity, registry=MetricsRegistry(),
                       clock=clock or FakeClock(), **kw)


# ------------------------------------------------------------ event stream
def test_ring_is_bounded_and_seq_monotonic():
    s = _stream(capacity=4)
    for i in range(6):
        s.emit("numerics", "tick", f"m{i}", severity="info")
    evs = s.events()
    assert len(evs) == 4 and s.total_emitted == 6 and s.dropped == 2
    assert [e.seq for e in evs] == [3, 4, 5, 6]
    assert [e.message for e in evs] == ["m2", "m3", "m4", "m5"]
    assert float(s.registry.gauge("events/buffered").value) == 4.0


def test_dedup_folds_onto_first_occurrence():
    clk = FakeClock(0.0)
    s = _stream(clock=clk, dedup_window_s=300.0)
    first = s.emit("coll", "drift", "drifting", dedup_key="coll:drift:x")
    clk.t = 10.0
    assert s.emit("coll", "drift", "drifting", dedup_key="coll:drift:x") is None
    assert first.count == 2 and s.total_emitted == 1
    assert float(s.registry.counter("events/deduped").value) == 1.0
    # past the window: a fresh event, not a fold
    clk.t = 400.0
    again = s.emit("coll", "drift", "drifting", dedup_key="coll:drift:x")
    assert again is not None and again.seq == 2 and first.count == 2


def test_severity_validated_and_filters_apply():
    clk = FakeClock(0.0)
    s = _stream(clock=clk)
    with pytest.raises(ValueError):
        s.emit("numerics", "x", "m", severity="fatal")
    s.emit("numerics", "a", "m", severity="info")
    clk.t = 5.0
    s.emit("fabric", "b", "m", severity="warn")
    clk.t = 9.0
    s.emit("fabric", "c", "m", severity="critical")
    assert len(s.events(min_severity="warn")) == 2
    assert [e.kind for e in s.events(subsystem="fabric")] == ["b", "c"]
    assert [e.kind for e in s.events(since_ts=5.0)] == ["b", "c"]
    assert [e["kind"] for e in s.drain_since(2)] == ["c"]
    assert s.last_seq == 3


def test_disabled_stream_emits_nothing():
    s = _stream()
    s.enabled = False
    assert s.emit("numerics", "x", "m") is None
    assert s.total_emitted == 0 and not s.events()


def test_subscriber_failure_is_counted_never_raised():
    s = _stream()
    seen = []

    def bad(ev):
        raise RuntimeError("boom")

    s.subscribe(bad)
    s.subscribe(seen.append)
    ev = s.emit("health", "probe", "m")
    assert ev is not None and seen == [ev]
    assert float(s.registry.counter("events/subscriber_failures").value) == 1.0


def test_jsonl_round_trip(tmp_path):
    s = _stream(clock=FakeClock(123.5))
    s.emit("perf", "regression", "slow", severity="warn",
           labels={"suite": "train"}, dedup_key="perf:x", step=7)
    s.emit("perf", "regression", "slow", dedup_key="perf:x")  # folds
    path = s.export_jsonl(str(tmp_path / "event_log.jsonl"))
    lines = [json.loads(ln) for ln in open(path, encoding="utf-8")]
    assert lines[0]["kind"] == "process_meta"
    assert lines[0]["schema"] == "dstpu_events_v1"
    assert lines[0]["identity"]["run_id"] == "testrun"
    back = events_mod.load_events_jsonl(path)
    assert len(back) == 1
    ev = back[0]
    assert (ev.subsystem, ev.kind, ev.count, ev.step) == (
        "perf", "regression", 2, 7)
    assert ev.labels == {"suite": "train"}
    # wire-dict round trip is exact
    assert Event.from_dict(ev.to_dict()).to_dict() == ev.to_dict()


# --------------------------------------------------------------- warn-once
def test_warn_once_set_logs_once_and_emits_typed_event(dslog, caplog):
    w = WarnOnceSet(subsystem="coll", default_kind="observatory_warning")
    with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
        assert w("k1", "the sky is falling") is True
        assert w("k1", "the sky is falling") is False
    assert [r for r in caplog.records
            if "sky is falling" in r.message][0] and len(
        [r for r in caplog.records if "sky is falling" in r.message]) == 1
    evs = events_mod.get_event_stream().events(subsystem="coll")
    assert len(evs) == 1
    assert (evs[0].kind, evs[0].dedup_key) == ("observatory_warning", "k1")
    assert w.seen("k1") and not w.seen("k2")
    w.reset()
    assert w("k1", "again") is True


def test_module_warn_once_defaults_key_to_message(dslog, caplog):
    with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
        assert events_mod.warn_once("legacy warning path") is True
        assert events_mod.warn_once("legacy warning path") is False
    evs = events_mod.get_event_stream().events(subsystem="logging")
    assert len(evs) == 1 and evs[0].kind == "warning_once"


# ------------------------------------------------------ alert state machine
def _engine_with(rules, clk, stream=None):
    reg = MetricsRegistry()
    return AlertEngine(rules=rules, registry=reg,
                       stream=stream or _stream(clock=clk),
                       sinks=[], clock=clk), reg


def test_threshold_pending_for_duration_then_firing_then_resolved():
    clk = FakeClock(0.0)
    rule = AlertRule(name="hot", metric="perf/regression_events",
                     op=">", value=0, for_s=10.0, resolve_s=10.0,
                     summary="regressions: {value}")
    eng, reg = _engine_with([rule], clk)
    g = reg.gauge("perf/regression_events")
    assert eng.evaluate() == [] and eng.firing() == []
    g.set(3)
    assert eng.evaluate() == []          # pending, waiting out for_s
    assert eng.firing() == []
    clk.t = 5.0
    assert eng.evaluate() == []
    clk.t = 10.0
    notes = eng.evaluate()               # for_s elapsed -> firing
    assert [n["state"] for n in notes] == ["firing"]
    assert notes[0]["summary"] == "regressions: 3.0"
    assert float(reg.gauge("alerts/firing", rule="hot").value) == 1.0
    assert [f["rule"] for f in eng.firing()] == ["hot"]
    # a clear shorter than resolve_s never resolves (flap damper)
    g.set(0)
    clk.t = 15.0
    assert eng.evaluate() == [] and eng.firing()
    g.set(2)
    clk.t = 16.0
    assert eng.evaluate() == []          # reactivated: still one firing
    g.set(0)
    clk.t = 20.0
    assert eng.evaluate() == []
    clk.t = 31.0
    notes = eng.evaluate()               # clear held resolve_s -> resolved
    assert [n["state"] for n in notes] == ["resolved"]
    assert eng.firing() == []
    assert float(reg.gauge("alerts/firing", rule="hot").value) == 0.0
    assert float(reg.counter("alerts/fired", rule="hot").value) == 1.0
    assert float(reg.counter("alerts/resolved", rule="hot").value) == 1.0


def test_pending_that_clears_never_notifies():
    clk = FakeClock(0.0)
    rule = AlertRule(name="blip", metric="perf/regression_events",
                     op=">", value=0, for_s=30.0)
    eng, reg = _engine_with([rule], clk)
    g = reg.gauge("perf/regression_events")
    g.set(1)
    eng.evaluate()
    g.set(0)
    clk.t = 5.0
    assert eng.evaluate() == []
    g.set(1)
    clk.t = 10.0
    eng.evaluate()                       # pending restarts from t=10
    clk.t = 35.0
    assert eng.evaluate() == []          # 25s < for_s: still pending
    clk.t = 40.0
    assert [n["state"] for n in eng.evaluate()] == ["firing"]


def test_refire_suppression_counts_but_keeps_state():
    clk = FakeClock(0.0)
    rule = AlertRule(name="flappy", metric="perf/regression_events",
                     op=">", value=0, refire_suppress_s=100.0)
    eng, reg = _engine_with([rule], clk)
    g = reg.gauge("perf/regression_events")
    g.set(1)
    assert [n["state"] for n in eng.evaluate()] == ["firing"]
    g.set(0)
    clk.t = 10.0
    eng.evaluate()                       # resolved (resolve_s=0)
    g.set(1)
    clk.t = 20.0
    assert eng.evaluate() == []          # re-fire inside suppress window
    assert [f["rule"] for f in eng.firing()] == ["flappy"]  # state transitioned
    assert float(reg.counter("alerts/suppressed", rule="flappy").value) == 1.0
    g.set(0)
    clk.t = 30.0
    eng.evaluate()
    g.set(1)
    clk.t = 150.0
    assert [n["state"] for n in eng.evaluate()] == ["firing"]  # window passed


def test_threshold_matches_every_labelled_child():
    clk = FakeClock(0.0)
    rule = AlertRule(name="fail", metric="fabric/rpc_failures",
                     op=">", value=0)
    eng, reg = _engine_with([rule], clk)
    reg.counter("fabric/rpc_failures", endpoint="query").add(1)
    reg.counter("fabric/rpc_failures", endpoint="admit").add(2)
    notes = eng.evaluate()
    assert len(notes) == 2
    assert {n["labels_key"] for n in notes} == {
        '{endpoint="admit"}', '{endpoint="query"}'}
    assert float(reg.gauge("alerts/firing", rule="fail").value) == 2.0


def test_absence_rule_missing_and_stalled():
    clk = FakeClock(0.0)
    rule = AlertRule(name="stalled", kind="absence", metric="fleet/last_step",
                     window_s=60.0)
    eng, reg = _engine_with([rule], clk)
    # missing entirely -> fires immediately (for_s=0)
    assert [n["state"] for n in eng.evaluate()] == ["firing"]
    # metric appears and moves -> resolves
    g = reg.gauge("fleet/last_step")
    g.set(1)
    clk.t = 10.0
    assert [n["state"] for n in eng.evaluate()] == ["resolved"]
    # value keeps changing: quiet
    g.set(2)
    clk.t = 30.0
    assert eng.evaluate() == []
    clk.t = 80.0
    assert eng.evaluate() == []          # change at t=30 restarts staleness
    # stalled past window_s -> fires again
    clk.t = 95.0
    assert [n["state"] for n in eng.evaluate()] == ["firing"]


def test_event_rate_rule_over_trailing_window():
    clk = FakeClock(0.0)
    stream = _stream(clock=clk)
    rule = AlertRule(name="rpc", kind="event_rate", subsystem="fabric",
                     event_kind="rpc_failure", window_s=300.0,
                     op=">", value=2)
    eng, _reg = _engine_with([rule], clk, stream=stream)
    for _ in range(2):
        stream.emit("fabric", "rpc_failure", "down")
    assert eng.evaluate() == []          # 2 is not > 2
    stream.emit("fabric", "rpc_failure", "down")
    notes = eng.evaluate()
    assert [n["state"] for n in notes] == ["firing"]
    assert notes[0]["value"] == 3.0
    # dedup counts fold into the rate
    stream.emit("fabric", "rpc_failure", "down", dedup_key="k")
    stream.emit("fabric", "rpc_failure", "down", dedup_key="k")
    assert eng.evaluate() == []          # already firing
    # the window slides past the burst -> resolves
    clk.t = 301.0
    assert [n["state"] for n in eng.evaluate()] == ["resolved"]


def test_rule_error_is_isolated_to_that_rule():
    clk = FakeClock(0.0)
    good = AlertRule(name="good", metric="perf/regression_events",
                     op=">", value=0)
    bad = AlertRule(name="bad", kind="event_rate", subsystem="fabric",
                    event_kind="rpc_failure")
    class BrokenEvents:
        def events(self, **kw):
            raise RuntimeError("ring poisoned")

        def emit(self, *a, **kw):        # delivery path must stay alive
            return None

    eng, reg = _engine_with([good, bad], clk)
    eng.stream = BrokenEvents()          # event-rate access now raises
    reg.gauge("perf/regression_events").set(1)
    notes = eng.evaluate()               # must not propagate the bad rule
    assert [n["rule"] for n in notes] == ["good"]
    assert float(reg.counter("alerts/rule_errors", rule="bad").value) == 1.0


def test_firing_alert_emits_alert_event_and_jsonl_sink(tmp_path):
    clk = FakeClock(0.0)
    stream = _stream(clock=clk)
    path = str(tmp_path / "notifications.jsonl")
    rule = AlertRule(name="diverged", metric="numerics/divergence_events",
                     op=">", value=0, severity="critical",
                     summary="divergence: {value}")
    reg = MetricsRegistry()
    eng = AlertEngine(rules=[rule], registry=reg, stream=stream,
                      sinks=[JsonlSink(path)], clock=clk)
    reg.counter("numerics/divergence_events").add(1)
    eng.evaluate()
    # alerts are events too: they federate + correlate like any detector
    evs = stream.events(subsystem="alerts")
    assert len(evs) == 1
    assert (evs[0].kind, evs[0].severity) == ("firing", "critical")
    assert evs[0].labels["rule"] == "diverged"
    rows = [json.loads(ln) for ln in open(path, encoding="utf-8")]
    assert rows[0]["rule"] == "diverged" and rows[0]["state"] == "firing"
    assert rows[0]["identity"]["run_id"] == "testrun"


def test_raising_sink_is_counted_never_propagated():
    clk = FakeClock(0.0)

    class BadSink:
        name = "bad"

        def notify(self, n):
            raise RuntimeError("receiver down")

    rule = AlertRule(name="r", metric="perf/regression_events",
                     op=">", value=0)
    reg = MetricsRegistry()
    eng = AlertEngine(rules=[rule], registry=reg,
                      stream=_stream(clock=clk), sinks=[BadSink()], clock=clk)
    reg.gauge("perf/regression_events").set(1)
    notes = eng.evaluate()               # must not raise
    assert [n["state"] for n in notes] == ["firing"]
    assert float(reg.counter("alerts/sink_failures", sink="bad").value) == 1.0


def test_webhook_sink_dead_receiver_never_raises():
    sink = WebhookSink("http://127.0.0.1:9/unroutable", timeout=0.2)
    for i in range(3):
        sink.notify({"rule": "r", "state": "firing", "n": i})
    sink.flush(timeout=10.0)
    sink.stop()
    assert sink.failures >= 1 and sink.delivered == 0


def test_default_rules_quiet_on_empty_state():
    clk = FakeClock(0.0)
    eng, reg = _engine_with(alerts_mod.default_rules(), clk)
    assert eng.evaluate() == [] and eng.firing() == []
    names = {r.name for r in eng.rules}
    assert {"numerics_divergence", "replica_dead",
            "replica_unreachable", "rpc_failures", "health_abort",
            "recompile_storm"} <= names
    # and loud once a defect counter moves
    reg.counter("numerics/divergence_events").add(1)
    assert {n["rule"] for n in eng.evaluate()} == {"numerics_divergence"}


# ----------------------------------------------- cross-process correlation
def _ev(ts, subsystem, kind, seq, severity="critical", **labels):
    d = {"ts": ts, "severity": severity, "subsystem": subsystem,
         "kind": kind, "message": f"{subsystem}/{kind}", "seq": seq,
         "count": 1}
    if labels:
        d["labels"] = {k: str(v) for k, v in labels.items()}
    return d


def test_collector_ingest_correlates_two_processes_into_one_incident():
    c = FleetCollector(incident_window_s=30.0)
    base = 1_000_000.0
    c.ingest({"identity": {"run_id": "r1", "process_index": 0},
              "events": [_ev(base, "numerics", "divergence", 1)]})
    c.ingest({"identity": {"run_id": "r1", "process_index": 1},
              "events": [_ev(base + 5.0, "fabric", "replica_unreachable", 1)]})
    incs = c.incidents()
    assert len(incs) == 1
    inc = incs[0]
    assert inc["run_id"] == "r1" and inc["severity"] == "critical"
    assert set(inc["kinds"]) == {"numerics/divergence",
                                 "fabric/replica_unreachable"}
    assert set(inc["procs"]) == {"r1/p0", "r1/p1"}
    # id is stable across repeated reads of the same state
    assert c.incidents()[0]["id"] == inc["id"]
    assert inc["id"].startswith("inc-")


def test_collector_repushed_tail_is_idempotent():
    c = FleetCollector()
    doc = {"identity": {"run_id": "r1", "process_index": 0},
           "events": [_ev(1.0, "health", "abort", 1),
                      _ev(2.0, "health", "abort", 2)]}
    c.ingest(doc)
    c.ingest(doc)                        # ack lost, client re-sends the tail
    assert c.events_ingested == 2 and len(c.events()) == 2
    # a genuinely new event past the watermark still appends
    c.ingest({"identity": {"run_id": "r1", "process_index": 0},
              "events": [_ev(3.0, "health", "abort", 3)]})
    assert len(c.events()) == 3


def test_incident_key_bridges_events_across_the_window():
    base = 1_000_000.0
    far = [_ev(base, "coll", "drift", 1, incident_key="perf_gate:x"),
           _ev(base + 500.0, "perf", "regression", 2,
               incident_key="perf_gate:x"),
           _ev(base + 900.0, "numerics", "divergence", 3)]
    for e in far:
        e["proc"] = "r1/p0"
        e.setdefault("identity", {"run_id": "r1", "process_index": 0})
    incs = correlate_events(far, window_s=30.0)
    assert len(incs) == 2                # key joins 1+2; 3 stands alone
    joined = max(incs, key=lambda i: i["event_count"])
    assert set(joined["kinds"]) == {"coll/drift", "perf/regression"}
    # without the stamp the same spacing is three separate incidents
    for e in far:
        e.pop("labels", None)
    assert len(correlate_events(far, window_s=30.0)) == 3


def test_correlation_separates_runs_and_respects_severity_floor():
    base = 1_000_000.0
    evs = [dict(_ev(base, "health", "abort", 1), proc="r1/p0",
                identity={"run_id": "r1", "process_index": 0}),
           dict(_ev(base + 1.0, "health", "abort", 1), proc="r2/p0",
                identity={"run_id": "r2", "process_index": 0}),
           dict(_ev(base + 2.0, "data", "note", 2, severity="info"),
                proc="r1/p0", identity={"run_id": "r1", "process_index": 0})]
    incs = correlate_events(evs, window_s=30.0)
    assert len(incs) == 2                # per-run, info below the floor
    assert {i["run_id"] for i in incs} == {"r1", "r2"}
    assert all(i["event_count"] == 1 for i in incs)


# ---------------------------------------------------------- program identity
def test_event_plane_is_jaxpr_invisible():
    """THE structural acceptance: the traced update program is one and the
    same jaxpr with the event plane absent, actively emitting, and
    disabled — emission is host-side bookkeeping, never an op in the
    step."""

    def make_engine():
        eng, *_ = deepspeed_tpu.initialize(
            model=simple_model_spec(),
            config={
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1},
                "steps_per_print": 10_000,
            })
        return eng

    def update_jaxpr(eng):
        state = eng.state
        grads = jax.tree_util.tree_map(jnp.zeros_like, state.params)

        def fn(s, g):
            return eng._update_math(s, g, s.rng, grads_are_unscaled=True)

        return str(jax.make_jaxpr(fn)(state, grads))

    stream = events_mod.get_event_stream()
    j_absent = update_jaxpr(make_engine())
    for i in range(5):
        events_mod.emit_event("bench", "tick", f"t{i}", severity="info")
    clk = FakeClock(0.0)
    AlertEngine(rules=alerts_mod.default_rules(),
                registry=MetricsRegistry(), stream=stream,
                sinks=[], clock=clk).evaluate()
    j_emitting = update_jaxpr(make_engine())
    stream.enabled = False
    j_disabled = update_jaxpr(make_engine())
    stream.enabled = True
    assert j_absent == j_emitting == j_disabled


def test_engine_config_wires_event_plane():
    eng, *_ = deepspeed_tpu.initialize(
        model=simple_model_spec(),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 10_000,
            "telemetry": {"enabled": False, "events_capacity": 99,
                          "events_dedup_window_s": 7.5},
        })
    s = events_mod.get_event_stream()
    assert s.capacity == 99 and s.dedup_window_s == 7.5
    assert eng._alert_engine is None     # alerts stay opt-in
