"""Declarative alert rules over the metrics registry and the event stream.

The incident plane's routing half (ISSUE 20): detectors *emit* typed events
(``telemetry/events.py``); this engine decides which conditions page
someone. Three rule kinds, all evaluated against process-local state on a
cadence (an injectable clock makes the state machine unit-testable):

  - **threshold** — a registry metric (every labelled child matching the
    base name, or one exact ``name{k="v"}`` child) compared against a bound
    (``> < >= <= ==``). Counters/gauges compare their value; histograms
    compare their observation count.
  - **absence** — liveness inverted: fires when the metric is MISSING from
    the registry or its value has not *changed* within ``window_s`` (a
    stalled step counter is the canonical page).
  - **event_rate** — at least ``value`` events matching
    (subsystem, kind, min severity) inside the trailing ``window_s``.

State machine per (rule, labelled child): inactive -> pending (condition
true, waiting out ``for_s``) -> firing -> resolved (condition clear for
``resolve_s`` — the flap damper; a clear shorter than that never resolves).
Re-fires inside ``refire_suppress_s`` of the previous notification keep the
state transition but suppress the notification (counted, never silent).

Firing/resolution notify the configured sinks and ALSO emit ``alerts/*``
events, so alerts federate to the collector and correlate into incidents
like any other detector output. The webhook sink does its HTTP on a daemon
worker thread with a bounded queue and never raises into the evaluation
path — the PR-13 ``push_async`` discipline.

``alerts/firing{rule=}`` gauges expose the live state to every scrape.
"""

from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from deepspeed_tpu.telemetry.events import (
    Event,
    get_event_stream,
    severity_rank,
)
from deepspeed_tpu.telemetry.registry import decode_key, encode_labels
from deepspeed_tpu.utils.logging import logger

_OPS: Dict[str, Callable[[float, float], bool]] = {
    ">": lambda a, b: a > b,
    "<": lambda a, b: a < b,
    ">=": lambda a, b: a >= b,
    "<=": lambda a, b: a <= b,
    "==": lambda a, b: a == b,
}


@dataclass
class AlertRule:
    """One declarative rule. ``labels`` narrows threshold/absence matching
    to one labelled child (exact match); empty matches every child of the
    base name. Dedup identity is (rule name, matched child labels)."""

    name: str
    kind: str = "threshold"          # threshold | absence | event_rate
    severity: str = "warn"
    metric: Optional[str] = None     # threshold/absence: registry base name
    labels: Dict[str, str] = field(default_factory=dict)
    op: str = ">"
    value: float = 0.0               # threshold bound / event-rate count
    window_s: float = 60.0           # absence staleness / event-rate window
    for_s: float = 0.0               # condition must hold before firing
    resolve_s: float = 0.0           # condition must clear before resolving
    refire_suppress_s: float = 0.0   # notification dedup after a resolve
    subsystem: Optional[str] = None  # event_rate: event subsystem filter
    event_kind: Optional[str] = None  # event_rate: event kind filter
    min_severity: str = "warn"       # event_rate: severity floor
    summary: str = ""                # human template; {value} interpolates

    def __post_init__(self):
        if self.kind not in ("threshold", "absence", "event_rate"):
            raise ValueError(f"rule {self.name}: kind {self.kind!r}")
        if self.op not in _OPS:
            raise ValueError(f"rule {self.name}: op {self.op!r}")
        if self.kind in ("threshold", "absence") and not self.metric:
            raise ValueError(f"rule {self.name}: {self.kind} needs a metric")
        if self.kind == "event_rate" and not (self.subsystem or self.event_kind):
            raise ValueError(
                f"rule {self.name}: event_rate needs subsystem and/or kind")

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "AlertRule":
        return cls(**{k: v for k, v in d.items()})


@dataclass
class _InstanceState:
    state: str = "inactive"          # inactive | pending | firing
    pending_since: float = 0.0
    firing_since: float = 0.0
    clear_since: Optional[float] = None
    last_value: float = 0.0
    last_notified: float = -1e18     # wall time of the last notification


# --------------------------------------------------------------------- sinks
class LogSink:
    """Notifications as log lines (warning on fire, info on resolve)."""

    name = "log"

    def notify(self, n: Dict[str, Any]) -> None:
        line = (f"[alerts] {n['state'].upper()} {n['rule']}"
                f"{n.get('labels_key', '')} value={n.get('value')}"
                f" severity={n['severity']}: {n.get('summary', '')}")
        (logger.warning if n["state"] == "firing" else logger.info)(line)


class JsonlSink:
    """Notifications appended to a JSONL file (post-mortem joins read it)."""

    name = "jsonl"

    def __init__(self, path: str):
        self.path = path

    def notify(self, n: Dict[str, Any]) -> None:
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        with open(self.path, "a") as f:
            f.write(json.dumps(n) + "\n")


class WebhookSink:
    """POST each notification as JSON to a URL — on a daemon worker thread
    with a bounded queue, so a dead receiver can never block or raise into
    the evaluation path (the ``FleetClient.push_async`` discipline).
    Delivery failures are counted and warned once, never raised."""

    name = "webhook"

    def __init__(self, url: str, timeout: float = 2.0, queue_max: int = 64):
        self.url = url
        self.timeout = float(timeout)
        self.failures = 0
        self.delivered = 0
        self._queue: List[Dict[str, Any]] = []
        self._queue_max = int(queue_max)
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._worker: Optional[threading.Thread] = None
        self._stop = False
        self._warned = False

    def notify(self, n: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._queue) >= self._queue_max:
                self._queue.pop(0)  # oldest-out: latest state wins
            self._queue.append(n)
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(
                    target=self._drain, name="alerts-webhook", daemon=True)
                self._worker.start()
            self._wake.notify()

    def _drain(self) -> None:
        while True:
            with self._lock:
                while not self._queue and not self._stop:
                    self._wake.wait(timeout=1.0)
                if self._stop and not self._queue:
                    return
                n = self._queue.pop(0)
            try:
                self._post(n)
                with self._lock:
                    self.delivered += 1
            except Exception as e:  # noqa: BLE001 - sink never raises
                with self._lock:
                    self.failures += 1
                    warned, self._warned = self._warned, True
                if not warned:
                    logger.warning(
                        f"alerts: webhook {self.url} delivery failed ({e}); "
                        "further failures counted silently")

    def _post(self, n: Dict[str, Any]) -> None:
        import urllib.request

        body = json.dumps(n).encode("utf-8")
        req = urllib.request.Request(
            self.url, data=body,
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=self.timeout) as resp:
            resp.read()

    def flush(self, timeout: float = 5.0) -> None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            with self._lock:
                if not self._queue:
                    return
            time.sleep(0.01)

    def stop(self) -> None:
        with self._lock:
            self._stop = True
            self._wake.notify_all()


# -------------------------------------------------------------------- engine
class AlertEngine:
    """Evaluates rules on demand (:meth:`evaluate`) or on a daemon cadence
    (:meth:`start`). ``clock`` is injectable so tests drive the pending ->
    firing -> resolved machine with a fake clock."""

    def __init__(self, rules: Optional[List[AlertRule]] = None,
                 registry=None, stream=None,
                 sinks: Optional[List[Any]] = None,
                 clock: Callable[[], float] = time.time):
        self.rules: List[AlertRule] = list(rules or [])
        self._registry = registry
        self.stream = stream or get_event_stream()
        self.sinks: List[Any] = list(sinks) if sinks is not None else [LogSink()]
        self.clock = clock
        self._lock = threading.Lock()
        # (rule.name, labels_key) -> _InstanceState
        self._instances: Dict[tuple, _InstanceState] = {}
        # metric child key -> (last value, last change wall time) for absence
        self._last_changed: Dict[str, tuple] = {}
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self.evaluations = 0

    @property
    def registry(self):
        if self._registry is None:
            from deepspeed_tpu.telemetry.tracer import get_tracer

            self._registry = get_tracer().registry
        return self._registry

    def add_rule(self, rule: AlertRule) -> None:
        with self._lock:
            self.rules.append(rule)

    # ----------------------------------------------------------- conditions
    def _metric_children(self, rule: AlertRule) -> Dict[str, float]:
        """Current value of every registry child matching the rule's metric
        (counters + gauges by value, histograms by count), keyed by the
        encoded child key with the base name stripped."""
        want = rule.metric
        sel = encode_labels(rule.labels) if rule.labels else None
        out: Dict[str, float] = {}
        for kind, base, m in self.registry.iter_metrics():
            if base != want:
                continue
            child = encode_labels(m.labels)
            if sel is not None and child != sel:
                continue
            if kind == "histogram":
                out[child] = float(m.state()["count"])
            else:
                out[child] = float(m.value)
        return out

    def _condition_instances(self, rule: AlertRule, now: float,
                             ) -> Dict[str, tuple]:
        """labels_key -> (active, value) for every instance the rule
        currently addresses."""
        if rule.kind == "threshold":
            children = self._metric_children(rule)
            return {k: (_OPS[rule.op](v, rule.value), v)
                    for k, v in children.items()}
        if rule.kind == "absence":
            children = self._metric_children(rule)
            if not children:
                # missing entirely: one instance under the rule's own labels
                key = encode_labels(rule.labels)
                return {key: (True, float("nan"))}
            out = {}
            for k, v in children.items():
                full = (rule.metric or "") + k
                prev = self._last_changed.get(full)
                if prev is None or prev[0] != v:
                    self._last_changed[full] = (v, now)
                    out[k] = (False, v)
                else:
                    out[k] = (now - prev[1] >= rule.window_s, v)
            return out
        # event_rate
        floor = severity_rank(rule.min_severity)
        n = 0
        for ev in self.stream.events(since_ts=now - rule.window_s):
            if severity_rank(ev.severity) < floor:
                continue
            if rule.subsystem is not None and ev.subsystem != rule.subsystem:
                continue
            if rule.event_kind is not None and ev.kind != rule.event_kind:
                continue
            n += ev.count
        key = encode_labels(rule.labels)
        return {key: (_OPS[rule.op](float(n), rule.value), float(n))}

    # ----------------------------------------------------------- evaluation
    def evaluate(self, now: Optional[float] = None) -> List[Dict[str, Any]]:
        """One evaluation pass; returns the notifications produced (also
        delivered to every sink)."""
        now = self.clock() if now is None else float(now)
        notifications: List[Dict[str, Any]] = []
        with self._lock:
            rules = list(self.rules)
        for rule in rules:
            try:
                instances = self._condition_instances(rule, now)
            except Exception as e:  # noqa: BLE001 - a bad rule must not
                # take down the evaluation of every other rule
                self.registry.counter("alerts/rule_errors",
                                      rule=rule.name).add(1)
                logger.debug(f"alerts: rule {rule.name} errored: {e}")
                continue
            with self._lock:
                for labels_key, (active, value) in instances.items():
                    n = self._step_instance(rule, labels_key, active,
                                            value, now)
                    if n is not None:
                        notifications.append(n)
                firing = sum(
                    1 for (rn, _lk), st in self._instances.items()
                    if rn == rule.name and st.state == "firing")
            self.registry.gauge("alerts/firing", rule=rule.name).set(
                float(firing))
        self.evaluations += 1
        self.registry.counter("alerts/evaluations").add(1)
        for n in notifications:
            self._deliver(n)
        return notifications

    def _step_instance(self, rule: AlertRule, labels_key: str, active: bool,
                       value: float, now: float) -> Optional[Dict[str, Any]]:
        key = (rule.name, labels_key)
        st = self._instances.get(key)
        if st is None:
            st = self._instances[key] = _InstanceState()
        st.last_value = value
        if active:
            st.clear_since = None
            if st.state == "inactive":
                st.state = "pending"
                st.pending_since = now
            if st.state == "pending" and now - st.pending_since >= rule.for_s:
                st.state = "firing"
                st.firing_since = now
                return self._notification(rule, labels_key, st, "firing",
                                          value, now)
            return None
        # condition clear
        if st.state == "pending":
            st.state = "inactive"
            return None
        if st.state == "firing":
            if st.clear_since is None:
                st.clear_since = now
            if now - st.clear_since >= rule.resolve_s:
                st.state = "inactive"
                st.clear_since = None
                return self._notification(rule, labels_key, st, "resolved",
                                          value, now)
        return None

    def _notification(self, rule: AlertRule, labels_key: str,
                      st: _InstanceState, state: str, value: float,
                      now: float) -> Optional[Dict[str, Any]]:
        suppressed = (state == "firing"
                      and now - st.last_notified < rule.refire_suppress_s)
        if state == "firing":
            st.last_notified = now
        if suppressed:
            self.registry.counter("alerts/suppressed", rule=rule.name).add(1)
            return None
        self.registry.counter(
            "alerts/fired" if state == "firing" else "alerts/resolved",
            rule=rule.name).add(1)
        summary = rule.summary or f"{rule.kind} rule {rule.name}"
        try:
            summary = summary.format(value=value)
        except Exception:  # noqa: BLE001 - a bad template stays literal
            pass
        from deepspeed_tpu.telemetry.fleet import get_identity

        n = {
            "ts": now, "rule": rule.name, "state": state,
            "severity": rule.severity, "value": value,
            "labels_key": labels_key, "summary": summary,
            "identity": get_identity().to_dict(),
        }
        return n

    def _deliver(self, n: Dict[str, Any]) -> None:
        # alerts are events too: they federate + correlate like any detector
        labels = decode_key("x" + n["labels_key"])[1] if n["labels_key"] else {}
        labels["rule"] = n["rule"]
        self.stream.emit(
            "alerts", n["state"], n["summary"],
            severity=n["severity"] if n["state"] == "firing" else "info",
            labels=labels, ts=n["ts"])
        for sink in self.sinks:
            try:
                sink.notify(n)
            except Exception as e:  # noqa: BLE001 - PR-13 discipline: a sink
                # failure must never reach the caller (which may be a step)
                self.registry.counter(
                    "alerts/sink_failures",
                    sink=getattr(sink, "name", type(sink).__name__)).add(1)
                logger.debug(f"alerts: sink {sink!r} failed: {e}")

    # -------------------------------------------------------------- helpers
    def firing(self) -> List[Dict[str, Any]]:
        """Currently-firing instances (rule, labels, since, last value)."""
        with self._lock:
            return [
                {"rule": rn, "labels_key": lk, "since": st.firing_since,
                 "value": st.last_value}
                for (rn, lk), st in sorted(self._instances.items())
                if st.state == "firing"]

    def start(self, interval_s: float = 5.0) -> "AlertEngine":
        """Evaluate on a daemon cadence until :meth:`stop`."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop_evt.clear()

        def loop():
            while not self._stop_evt.wait(interval_s):
                try:
                    self.evaluate()
                except Exception as e:  # noqa: BLE001 - cadence survives
                    logger.debug(f"alerts: evaluation failed: {e}")

        self._thread = threading.Thread(
            target=loop, name="alerts-engine", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        for sink in self.sinks:
            stop = getattr(sink, "stop", None)
            if stop is not None:
                stop()


def default_rules() -> List[AlertRule]:
    """The stock rule pack covering the repo's detectors: quiet on a clean
    run (every threshold is on a *defect* counter that stays zero), loud on
    the faults ``tools/alerts_smoke.py`` injects."""
    return [
        AlertRule(name="numerics_divergence", metric="numerics/divergence_events",
                  op=">", value=0, severity="critical",
                  summary="cross-replica divergence events: {value}"),
        AlertRule(name="replica_dead", kind="event_rate", subsystem="fabric",
                  event_kind="replica_dead", window_s=300.0, op=">", value=0,
                  severity="critical",
                  summary="dead serving replicas detected: {value}"),
        AlertRule(name="replica_unreachable", kind="event_rate",
                  subsystem="fabric", event_kind="replica_unreachable",
                  window_s=300.0, op=">", value=0, severity="critical",
                  summary="unreachable serving replicas: {value}"),
        AlertRule(name="rpc_failures", kind="event_rate", subsystem="fabric",
                  event_kind="rpc_failure", window_s=300.0, op=">", value=2,
                  severity="warn",
                  summary="fabric RPC failures in window: {value}"),
        AlertRule(name="health_abort", kind="event_rate", subsystem="health",
                  event_kind="abort", window_s=600.0, op=">", value=0,
                  severity="critical",
                  summary="training health abort: {value}"),
        AlertRule(name="recompile_storm", kind="event_rate",
                  subsystem="recompile", event_kind="storm", window_s=600.0,
                  op=">", value=0, severity="warn",
                  summary="recompile storms: {value}"),
    ]


# ----------------------------------------------------------- process-global
_engine: Optional[AlertEngine] = None
_engine_lock = threading.Lock()


def get_alert_engine() -> AlertEngine:
    """The process-global engine (created empty — rules come from config or
    :func:`default_rules`)."""
    global _engine
    if _engine is None:
        with _engine_lock:
            if _engine is None:
                _engine = AlertEngine(rules=[])
    return _engine


def configure_alerts(rules: Optional[List[Any]] = None,
                     use_defaults: bool = True,
                     jsonl_path: Optional[str] = None,
                     webhook_url: Optional[str] = None,
                     interval_s: Optional[float] = None) -> AlertEngine:
    """(Re)configure the process-global engine: replace the rule set
    (dicts are parsed via :meth:`AlertRule.from_dict`), rebuild sinks, and
    (when ``interval_s`` is set) start the cadence thread."""
    eng = get_alert_engine()
    new_rules: List[AlertRule] = list(default_rules()) if use_defaults else []
    for r in rules or []:
        new_rules.append(r if isinstance(r, AlertRule)
                         else AlertRule.from_dict(r))
    sinks: List[Any] = [LogSink()]
    if jsonl_path:
        sinks.append(JsonlSink(jsonl_path))
    if webhook_url:
        sinks.append(WebhookSink(webhook_url))
    with eng._lock:
        eng.rules = new_rules
        eng._instances.clear()
    eng.sinks = sinks
    if interval_s is not None and interval_s > 0:
        eng.start(interval_s)
    return eng
