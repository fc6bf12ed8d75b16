"""Process-local structured event stream — the incident plane's front door.

Every detector the repo has grown (diagnostics health/anomaly/recompile, the
numerics divergence sentinel, the perf gate, the router's
liveness/migration paths, the rewind supervisor) used to terminate in a warn-once log line on whichever process
happened to notice. This module gives those warnings a second, *typed*
destination: an :class:`Event` with severity / subsystem / kind / labels /
dedup key / process identity, appended to a bounded ring, exportable as
JSONL next to the trace stream, and shippable to the fleet collector where
cross-process events correlate into incidents (``telemetry/collector.py``).

Log lines are unchanged — ``emit_event`` rides *alongside* every existing
``logger.warning``, never replaces it. Emission is host-side only (a lock,
a deque append, two counter bumps): nothing here is ever traced into a
jitted program, so the hot train/decode programs are jaxpr-identical with
the event plane on, off, or absent.

Dedup: an event carrying a ``dedup_key`` that was already seen inside
``dedup_window_s`` is not appended again — the FIRST occurrence's ``count``
is bumped and ``events/deduped`` counts the suppression. That is the
warn-once discipline, applied to the typed stream.

The shared warn-once helper (:class:`WarnOnceSet` / :func:`warn_once`)
unifies the message-keyed ``utils/logging.py`` one and the key-keyed one of
the detectors so warn-once coverage and event coverage cannot drift apart:
one call logs once AND emits the typed event.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

SEVERITIES = ("info", "warn", "critical")
_SEV_RANK = {s: i for i, s in enumerate(SEVERITIES)}


def severity_rank(severity: str) -> int:
    """info=0 < warn=1 < critical=2 (unknown severities read as info)."""
    return _SEV_RANK.get(severity, 0)


@dataclass
class Event:
    """One structured occurrence. ``ts`` is wall-clock unix seconds (events
    cross process boundaries — a shared epoch, not a per-process origin);
    ``seq`` is the per-process monotonic sequence number; ``count`` grows
    when later emissions dedup onto this event."""

    ts: float
    severity: str
    subsystem: str
    kind: str
    message: str
    labels: Dict[str, str] = field(default_factory=dict)
    dedup_key: Optional[str] = None
    seq: int = 0
    count: int = 1
    identity: Optional[Dict[str, Any]] = None
    request_id: Optional[int] = None
    flow_id: Optional[int] = None
    step: Optional[int] = None

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "ts": self.ts, "severity": self.severity,
            "subsystem": self.subsystem, "kind": self.kind,
            "message": self.message, "seq": self.seq, "count": self.count,
        }
        if self.labels:
            d["labels"] = dict(self.labels)
        if self.dedup_key is not None:
            d["dedup_key"] = self.dedup_key
        if self.identity is not None:
            d["identity"] = self.identity
        if self.request_id is not None:
            d["request_id"] = self.request_id
        if self.flow_id is not None:
            d["flow_id"] = self.flow_id
        if self.step is not None:
            d["step"] = self.step
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Event":
        return cls(
            ts=float(d.get("ts", 0.0)),
            severity=str(d.get("severity", "info")),
            subsystem=str(d.get("subsystem", "")),
            kind=str(d.get("kind", "")),
            message=str(d.get("message", "")),
            labels=dict(d.get("labels") or {}),
            dedup_key=d.get("dedup_key"),
            seq=int(d.get("seq", 0)),
            count=int(d.get("count", 1)),
            identity=d.get("identity"),
            request_id=d.get("request_id"),
            flow_id=d.get("flow_id"),
            step=d.get("step"),
        )


class EventStream:
    """Bounded ring of :class:`Event` with dedup and subscriber fan-out.

    Thread-safe; emission under load is O(1). Subscribers (the alert
    engine's event-rate rules, tests) are called OUTSIDE the stream lock
    with the appended event; a subscriber that raises is dropped from the
    hot path into a counted failure — a watcher must never break the
    detector that fed it (the PR-13 never-raise discipline).
    """

    def __init__(self, capacity: int = 2048, dedup_window_s: float = 300.0,
                 registry=None, clock: Callable[[], float] = time.time):
        self.capacity = int(capacity)
        self.dedup_window_s = float(dedup_window_s)
        self.enabled = True
        self._clock = clock
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self._seq = 0
        self._total = 0
        # dedup_key -> (first Event still holding the count, ts last seen)
        self._dedup: Dict[str, List[Any]] = {}
        self._subscribers: List[Callable[[Event], None]] = []
        self._registry = registry
        self.jsonl_path: Optional[str] = None

    # ------------------------------------------------------------- plumbing
    @property
    def registry(self):
        if self._registry is None:
            from deepspeed_tpu.telemetry.tracer import get_tracer

            self._registry = get_tracer().registry
        return self._registry

    def subscribe(self, fn: Callable[[Event], None]) -> None:
        with self._lock:
            if fn not in self._subscribers:
                self._subscribers.append(fn)

    def unsubscribe(self, fn: Callable[[Event], None]) -> None:
        with self._lock:
            if fn in self._subscribers:
                self._subscribers.remove(fn)

    # ------------------------------------------------------------- emission
    def emit(self, subsystem: str, kind: str, message: str, *,
             severity: str = "warn", labels: Optional[Dict[str, Any]] = None,
             dedup_key: Optional[str] = None, ctx=None,
             request_id: Optional[int] = None, step: Optional[int] = None,
             ts: Optional[float] = None) -> Optional[Event]:
        """Append one event; returns it, or ``None`` when disabled or
        deduped onto an earlier occurrence. ``ctx`` may be a
        :class:`~deepspeed_tpu.telemetry.fleet.TraceContext` — its
        request/flow ids become incident-correlation join keys."""
        if not self.enabled:
            return None
        if severity not in _SEV_RANK:
            raise ValueError(f"severity {severity!r}: one of {SEVERITIES}")
        now = self._clock() if ts is None else float(ts)
        flow_id = None
        if ctx is not None:
            request_id = ctx.request_id if request_id is None else request_id
            flow_id = ctx.flow_id
        with self._lock:
            if dedup_key is not None:
                hit = self._dedup.get(dedup_key)
                if hit is not None and now - hit[1] <= self.dedup_window_s:
                    hit[0].count += 1
                    hit[1] = now
                    deduped = True
                else:
                    deduped = False
            else:
                deduped = False
            if deduped:
                ev = None
            else:
                from deepspeed_tpu.telemetry.fleet import get_identity

                self._seq += 1
                self._total += 1
                ev = Event(
                    ts=now, severity=severity, subsystem=subsystem,
                    kind=kind, message=message,
                    labels={k: str(v) for k, v in (labels or {}).items()},
                    dedup_key=dedup_key, seq=self._seq,
                    identity=get_identity().to_dict(),
                    request_id=request_id, flow_id=flow_id, step=step)
                self._ring.append(ev)
                if dedup_key is not None:
                    self._dedup[dedup_key] = [ev, now]
                    if len(self._dedup) > 4 * self.capacity:
                        # bound the dedup index like the ring it shadows
                        for k in list(self._dedup)[: self.capacity]:
                            self._dedup.pop(k, None)
            subscribers = list(self._subscribers)
        reg = self.registry
        if ev is None:
            reg.counter("events/deduped").add(1)
            return None
        reg.counter("events/emitted", severity=severity).add(1)
        reg.gauge("events/buffered").set(float(len(self._ring)))
        for fn in subscribers:
            try:
                fn(ev)
            except Exception as e:  # noqa: BLE001 - never break the emitter
                reg.counter("events/subscriber_failures").add(1)
                from deepspeed_tpu.utils.logging import logger

                logger.debug(f"events: subscriber {fn!r} raised: {e}")
        return ev

    # -------------------------------------------------------------- reading
    def events(self, min_severity: Optional[str] = None,
               subsystem: Optional[str] = None,
               since_ts: Optional[float] = None,
               since_seq: Optional[int] = None) -> List[Event]:
        with self._lock:
            out = list(self._ring)
        if min_severity is not None:
            floor = severity_rank(min_severity)
            out = [e for e in out if severity_rank(e.severity) >= floor]
        if subsystem is not None:
            out = [e for e in out if e.subsystem == subsystem]
        if since_ts is not None:
            out = [e for e in out if e.ts >= since_ts]
        if since_seq is not None:
            out = [e for e in out if e.seq > since_seq]
        return out

    def drain_since(self, seq: int) -> List[Dict[str, Any]]:
        """Wire dicts of every buffered event with ``seq`` greater than the
        given watermark — the fleet client's incremental push cursor."""
        return [e.to_dict() for e in self.events(since_seq=seq)]

    @property
    def last_seq(self) -> int:
        with self._lock:
            return self._seq

    @property
    def total_emitted(self) -> int:
        with self._lock:
            return self._total

    @property
    def dropped(self) -> int:
        """Events pushed out of the bounded ring (emitted minus retained)."""
        with self._lock:
            return self._total - len(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._dedup.clear()

    # ------------------------------------------------------------ exporting
    def export_jsonl(self, path: Optional[str] = None) -> str:
        """Write the buffered events as JSONL next to the trace stream: one
        ``process_meta`` header line (identity + schema marker), then one
        event per line. Returns the path written."""
        from deepspeed_tpu.telemetry.exporters import default_output_dir
        from deepspeed_tpu.telemetry.fleet import get_identity

        path = path or self.jsonl_path or os.path.join(
            default_output_dir(), "event_log.jsonl")
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            f.write(json.dumps({
                "kind": "process_meta", "schema": "dstpu_events_v1",
                "identity": get_identity().to_dict(), "pid": os.getpid(),
            }) + "\n")
            for ev in self.events():
                f.write(json.dumps(ev.to_dict()) + "\n")
        return path

    def maybe_export(self) -> Optional[str]:
        """Export iff a path is configured (the tracer's flush hook)."""
        if self.jsonl_path:
            return self.export_jsonl(self.jsonl_path)
        return None


def load_events_jsonl(path: str) -> List[Event]:
    """Parse an ``export_jsonl`` file back into events (header skipped) —
    the incident-report side of the round trip."""
    out: List[Event] = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            d = json.loads(line)
            if d.get("kind") == "process_meta" or "severity" not in d:
                continue
            out.append(Event.from_dict(d))
    return out


# ----------------------------------------------------------- process-global
_stream: Optional[EventStream] = None
_stream_lock = threading.Lock()


def get_event_stream() -> EventStream:
    global _stream
    if _stream is None:
        with _stream_lock:
            if _stream is None:
                _stream = EventStream()
    return _stream


def configure_events(capacity: Optional[int] = None,
                     dedup_window_s: Optional[float] = None,
                     jsonl_path: Optional[str] = None,
                     enabled: Optional[bool] = None) -> EventStream:
    """(Re)configure the process-global stream in place — handles held by
    detectors and the fleet client stay valid (the tracer convention)."""
    s = get_event_stream()
    if capacity is not None and int(capacity) != s.capacity:
        with s._lock:
            s.capacity = int(capacity)
            s._ring = deque(s._ring, maxlen=s.capacity)
    if dedup_window_s is not None:
        s.dedup_window_s = float(dedup_window_s)
    if jsonl_path is not None:
        s.jsonl_path = jsonl_path or None
    if enabled is not None:
        s.enabled = bool(enabled)
    return s


def emit_event(subsystem: str, kind: str, message: str, *,
               severity: str = "warn",
               labels: Optional[Dict[str, Any]] = None,
               dedup_key: Optional[str] = None, ctx=None,
               request_id: Optional[int] = None,
               step: Optional[int] = None,
               ts: Optional[float] = None) -> Optional[Event]:
    """Emit onto the process-global stream (see :meth:`EventStream.emit`).

    This is THE detector-side API: call it right next to the existing
    ``logger.warning`` — never instead of it."""
    return get_event_stream().emit(
        subsystem, kind, message, severity=severity, labels=labels,
        dedup_key=dedup_key, ctx=ctx, request_id=request_id, step=step,
        ts=ts)


# ------------------------------------------------------- shared warn-once
class WarnOnceSet:
    """THE warn-once implementation (satellite of ISSUE 20): one keyed set
    behind its own lock (callers may hold other non-reentrant locks),
    logging once per key AND emitting a typed event on that first
    occurrence.

    Returns True when this call was the first for ``key`` (and therefore
    logged + emitted), False on every repeat — the numerics call sites
    branch on that.
    """

    def __init__(self, subsystem: str = "telemetry",
                 default_kind: str = "warn_once"):
        self.subsystem = subsystem
        self.default_kind = default_kind
        self._lock = threading.Lock()
        self._seen: set = set()

    def __call__(self, key: str, message: str, *, kind: Optional[str] = None,
                 severity: str = "warn",
                 labels: Optional[Dict[str, Any]] = None,
                 subsystem: Optional[str] = None, log=None) -> bool:
        with self._lock:
            if key in self._seen:
                return False
            self._seen.add(key)
        if log is None:
            from deepspeed_tpu.utils.logging import logger as log
        log.warning(message)
        try:
            emit_event(subsystem or self.subsystem,
                       kind or self.default_kind, message,
                       severity=severity, labels=labels, dedup_key=key)
        except Exception:  # noqa: BLE001 - a warn must never raise
            pass
        return True

    def seen(self, key: str) -> bool:
        with self._lock:
            return key in self._seen

    def reset(self) -> None:
        with self._lock:
            self._seen.clear()


_global_warn_once = WarnOnceSet(subsystem="logging", default_kind="warning_once")


def warn_once(message: str, *, key: Optional[str] = None,
              subsystem: str = "logging", kind: str = "warning_once",
              severity: str = "warn") -> bool:
    """Process-global warn-once keyed by ``key`` (default: the message
    itself — the historic ``utils/logging.warning_once`` contract)."""
    k = message if key is None else key
    return _global_warn_once(k, message, kind=kind, severity=severity,
                             subsystem=subsystem)


def reset_warn_once() -> None:
    """Test hook: forget every process-global warn-once key."""
    _global_warn_once.reset()
