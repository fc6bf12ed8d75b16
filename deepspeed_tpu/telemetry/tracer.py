"""Span tracer: nestable named wall-clock spans with bounded buffering.

The temporal half of the telemetry subsystem. Design constraints, in order:

1. **The device trace is the clock.** Every ``span()`` opens a
   ``jax.profiler.TraceAnnotation("dstpu:<name>", **args)``. Whenever a
   ``jax.profiler`` session is on (the benchmark's ``--trace 1``, or
   ``profiling/capture.py`` armed by SIGUSR2 or an anomaly) the span is an
   event of the trace's ``/host:CPU`` plane, its args the event's stats, on
   the same clock as the device operations — no config switch. With no
   session on, the annotation is inactive (under a microsecond).
2. **Nothing else when disabled.** ``span()`` on a disabled tracer returns
   the bare annotation: no event buffered, no histogram, no lock. Engine hot
   paths call it unconditionally.
3. **Honest on an async-dispatch runtime.** JAX dispatch is asynchronous, so a
   host-side span around a compiled-step call measures *dispatch*, not device
   time. Device time is read from the device rows of the same trace, beside
   the span; nothing here drains the device queue.
4. **Bounded memory.** At most ``max_events`` events are buffered; overflow
   increments ``dropped_events`` instead of growing without bound.

Spans on the same thread nest by timestamp containment, which is exactly how
the Chrome trace-event viewer (Perfetto) reconstructs flame graphs — no
explicit parent pointers needed. Every completed span also feeds the
``span/<name>`` histogram in the shared ``MetricsRegistry`` so phase
breakdowns come from the same source of truth as the trace.
"""

from __future__ import annotations

import gc
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from deepspeed_tpu.telemetry.registry import MetricsRegistry

SPAN_PREFIX = "dstpu:"  # every span's name in a jax.profiler trace


class _NoopSpan:
    """Shared do-nothing context manager for callers that gate their own
    spans on ``tracer.enabled`` (the trace-time comm/collective spans)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


class _Span:
    """A live span of an enabled tracer: the trace annotation, and the
    tracer's own record of it on ``__exit__``."""

    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._annotation = TraceAnnotation(SPAN_PREFIX + name, **args)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set_metadata(self, **args: Any) -> None:
        """Args known only once the span's work is done (counts measured
        where the work happens); same call as the bare annotation's."""
        self.args.update(args)
        self._annotation.set_metadata(**args)

    def __exit__(self, *exc):
        # close is inlined (no helper-call indirection): the serving loop
        # closes six spans per decode chain, so every fixed cost here is
        # paid on the hot path
        tracer = self._tracer
        t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        dur_s = t1 - self._t0
        ev = {
            "kind": "span",
            "name": self.name,
            "cat": self.cat,
            "ts": self._t0 - tracer._origin,
            "dur": dur_s,
            "tid": threading.get_ident(),
        }
        if self.args:
            ev["args"] = self.args
        with tracer._lock:
            if len(tracer._events) >= tracer.max_events:
                tracer.dropped_events += 1
            else:
                tracer._events.append(ev)
        h = tracer._span_hists.get(self.name)
        if h is None:  # get-or-create once, then plain dict hits
            h = tracer._span_hists[self.name] = tracer.registry.histogram(
                "span/" + self.name)
        h.observe(dur_s)
        return False


class Tracer:
    """Nestable span recorder + shared metrics registry.

    One global instance (``get_tracer()``) serves the whole process so the
    engine, comm facade, dataloader, and checkpoint paths need no plumbing —
    the same pattern as ``comm.comms_logger``.
    """

    def __init__(self, enabled: bool = False,
                 max_events: int = 100_000, memory_watermarks: bool = True):
        self.enabled = enabled
        self.max_events = max_events
        self.memory_watermarks = memory_watermarks
        self.trace_path: Optional[str] = None
        self.jsonl_path: Optional[str] = None
        self.prometheus_path: Optional[str] = None
        self.dropped_events = 0
        self.registry = MetricsRegistry()
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._origin = time.perf_counter()
        # wall-clock stamp taken at the same instant as _origin: the anchor
        # that lets tools/trace_merge.py place this process's origin-relative
        # event stream on a fleet-wide timeline (telemetry/fleet.py)
        self._origin_unix = time.time()
        self._last_counts: Dict[str, float] = {}
        # virtual-track names (e.g. per-request serving tracks): tid -> label,
        # exported as Chrome thread_name metadata so Perfetto shows the label
        self._track_names: Dict[int, str] = {}
        # span-name -> Histogram handle cache: skips the f-string + registry
        # RLock on every span close (the serving hot path closes 3 per chain)
        self._span_hists: Dict[str, Any] = {}

    # ------------------------------------------------------------ config
    def configure(self, enabled: bool = True,
                  max_events: Optional[int] = None,
                  memory_watermarks: Optional[bool] = None,
                  trace_path: Optional[str] = None,
                  jsonl_path: Optional[str] = None,
                  prometheus_path: Optional[str] = None) -> "Tracer":
        self.enabled = enabled
        if max_events is not None:
            self.max_events = max_events
        if memory_watermarks is not None:
            self.memory_watermarks = memory_watermarks
        if trace_path is not None:
            self.trace_path = trace_path
        if jsonl_path is not None:
            self.jsonl_path = jsonl_path
        if prometheus_path is not None:
            self.prometheus_path = prometheus_path
        return self

    def reset(self) -> None:
        """Drop buffered events and registry contents (config is kept)."""
        with self._lock:
            self._events = []
            self.dropped_events = 0
            self._origin = time.perf_counter()
            self._origin_unix = time.time()
            self._last_counts = {}
            self._track_names = {}
            self._span_hists = {}
        self.registry.reset()

    # ------------------------------------------------------------- spans
    def span(self, name: str, cat: str = "span", **args: Any):
        """Context manager for one named span: always a ``dstpu:<name>``
        annotation of the ``jax.profiler`` trace (inactive when no session is
        on), and with the tracer enabled also an event of its own buffer."""
        if not self.enabled:
            return TraceAnnotation(SPAN_PREFIX + name, **args)
        return _Span(self, name, cat, args)

    def recording(self) -> bool:
        """True when a span's args are read by anyone: the tracer is enabled
        or a ``jax.profiler`` session is on. Callers check it before
        formatting an arg that costs more than a count."""
        return self.enabled or TraceAnnotation.is_enabled()

    def instant(self, name: str, cat: str = "event", **args: Any) -> None:
        """Record a zero-duration marker event."""
        if not self.enabled:
            return
        ev = {
            "kind": "instant",
            "name": name,
            "cat": cat,
            "ts": time.perf_counter() - self._origin,
            "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def count(self, name: str, value: float = 1.0) -> None:
        """Increment a registry counter (no trace event; cheap)."""
        if not self.enabled:
            return
        self.registry.counter(name).add(value)

    def sample_counter(self, name: str, value: float) -> None:
        """Set a gauge AND emit a Chrome 'C' counter event (a plotted track
        in Perfetto) — used for memory watermarks."""
        if not self.enabled:
            return
        self.registry.gauge(name).set(value)
        self._append({
            "kind": "counter",
            "name": name,
            "ts": time.perf_counter() - self._origin,
            "value": value,
        })

    # ------------------------------------------ virtual tracks + flow events
    # (serving per-request observability: each request gets its own Perfetto
    # track, and flow arrows link its admission to the prefill/chain dispatch
    # spans on the engine thread — see inference/lifecycle.py)
    def name_track(self, tid: int, name: str) -> None:
        """Label a virtual track (exported as Chrome thread_name metadata)."""
        if not self.enabled:
            return
        with self._lock:
            self._track_names[tid] = name

    def track_names(self) -> Dict[int, str]:
        with self._lock:
            return dict(self._track_names)

    def emit_span(self, name: str, t0: float, t1: float, tid: Optional[int] = None,
                  cat: str = "span", **args: Any) -> None:
        """Record a span from explicit ``time.perf_counter()`` stamps —
        deferred emission for lifecycles whose phases are stamped on the hot
        path but materialized (one cheap append per phase) only at request
        finish. ``tid`` selects a virtual track; default: calling thread."""
        if not self.enabled:
            return
        ev: Dict[str, Any] = {
            "kind": "span",
            "name": name,
            "cat": cat,
            "ts": t0 - self._origin,
            "dur": max(t1 - t0, 0.0),
            "tid": threading.get_ident() if tid is None else tid,
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def flow(self, name: str, flow_id: int, phase: str,
             ts: Optional[float] = None, tid: Optional[int] = None,
             cat: str = "flow") -> None:
        """Record one flow event (``phase``: 'start' | 'step' | 'end').

        Chrome flow events with a shared (cat, name, id) draw arrows between
        the slices enclosing them — this is what links a request's admission
        on its own track to every dispatch span that served it."""
        if not self.enabled:
            return
        ev: Dict[str, Any] = {
            "kind": "flow",
            "name": name,
            "cat": cat,
            "ph": {"start": "s", "step": "t", "end": "f"}[phase],
            "id": flow_id,
            "ts": (time.perf_counter() if ts is None else ts) - self._origin,
            "tid": threading.get_ident() if tid is None else tid,
        }
        self._append(ev)

    def origin(self) -> float:
        """The ``perf_counter`` stamp event ``ts`` values are relative to —
        for callers building deferred event batches (``append_events``)."""
        return self._origin

    def origin_unix(self) -> float:
        """Wall-clock time of the origin — the per-process anchor the trace
        merger and the fleet collector's clock handshake align on. Every
        event's absolute wall time is ``origin_unix() + ev["ts"]``."""
        return self._origin_unix

    def append_events(self, evs: List[Dict[str, Any]]) -> None:
        """Append a pre-built event batch under ONE lock acquisition.

        Events must already carry origin-relative ``ts`` (see ``origin()``)
        and the raw tracer schema (``kind`` span/instant/flow/counter). This
        is the deferred-emission path: a request lifecycle materializes its
        whole track (spans + flow arrows) in one call at finish instead of
        paying a lock per event on the serving hot path."""
        if not self.enabled or not evs:
            return
        with self._lock:
            space = self.max_events - len(self._events)
            if space <= 0:
                self.dropped_events += len(evs)
                return
            if len(evs) > space:
                self.dropped_events += len(evs) - space
                evs = evs[:space]
            self._events.extend(evs)

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped_events += 1
                return
            self._events.append(ev)

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    # --------------------------------------------------------- summaries
    def phase_summary(self) -> Dict[str, Dict[str, float]]:
        """``{span_name: {count, total_ms, mean_ms, min_ms, max_ms}}`` from
        the registry."""
        out: Dict[str, Dict[str, float]] = {}
        for name, val in self.registry.snapshot().items():
            if not name.startswith("span/") or not isinstance(val, dict):
                continue
            out[name[len("span/"):]] = {
                "count": val["count"],
                "total_ms": round(val["total"] * 1e3, 3),
                "mean_ms": round(val["mean"] * 1e3, 3),
                "min_ms": round(val["min"] * 1e3, 3),
                "max_ms": round(val["max"] * 1e3, 3),
            }
        return out

    def sample_memory(self) -> Dict[str, float]:
        """Device-memory watermark sample: PJRT ``memory_stats()`` where the
        backend reports it (TPU HBM), else the ``jax.live_arrays`` census
        (CPU test meshes). Feeds gauges + Perfetto counter tracks."""
        if not (self.enabled and self.memory_watermarks):
            return {}
        out: Dict[str, float] = {}
        try:
            import jax

            stats = {}
            try:
                stats = jax.local_devices()[0].memory_stats() or {}
            except Exception:
                stats = {}
            if "bytes_in_use" in stats:
                out["device_bytes_in_use"] = float(stats["bytes_in_use"])
                if "peak_bytes_in_use" in stats:
                    out["device_peak_bytes_in_use"] = float(stats["peak_bytes_in_use"])
            else:
                out["live_array_bytes"] = float(
                    sum(getattr(a, "nbytes", 0) for a in jax.live_arrays()))
        except Exception:  # pragma: no cover - backendless environments
            return {}
        for k, v in out.items():
            self.sample_counter(f"mem/{k}", v)
        return out

    def step_scalars(self, prefix: str = "Telemetry/") -> Dict[str, float]:
        """Per-step scalars for the ``MonitorMaster``: counter deltas since
        the previous call, gauge samples (flops/MFU, anomaly flags...),
        memory watermarks, and the last completed step-phase wall times. All
        host-side floats — never blocks the dispatch pipeline.

        Caveat on ``comm/*`` counters: the facade records collectives at
        TRACE time (one bump per compiled program, not per execution), so
        their deltas spike on compile steps and read 0 in steady state —
        they chart recompile/compile activity, not per-step wire volume."""
        if not self.enabled:
            return {}
        out: Dict[str, float] = {}
        for name, value in self.registry.counters().items():
            delta = value - self._last_counts.get(name, 0.0)
            self._last_counts[name] = value
            out[prefix + name] = float(delta)
        for name, value in self.registry.gauges().items():
            # gauges are last-write samples (flops/MFU, anomaly/ flags...);
            # mem/ gauges are refreshed + emitted by sample_memory below
            if not name.startswith("mem/"):
                out[prefix + name] = float(value)
        for k, v in self.sample_memory().items():
            out[f"{prefix}mem/{k}"] = v
        for phase in ("train_batch", "data", "step", "fwd_bwd", "fwd", "bwd"):
            h = self.registry.peek_histogram(f"span/{phase}")
            if h is not None and h.count:
                out[f"{prefix}span/{phase}_ms"] = round(h.last * 1e3, 3)
        return out

    # ----------------------------------------------------------- export
    def maybe_export(self) -> None:
        """Write configured exports (no-op when no path is configured)."""
        from deepspeed_tpu.telemetry import exporters

        if self.trace_path:
            exporters.export_chrome_trace(self.trace_path, tracer=self)
        if self.jsonl_path:
            exporters.export_jsonl(self.jsonl_path, tracer=self)
        if self.prometheus_path:
            from deepspeed_tpu.telemetry import exposition

            exposition.export_prometheus(self.prometheus_path, registry=self.registry)
        # the structured event stream (ISSUE 20) flushes next to the trace
        # stream when IT has a path configured — same flush cadence, one
        # artifact directory for the incident-report join
        from deepspeed_tpu.telemetry import events as events_mod

        events_mod.get_event_stream().maybe_export()


def env_enabled() -> bool:
    """True when DSTPU_TELEMETRY opts telemetry in from the environment —
    the ONE place the accepted truthy spellings live (don't re-implement
    the parse)."""
    return os.environ.get("DSTPU_TELEMETRY", "").lower() in ("1", "true", "yes")


_tracer = Tracer(enabled=env_enabled())


class _Collector:
    """The garbage collector as a span: one ``gc.callbacks`` entry for the
    process. A collection is a ``dstpu:gc`` annotation of the ``jax.profiler``
    trace (``generation``; ``collected`` once it is over), so a traced run's
    idle table puts a gap it caused down to ``gc``, and its seconds add up
    process-wide whether anybody traces or not: the serving loop's call log
    reads them at every edge of a call (``diagnostics/anomaly.py``). It is the
    BARE annotation, never ``Tracer.span``: a collection runs on whichever
    thread trips it, at any allocation, so also inside a section that holds
    ``Tracer._lock``, which is not reentrant. The collector runs one
    collection at a time, so one open span is all there is to keep."""

    __slots__ = ("seconds", "_span", "_t0")

    def __init__(self):
        self.seconds, self._span = 0.0, None

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._span = TraceAnnotation(SPAN_PREFIX + "gc", generation=info["generation"])
            self._span.__enter__()
            self._t0 = time.perf_counter()
        elif self._span is not None:
            self.seconds += time.perf_counter() - self._t0
            span, self._span = self._span, None
            span.set_metadata(collected=info["collected"])
            span.__exit__(None, None, None)


_collector = _Collector()
gc.callbacks.append(_collector)


def gc_seconds() -> float:
    """Seconds this process has spent in garbage collections so far."""
    return _collector.seconds


def get_tracer() -> Tracer:
    return _tracer


def configure(**kwargs) -> Tracer:
    """Configure the process-global tracer (see ``Tracer.configure``)."""
    return _tracer.configure(**kwargs)


def span(name: str, cat: str = "span", **args: Any):
    return _tracer.span(name, cat=cat, **args)


def enabled() -> bool:
    return _tracer.enabled
