"""deepspeed_tpu.telemetry: unified observability substrate.

One process-global ``Tracer`` (nestable wall-clock spans, bounded buffer)
plus a shared ``MetricsRegistry`` (labelled counters/gauges + log-bucketed
quantile histograms), two trace exporters (Chrome trace-event JSON for
Perfetto, JSONL for tooling), and a metrics exposition layer
(``exposition.py``: Prometheus text format, JSON snapshot, opt-in stdlib
``/metrics`` HTTP endpoint).

Wired into:
  - ``runtime/engine.py``   — train_batch/data/step + fwd/bwd/step parity
    phases, per-step monitor scalars, device-memory watermarks
  - ``comm/comm.py``        — every facade collective as a trace-time span
    tagged with op/axis/dtype/payload bytes/participant count, plus
    ``comm/bytes`` + ``comm/count`` counters
  - ``checkpoint/``         — save/load spans
  - ``runtime/dataloader.py`` — batch materialization spans

Enable via the ``telemetry`` config block (see ``config/config.py``) or the
``DSTPU_TELEMETRY=1`` env var; export dir defaults to ``DSTPU_TELEMETRY_DIR``
(else ``./telemetry_out``). Disabled (the default) every hook is a single
attribute check — zero measurable overhead. See ``docs/telemetry.md``.

What WATCHES these streams lives in ``deepspeed_tpu/diagnostics`` (health
probes, recompile detection, step-time anomaly flags, crash flight recorder)
— it shares this registry, so its ``health/``, ``recompile/``, ``anomaly/``,
and ``flops/`` metrics ride the same monitor/export paths. See
``docs/diagnostics.md``.

The FLEET plane (``fleet.py`` + ``collector.py``) lifts all of this across
process boundaries: a ``ProcessIdentity`` stamped on every artifact,
bit-exact metric federation into a ``FleetCollector`` (counters sum,
log-bucket histograms merge bucket-wise, gauges keep last-per-process
under ``{proc=}``), cross-process trace contexts whose flow arrows join
in ``tools/trace_merge.py``, and a cluster health ledger of per-process
heartbeats. See docs/telemetry.md "Fleet telemetry".

The INCIDENT plane (``events.py`` + ``alerts.py`` + the collector's
``/events``, ``/incidents``, ``/console`` routes) types the warnings:
every detector also emits a structured :class:`Event` onto a bounded
process-local stream, a declarative :class:`AlertEngine` evaluates
threshold/absence/event-rate rules over the registry + stream with a
pending→firing→resolved state machine, and the collector correlates
shipped events into cross-process incidents. See docs/telemetry.md
"Events, alerts, incidents".
"""

from deepspeed_tpu.telemetry.alerts import (
    AlertEngine,
    AlertRule,
    configure_alerts,
    default_rules,
    get_alert_engine,
)
from deepspeed_tpu.telemetry.events import (
    Event,
    EventStream,
    WarnOnceSet,
    configure_events,
    emit_event,
    get_event_stream,
    warn_once,
)
from deepspeed_tpu.telemetry.exporters import (
    chrome_trace_events,
    default_output_dir,
    export_chrome_trace,
    export_jsonl,
)
from deepspeed_tpu.telemetry.exposition import (
    MetricsServer,
    export_json_snapshot,
    export_prometheus,
    render_json_snapshot,
    render_prometheus,
    serve_metrics,
)
from deepspeed_tpu.telemetry.fleet import (
    ProcessIdentity,
    TraceContext,
    configure_identity,
    get_identity,
)
from deepspeed_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from deepspeed_tpu.telemetry.tracer import (
    NOOP_SPAN,
    Tracer,
    configure,
    enabled,
    env_enabled,
    get_tracer,
    span,
)

__all__ = [
    "AlertEngine",
    "AlertRule",
    "Counter",
    "Event",
    "EventStream",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "MetricsServer",
    "NOOP_SPAN",
    "ProcessIdentity",
    "TraceContext",
    "Tracer",
    "WarnOnceSet",
    "chrome_trace_events",
    "configure",
    "configure_alerts",
    "configure_events",
    "configure_identity",
    "default_output_dir",
    "default_rules",
    "emit_event",
    "enabled",
    "env_enabled",
    "export_chrome_trace",
    "export_json_snapshot",
    "export_jsonl",
    "export_prometheus",
    "get_alert_engine",
    "get_event_stream",
    "get_identity",
    "get_tracer",
    "render_json_snapshot",
    "render_prometheus",
    "serve_metrics",
    "span",
    "warn_once",
]
