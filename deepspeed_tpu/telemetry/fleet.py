"""Fleet telemetry primitives: process identity, mergeable registry dumps,
and cross-process trace context.

Everything the telemetry stack built so far is process-local — one registry,
one tracer with a private ``perf_counter`` origin, one ``/metrics`` port.
This module is the layer that lets N such processes read as ONE system:

  - :class:`ProcessIdentity` — (run_id, process_index, host, role) stamped
    onto every registry exposition, tracer stream and flight-recorder dump,
    so artifacts from different processes can be joined after the fact.
    Process-global like the tracer (:func:`get_identity` /
    :func:`configure_identity`); defaults come from ``DSTPU_RUN_ID`` /
    ``DSTPU_PROCESS_INDEX`` / ``DSTPU_ROLE`` (the launcher's contract),
    then ``jax.process_index()``, then 0.
  - :func:`registry_dump` / :func:`merge_dump_into` — the wire format and
    merge rules for metric federation (``telemetry/collector.py``). The
    merge is exact by construction: counters SUM, the log-bucket histograms
    merge bucket-wise (``Histogram.merge_state`` — a sample lands in the
    same bucket no matter which process observed it, so merging K sharded
    registries equals observing the concatenated stream), and gauges —
    which have no meaningful cross-process fold — keep last-per-process
    under a ``{proc=}`` label.
  - :class:`TraceContext` — the request-scoped context a router propagates
    to a replica across a process boundary. Both sides derive the SAME
    Chrome flow id from (run_id, request_id), so the admission flow arrow
    emitted in the router process and the ``serve:dispatch`` flow step
    emitted in the replica process bind into one arrow once
    ``tools/trace_merge.py`` joins the per-process streams.
  - :func:`note_step` / :func:`last_step_info` — the per-process liveness
    breadcrumb ``/healthz`` and fleet heartbeats report (last step + age)
    without parsing the full exposition.

See docs/telemetry.md "Fleet telemetry".
"""

from __future__ import annotations

import dataclasses
import os
import socket
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.telemetry.registry import (
    MetricsRegistry,
    decode_key,
    encode_labels,
)

# roles a process can declare; free-form strings are accepted (the ledger
# just displays them) but these are the ones the runtime stamps itself.
# "prefill"/"decode" are the disaggregated serving pools (ISSUE 14): a
# phase-specialized replica process exports its role so the collector's
# per-role rollups and the merged traces read the topology directly.
ROLES = ("train", "router", "replica", "prefill", "decode", "collector",
         "worker")


@dataclasses.dataclass
class ProcessIdentity:
    """Who a telemetry stream came from — the join key for every
    cross-process artifact (dumps, traces, ledger rows)."""

    run_id: str
    process_index: int = 0
    host: str = ""
    role: str = "train"
    pid: int = 0

    @property
    def proc(self) -> str:
        """The short ``{proc=}`` label value: ``p<index>``."""
        return f"p{self.process_index}"

    def key(self) -> str:
        """Ledger/collector identity key — unique per fleet member."""
        return f"{self.run_id}/{self.proc}"

    def labels(self) -> Dict[str, str]:
        return {"run_id": self.run_id, "proc": self.proc,
                "host": self.host, "role": self.role}

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ProcessIdentity":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


_lock = threading.Lock()
_identity: Optional[ProcessIdentity] = None
# (step, wall-clock stamp) of the most recent note_step — /healthz liveness
_last_step: Optional[Tuple[int, float]] = None


def _default_run_id() -> str:
    """A run id every process of one launch shares: the launcher exports
    ``DSTPU_RUN_ID``; a standalone process mints one from its start time +
    pid (unique enough to join its own artifacts, and visibly NOT shared
    with anything else)."""
    env = os.environ.get("DSTPU_RUN_ID")
    if env:
        return env
    return f"r{int(time.time()):x}-{os.getpid():x}"


def _default_process_index() -> int:
    env = os.environ.get("DSTPU_PROCESS_INDEX")
    if env is not None:
        try:
            return int(env)
        except ValueError:
            pass
    try:  # multi-host jax runtimes know their index; CPU tests get 0
        import jax

        return int(jax.process_index())
    except Exception:  # noqa: BLE001 - backendless/early import
        return 0


def get_identity() -> ProcessIdentity:
    """The process-global identity (lazily built from the environment)."""
    global _identity
    with _lock:
        if _identity is None:
            _identity = ProcessIdentity(
                run_id=_default_run_id(),
                process_index=_default_process_index(),
                host=socket.gethostname(),
                role=os.environ.get("DSTPU_ROLE", "train"),
                pid=os.getpid(),
            )
        return _identity


def configure_identity(run_id: Optional[str] = None,
                       process_index: Optional[int] = None,
                       host: Optional[str] = None,
                       role: Optional[str] = None) -> ProcessIdentity:
    """Override identity fields (process-global, like ``telemetry.configure``).
    Unset fields keep their current/default resolution."""
    global _identity
    ident = get_identity()
    with _lock:
        if run_id is not None:
            ident.run_id = str(run_id)
        if process_index is not None:
            ident.process_index = int(process_index)
        if host is not None:
            ident.host = str(host)
        if role is not None:
            ident.role = str(role)
        return ident


def reset_identity() -> None:
    """Drop the cached identity (tests; env changes re-resolve lazily)."""
    global _identity, _last_step
    with _lock:
        _identity = None
        _last_step = None


def note_step(step: int) -> None:
    """Record that optimizer/serving step ``step`` just completed — two
    writes, no lock (a torn read across the tuple swap is harmless), cheap
    enough for the unconditional per-step call in the engines."""
    global _last_step
    _last_step = (int(step), time.time())


def last_step_info(now: Optional[float] = None) -> Dict[str, Any]:
    """``{"step", "age_s"}`` of the most recent :func:`note_step`, or
    ``{"step": None, "age_s": None}`` before any step ran — what /healthz
    and fleet heartbeats report as the liveness signal."""
    snap = _last_step
    if snap is None:
        return {"step": None, "age_s": None}
    step, t = snap
    return {"step": step, "age_s": round((now or time.time()) - t, 3)}


# --------------------------------------------------------------- federation
def registry_dump(registry=None, identity: Optional[ProcessIdentity] = None
                  ) -> Dict[str, Any]:
    """The mergeable wire snapshot of one process's registry: counters and
    gauges by flat key, histograms with their RAW sparse buckets
    (``Histogram.state`` — ``summary()`` drops exactly the piece a
    bit-exact merge needs). Served at ``GET /metrics.fleet`` and pushed to
    the collector; :func:`merge_dump_into` is the consuming half."""
    if registry is None:
        from deepspeed_tpu.telemetry.tracer import get_tracer

        registry = get_tracer().registry
    ident = identity or get_identity()
    counters: Dict[str, float] = {}
    gauges: Dict[str, float] = {}
    hists: Dict[str, Dict[str, Any]] = {}
    for kind, _base, metric in registry.iter_metrics():
        key = metric.name + encode_labels(metric.labels)
        if kind == "counter":
            counters[key] = metric.value
        elif kind == "gauge":
            gauges[key] = metric.value
        else:
            hists[key] = metric.state()
    return {
        "identity": ident.to_dict(),
        "time_unix": time.time(),
        "counters": counters,
        "gauges": gauges,
        "histograms": hists,
    }


def merge_dump_into(registry: MetricsRegistry, dump: Dict[str, Any],
                    proc_label: Optional[str] = None) -> None:
    """Fold one process's :func:`registry_dump` into a federated registry.

    Merge rules (pinned by the property test in tests/unit/test_fleet.py):
      - counters SUM: ``c.add(value)`` per dump, so folding the per-process
        cumulative values yields exactly their arithmetic sum;
      - histograms merge BUCKET-WISE (``merge_state``) — bit-identical to
        observing the concatenated sample stream;
      - gauges have no cross-process fold: each lands under its own
        ``{proc=}`` label (last-write-wins per process), so the federated
        view keeps every process's latest sample side by side.

    ``proc_label`` overrides the gauge label (default: the identity's
    short ``p<index>``) — the collector passes the run_id-qualified key
    when two fleet members share a process index, so their gauges never
    clobber each other."""
    ident = ProcessIdentity.from_dict(dump.get("identity") or {"run_id": "?"})
    proc = proc_label if proc_label is not None else ident.proc
    for key, value in (dump.get("counters") or {}).items():
        name, labels = decode_key(key)
        registry.counter(name, **labels).add(float(value))
    for key, value in (dump.get("gauges") or {}).items():
        name, labels = decode_key(key)
        labels["proc"] = proc
        registry.gauge(name, **labels).set(float(value))
    for key, state in (dump.get("histograms") or {}).items():
        name, labels = decode_key(key)
        registry.histogram(name, **labels).merge_state(state)


# ------------------------------------------------------------ trace context
def flow_id_for(run_id: str, request_id: int) -> int:
    """Stable 63-bit Chrome flow id both sides of a process boundary can
    derive independently from (run_id, request_id) — crc32 over each half,
    concatenated. Collision across requests of one trace is what matters;
    2^63 over a few thousand in-flight requests is comfortably unique."""
    hi = zlib.crc32(run_id.encode()) & 0x7FFF_FFFF
    lo = zlib.crc32(str(int(request_id)).encode()) & 0xFFFF_FFFF
    return (hi << 32) | lo


@dataclasses.dataclass
class TraceContext:
    """What a dispatch carries across a process boundary: enough for the
    receiver to emit spans/flow steps that join the sender's trace. The
    wire form is a plain dict (header-shaped — an HTTP/RPC transport can
    carry it verbatim)."""

    run_id: str
    request_id: int
    flow_id: int

    @property
    def flow_name(self) -> str:
        """The ONE spelling of the flow-event name for this context.
        Chrome binds flow events on (cat, name, id) — both sides of the
        process boundary must emit this exact name or the merged trace
        draws no arrow."""
        return f"req-{self.request_id}"

    @classmethod
    def mint(cls, request_id: int, run_id: Optional[str] = None
             ) -> "TraceContext":
        rid = run_id if run_id is not None else get_identity().run_id
        return cls(run_id=rid, request_id=int(request_id),
                   flow_id=flow_id_for(rid, int(request_id)))

    def to_wire(self) -> Dict[str, Any]:
        return {"run_id": self.run_id, "request_id": self.request_id,
                "flow_id": self.flow_id}

    @classmethod
    def from_wire(cls, d: Dict[str, Any]) -> "TraceContext":
        rid = str(d["run_id"])
        req = int(d["request_id"])
        return cls(run_id=rid, request_id=req,
                   flow_id=int(d.get("flow_id", flow_id_for(rid, req))))


class _DispatchSpan:
    """Span + in-span flow step for a received cross-process dispatch."""

    def __init__(self, tracer, ctx: TraceContext, name: str, args: Dict):
        self._tracer = tracer
        self._ctx = ctx
        self._name = name
        self._args = args
        self._span = None

    def __enter__(self):
        self._span = self._tracer.span(self._name, cat="serve", **self._args)
        self._span.__enter__()
        # the flow STEP lands inside the open span, so the merged trace's
        # arrow terminates on this slice (Chrome binds a flow event to its
        # enclosing slice)
        self._tracer.flow(self._ctx.flow_name, self._ctx.flow_id, "step")
        return self._span

    def __exit__(self, *exc):
        return self._span.__exit__(*exc)


def dispatch_span(ctx: TraceContext, name: str = "serve:dispatch",
                  tracer=None, **args: Any):
    """Context manager a replica wraps around serving a remotely-dispatched
    request: opens a ``serve:dispatch`` span and emits a flow step with the
    context's flow id INSIDE it, so the router process's admission arrow
    lands on this process's dispatch slice in the merged trace."""
    if tracer is None:
        from deepspeed_tpu.telemetry.tracer import get_tracer

        tracer = get_tracer()
    if not tracer.enabled:
        from deepspeed_tpu.telemetry.tracer import NOOP_SPAN

        return NOOP_SPAN
    return _DispatchSpan(tracer, ctx, name,
                         dict(args, request_id=ctx.request_id))


def clock_sync_doc() -> Dict[str, float]:
    """The clock-handshake payload a process sends at collector
    registration: its wall clock now and its tracer's origin as wall time.
    The collector computes ``clock_offset_s = recv_wall - time_unix``
    (one-way, so it includes network latency — honest to within the
    localhost/LAN RTT this targets); ``origin_unix`` is what the trace
    merger uses to place this process's events on the shared timeline."""
    from deepspeed_tpu.telemetry.tracer import get_tracer

    return {"time_unix": time.time(),
            "origin_unix": get_tracer().origin_unix()}


def fleet_rollups(registry: MetricsRegistry,
                  heartbeats: Optional[Dict[str, Dict[str, Any]]] = None,
                  straggler_mads: float = 6.0,
                  roles: Optional[Dict[str, str]] = None) -> None:
    """Compute the ``fleet/*`` rollup series into a federated registry:

      fleet/goodput        summed slo_met / (slo_met + slo_missed) counters
      fleet/tokens_per_s   sum of every process's serving/tokens_per_s gauge
      fleet/step_rate_min  slowest process's heartbeat step rate
      fleet/straggler{proc=} cross-process median+MAD verdict per process
                             (the PR-2 in-process detector's math, lifted)

    ``heartbeats`` maps proc label -> latest heartbeat dict (collector
    state); step-rate rollups are skipped without it. ``roles`` maps proc
    label -> declared role: when given, the disagg topology (ISSUE 14)
    gets per-role rollups — ``fleet/tokens_per_s{role=}`` (summed over the
    role's processes) and ``fleet/step_rate_min{role=}`` — so a dashboard
    reads the prefill pool and the decode pool as two series without
    re-deriving membership. ``fleet/processes`` is NOT set here: its one
    definition (all registered members, heartbeat or not) belongs to the
    collector, which knows the membership."""
    met = missed = 0.0
    tps = 0.0
    saw_tps = False
    role_tps: Dict[str, float] = {}
    roles = roles or {}
    for kind, name, metric in registry.iter_metrics():
        if kind == "counter" and name == "serving/slo_met":
            met += metric.value
        elif kind == "counter" and name == "serving/slo_missed":
            missed += metric.value
        elif kind == "gauge" and name == "serving/tokens_per_s":
            tps += metric.value
            saw_tps = True
            role = roles.get(metric.labels.get("proc", ""))
            if role is not None:
                role_tps[role] = role_tps.get(role, 0.0) + metric.value
    if met + missed > 0:
        registry.gauge("fleet/goodput").set(met / (met + missed))
    if saw_tps:
        # a summed rate of 0 during a fleet-wide stall is exactly when the
        # series matters — report 0, never drop it (an == 0 alert must fire)
        registry.gauge("fleet/tokens_per_s").set(tps)
    for role, v in role_tps.items():
        registry.gauge("fleet/tokens_per_s", role=role).set(v)
    if not heartbeats:
        return
    rates = {p: float(hb["step_rate"]) for p, hb in heartbeats.items()
             if hb.get("step_rate") is not None}
    if rates:
        registry.gauge("fleet/step_rate_min").set(min(rates.values()))
        role_rates: Dict[str, list] = {}
        for p, v in rates.items():
            role = roles.get(p)
            if role is not None:
                role_rates.setdefault(role, []).append(v)
        for role, vals in role_rates.items():
            registry.gauge("fleet/step_rate_min", role=role).set(min(vals))
    # same threshold the caller's ledger uses — the Prometheus gauge and
    # GET /fleet must never disagree on who is straggling
    for proc, flagged in straggler_flags(rates, mads=straggler_mads).items():
        registry.gauge("fleet/straggler", proc=proc).set(float(flagged))


def straggler_flags(rates: Dict[str, float], mads: float = 6.0
                    ) -> Dict[str, bool]:
    """Cross-process straggler verdicts over per-process step RATES: the
    diagnostics median+MAD discipline (``diagnostics/anomaly.py``) applied
    across the fleet instead of across a window — a process whose rate
    falls below ``median - mads * MAD`` is flagged. Same MAD floor as the
    in-process detector so identical healthy rates never flag on jitter."""
    if len(rates) < 3:  # median+MAD needs a quorum to mean anything
        return {p: False for p in rates}
    import statistics

    vals = list(rates.values())
    med = statistics.median(vals)
    mad = statistics.median(abs(v - med) for v in vals)
    mad = max(mad, 0.01 * abs(med), 1e-6)
    return {p: v < med - mads * mad for p, v in rates.items()}
