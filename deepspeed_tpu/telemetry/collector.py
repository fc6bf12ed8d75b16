"""FleetCollector: cross-process metric federation + cluster health ledger.

One collector process (or thread) receives pushed snapshots from — or
scrapes — every process of a run and merges them into ONE federated view:

  - ``POST /register``   identity + clock handshake ({"time_unix", ...})
                         → {"ok", "clock_offset_s"} — the offset the trace
                         merger can apply to this process's stream
  - ``POST /push``       full snapshot: identity, clock, ``registry`` (the
                         :func:`fleet.registry_dump` wire form), optional
                         ``heartbeat``
  - ``POST /heartbeat``  identity + heartbeat only (cheap liveness)
  - ``GET  /metrics``    FEDERATED Prometheus exposition (counters summed,
                         histograms merged bucket-wise, gauges
                         last-per-process under ``{proc=}``, plus the
                         ``fleet/*`` rollups)
  - ``GET  /metrics.json`` federated JSON snapshot
  - ``GET  /fleet``      the health ledger: per-process identity, last-seen
                         age, heartbeat (step rate, HBM watermark, queue
                         depth), clock offset, straggler verdict
  - ``GET  /healthz``    the collector's own liveness

The incident plane (ISSUE 20) rides the same transport:

  - ``POST /events``     structured-event ingestion ({"identity",
                         "events": [...]} — the ``telemetry/events.py``
                         wire form; also accepted inline on ``/push``).
                         Events APPEND (a bounded fleet-wide ring with a
                         per-process seq guard against re-push duplicates)
                         — unlike registry dumps, which replace.
  - ``GET  /events``     the fleet event ring, filterable by
                         ``?proc=&severity=&subsystem=&since=&limit=``
  - ``GET  /incidents``  cross-process correlation: warn+ events grouped
                         into incidents by (run_id, trailing time window,
                         shared TraceContext flow/request id, or an
                         explicit ``incident_key`` label — the causal-chain
                         join a detector stamps on cause AND effect), with
                         ids stable across repeated reads
  - ``GET  /console``    one self-contained stdlib HTML ops page: health
                         ledger, firing alerts, recent incidents, SLO
                         rollups

Merging happens at READ time from the latest dump per process: pushes carry
cumulative process-local snapshots, so the collector must replace a
process's previous contribution, never add to it — re-merging from the
stored dumps on each render is what makes a restarted worker's reset
counters harmless (its new dump simply replaces the old one).

The ledger (``ledger()`` / ``GET /fleet``) is the signal the elastic
supervisor (ROADMAP item 5) and router drain/join (item 1) consume: a
process whose heartbeat age exceeds ``stale_after_s`` is marked ``stale``;
cross-process stragglers are flagged by the PR-2 median+MAD discipline over
per-process step rates.

Scrape mode: :meth:`FleetCollector.scrape` GETs a worker's
``/metrics.fleet`` endpoint (``exposition.MetricsServer``) and ingests it —
same merge path as push, for fleets where workers can't reach out.
"""

from __future__ import annotations

import hashlib
import html
import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from deepspeed_tpu.telemetry import fleet
from deepspeed_tpu.telemetry.events import severity_rank
from deepspeed_tpu.telemetry.registry import MetricsRegistry, decode_key
from deepspeed_tpu.utils.logging import logger


# ------------------------------------------------------ incident correlation
def _event_run_id(ev: Dict[str, Any]) -> str:
    return str((ev.get("identity") or {}).get("run_id", "?"))


def _event_proc(ev: Dict[str, Any]) -> str:
    ident = ev.get("identity") or {}
    return f"{ident.get('run_id', '?')}/p{ident.get('process_index', '?')}"


def _incident_id(run_id: str, first_ev: Dict[str, Any]) -> str:
    """Stable across repeated correlations of the same state: derived from
    the FIRST event's immutable coordinates, never from list position."""
    basis = (f"{run_id}:{_event_proc(first_ev)}:{first_ev.get('seq', 0)}"
             f":{first_ev.get('subsystem')}:{first_ev.get('kind')}"
             f":{first_ev.get('ts')}")
    return "inc-" + hashlib.sha1(basis.encode()).hexdigest()[:10]


def correlate_events(events: List[Dict[str, Any]], window_s: float = 30.0,
                     min_severity: str = "warn") -> List[Dict[str, Any]]:
    """Group events (wire dicts) into incidents.

    Join rules, applied over the time-sorted warn+ stream:
      - same ``run_id`` AND within ``window_s`` of the incident's newest
        event (the drift -> profiler-capture -> regression causal chain is
        a cascade inside one window), OR
      - a shared ``flow_id`` / ``request_id`` (the TraceContext join — a
        request's failure on the router and its death on the replica are
        one incident however far apart), OR
      - a shared ``incident_key`` label (the explicit causal stamp a
        detector puts on cause and effect).
    An event bridging several open incidents MERGES them (the id of the
    earliest survives). Shared by the collector's ``/incidents`` and
    ``tools/incident_report.py`` — one correlation, two readers.
    """
    floor = severity_rank(min_severity)
    sev = [e for e in events if severity_rank(str(e.get("severity", "info")))
           >= floor]
    sev.sort(key=lambda e: (float(e.get("ts", 0.0)), _event_proc(e),
                            int(e.get("seq", 0))))
    incidents: List[Dict[str, Any]] = []

    def join_keys(ev: Dict[str, Any]) -> set:
        keys = set()
        if ev.get("flow_id") is not None:
            keys.add(("flow", ev["flow_id"]))
        if ev.get("request_id") is not None:
            keys.add(("req", _event_run_id(ev), ev["request_id"]))
        ik = (ev.get("labels") or {}).get("incident_key")
        if ik:
            keys.add(("key", ik))
        return keys

    for ev in sev:
        run_id = _event_run_id(ev)
        ts = float(ev.get("ts", 0.0))
        keys = join_keys(ev)
        matched = [
            inc for inc in incidents
            if (inc["run_id"] == run_id
                and ts - inc["end_ts"] <= window_s)
            or (keys & inc["_keys"])]
        if not matched:
            incidents.append({
                "id": _incident_id(run_id, ev), "run_id": run_id,
                "start_ts": ts, "end_ts": ts, "events": [ev],
                "_keys": keys})
            continue
        primary = matched[0]
        for other in matched[1:]:  # bridge: fold later incidents in
            primary["events"].extend(other["events"])
            primary["_keys"] |= other["_keys"]
            primary["start_ts"] = min(primary["start_ts"], other["start_ts"])
            primary["end_ts"] = max(primary["end_ts"], other["end_ts"])
            incidents.remove(other)
        primary["events"].append(ev)
        primary["_keys"] |= keys
        primary["start_ts"] = min(primary["start_ts"], ts)
        primary["end_ts"] = max(primary["end_ts"], ts)
    out = []
    for inc in incidents:
        evs = sorted(inc["events"], key=lambda e: float(e.get("ts", 0.0)))
        worst = max(evs, key=lambda e: severity_rank(
            str(e.get("severity", "info"))))
        out.append({
            "id": inc["id"], "run_id": inc["run_id"],
            "start_ts": inc["start_ts"], "end_ts": inc["end_ts"],
            "duration_s": round(inc["end_ts"] - inc["start_ts"], 3),
            "severity": worst.get("severity", "warn"),
            "event_count": sum(int(e.get("count", 1)) for e in evs),
            "procs": sorted({_event_proc(e) for e in evs}),
            "subsystems": sorted({str(e.get("subsystem", "")) for e in evs}),
            "kinds": sorted({f"{e.get('subsystem')}/{e.get('kind')}"
                             for e in evs}),
            "events": evs,
        })
    out.sort(key=lambda i: i["start_ts"])
    return out


class FleetCollector:
    """Merge-at-read federation over the latest snapshot per process."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1",
                 stale_after_s: float = 60.0,
                 straggler_mads: float = 6.0,
                 events_capacity: int = 4096,
                 incident_window_s: float = 30.0):
        self._host = host
        self._requested_port = port
        self.stale_after_s = float(stale_after_s)
        self.straggler_mads = float(straggler_mads)
        self.incident_window_s = float(incident_window_s)
        self._server = None  # exposition.RouteServer, built at start()
        self._lock = threading.Lock()
        # proc key -> {"identity", "dump", "heartbeat", "last_seen",
        #              "clock_offset_s", "origin_unix", "events_seq"}
        self._procs: Dict[str, Dict[str, Any]] = {}
        # fleet-wide event ring: APPEND semantics (each push carries only
        # events past the sender's cursor; the per-proc seq guard below
        # makes a re-push of the same tail idempotent)
        self._events: deque = deque(maxlen=int(events_capacity))
        self.events_ingested = 0

    # ------------------------------------------------------------- ingest
    def ingest(self, doc: Dict[str, Any],
               recv_time: Optional[float] = None) -> Dict[str, Any]:
        """Fold one pushed document (register/push/heartbeat all share this
        shape) into the collector state; returns the ack the HTTP layer
        sends back. In-process callers (tests, same-process supervisors)
        use it directly — HTTP is transport, not semantics."""
        now = recv_time if recv_time is not None else time.time()
        ident = fleet.ProcessIdentity.from_dict(
            doc.get("identity") or {"run_id": "?"})
        clock = doc.get("clock") or {}
        offset = None
        if clock.get("time_unix") is not None:
            # one-way handshake: includes transport latency, which is the
            # honest bound for the localhost/LAN fleets this targets
            offset = round(now - float(clock["time_unix"]), 6)
        with self._lock:
            entry = self._procs.setdefault(ident.key(), {})
            entry["identity"] = ident
            entry["last_seen"] = now
            if offset is not None:
                entry["clock_offset_s"] = offset
            if clock.get("origin_unix") is not None:
                entry["origin_unix"] = float(clock["origin_unix"])
            if "registry" in doc:
                entry["dump"] = doc["registry"]
            if "heartbeat" in doc:
                entry["heartbeat"] = dict(doc["heartbeat"])
            if doc.get("events"):
                # APPEND, unlike everything above: events are occurrences,
                # not cumulative state. The per-proc high-seq guard makes a
                # retried push (ack lost, client re-sends the same tail)
                # idempotent.
                high = int(entry.get("events_seq", 0))
                for ev in doc["events"]:
                    if not isinstance(ev, dict):
                        raise ValueError("events entries must be objects")
                    seq = int(ev.get("seq", 0))
                    if seq and seq <= high:
                        continue
                    high = max(high, seq)
                    ev = dict(ev)
                    ev.setdefault("identity", ident.to_dict())
                    ev["proc"] = ident.key()
                    ev["recv_ts"] = now
                    self._events.append(ev)
                    self.events_ingested += 1
                entry["events_seq"] = high
        return {"ok": True, "proc": ident.key(),
                **({"clock_offset_s": offset} if offset is not None else {})}

    def scrape(self, url: str, timeout_s: float = 5.0) -> Dict[str, Any]:
        """Pull one worker's ``/metrics.fleet`` dump and ingest it (the
        collector-initiated alternative to push). ``url`` is the worker
        MetricsServer base, e.g. ``http://127.0.0.1:9400``."""
        import urllib.request

        with urllib.request.urlopen(url.rstrip("/") + "/metrics.fleet",
                                    timeout=timeout_s) as resp:
            dump = json.loads(resp.read().decode())
        return self.ingest({"identity": dump.get("identity"),
                            "registry": dump,
                            "clock": {"time_unix": dump.get("time_unix")}})

    # ------------------------------------------------------------- views
    def processes(self) -> List[str]:
        with self._lock:
            return sorted(self._procs)

    def dumps(self) -> Dict[str, Dict[str, Any]]:
        """proc key -> the latest registry dump that process pushed — the
        raw inputs of the federated merge, for verifiers (the fleet
        smoke's bit-exactness gate sums these independently)."""
        with self._lock:
            return {k: e["dump"] for k, e in self._procs.items()
                    if e.get("dump") is not None}

    @staticmethod
    def _proc_labels(entries) -> Dict[str, str]:
        """entry key -> ``{proc=}`` label: the short ``p<index>`` when it is
        unique across the fleet, the run_id-qualified key otherwise — two
        standalone workers that both defaulted to process_index 0 (distinct
        minted run_ids) must not clobber each other's gauges, heartbeats,
        or straggler math."""
        shorts = [e["identity"].proc for _k, e in entries]
        dupes = {p for p in shorts if shorts.count(p) > 1}
        return {k: (e["identity"].key() if e["identity"].proc in dupes
                    else e["identity"].proc)
                for k, e in entries}

    def federated_registry(self) -> MetricsRegistry:
        """Build the merged view from the latest dump per process —
        deterministic merge order (sorted proc keys) so repeated renders of
        the same state are bit-identical."""
        with self._lock:
            entries = [(k, dict(v)) for k, v in sorted(self._procs.items())]
        labels = self._proc_labels(entries)
        reg = MetricsRegistry()
        heartbeats: Dict[str, Dict[str, Any]] = {}
        now = time.time()
        for key, entry in entries:
            proc = labels[key]
            dump = entry.get("dump")
            if dump is not None:
                fleet.merge_dump_into(reg, dump, proc_label=proc)
            hb = entry.get("heartbeat")
            if hb is not None:
                heartbeats[proc] = hb
                for field in ("queue_depth", "hbm_bytes_in_use"):
                    if hb.get(field) is not None:
                        reg.gauge(f"fleet/{field}", proc=proc).set(
                            float(hb[field]))
            reg.gauge("fleet/last_seen_age_s", proc=proc).set(
                round(now - entry["last_seen"], 3))
            if entry.get("clock_offset_s") is not None:
                reg.gauge("fleet/clock_offset_s", proc=proc).set(
                    entry["clock_offset_s"])
        # the ONE definition of fleet/processes: every registered member,
        # heartbeat or not — must always agree with the ledger's row count
        reg.gauge("fleet/processes").set(float(len(entries)))
        # disagg topology rollups (ISSUE 14): membership per declared role
        # (prefill/decode/...) plus role-summed serving rates inside
        # fleet_rollups — the phase pools read as two series
        roles = {labels[k]: e["identity"].role for k, e in entries}
        role_counts: Dict[str, int] = {}
        for r in roles.values():
            role_counts[r] = role_counts.get(r, 0) + 1
        for r, n in role_counts.items():
            reg.gauge("fleet/role_processes", role=r).set(float(n))
        fleet.fleet_rollups(reg, heartbeats,
                            straggler_mads=self.straggler_mads, roles=roles)
        return reg

    def render_prometheus(self) -> str:
        from deepspeed_tpu.telemetry import exposition

        # identity=False: the federated view spans processes — stamping the
        # collector's own process_info on it would misattribute the fleet
        return exposition.render_prometheus(self.federated_registry(),
                                            identity=False)

    def render_json(self) -> str:
        from deepspeed_tpu.telemetry import exposition

        return exposition.render_json_snapshot(self.federated_registry(),
                                               identity=False)

    def ledger(self) -> Dict[str, Any]:
        """The cluster health ledger: one row per process — what the
        elastic supervisor polls to decide drain/join/restart."""
        with self._lock:
            entries = [(k, dict(v)) for k, v in sorted(self._procs.items())]
        labels = self._proc_labels(entries)
        now = time.time()
        rates = {labels[k]: float(e["heartbeat"]["step_rate"])
                 for k, e in entries
                 if e.get("heartbeat", {}).get("step_rate") is not None}
        stragglers = fleet.straggler_flags(rates, mads=self.straggler_mads)
        rows = []
        for key, entry in entries:
            ident: fleet.ProcessIdentity = entry["identity"]
            age = now - entry["last_seen"]
            rows.append({
                "proc": key,
                "identity": ident.to_dict(),
                "last_seen_age_s": round(age, 3),
                "stale": age > self.stale_after_s,
                "clock_offset_s": entry.get("clock_offset_s"),
                "origin_unix": entry.get("origin_unix"),
                "heartbeat": entry.get("heartbeat"),
                "straggler": bool(stragglers.get(labels[key], False)),
            })
        return {"time_unix": now, "processes": rows}

    # ------------------------------------------------------------- events
    def events(self, proc: Optional[str] = None,
               min_severity: Optional[str] = None,
               subsystem: Optional[str] = None,
               since: Optional[float] = None,
               limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """The fleet event ring, filtered. ``proc`` matches either the full
        ``run_id/pN`` key or the short ``pN``; ``since`` is a unix ts over
        the event's own ``ts``."""
        with self._lock:
            out = list(self._events)
        if proc:
            out = [e for e in out
                   if e.get("proc") == proc
                   or str(e.get("proc", "")).endswith("/" + proc)]
        if min_severity:
            floor = severity_rank(min_severity)
            out = [e for e in out
                   if severity_rank(str(e.get("severity", "info"))) >= floor]
        if subsystem:
            out = [e for e in out if e.get("subsystem") == subsystem]
        if since is not None:
            out = [e for e in out if float(e.get("ts", 0.0)) >= float(since)]
        if limit is not None and limit >= 0:
            out = out[-int(limit):]
        return out

    def incidents(self, window_s: Optional[float] = None,
                  min_severity: str = "warn") -> List[Dict[str, Any]]:
        """Cross-process incident correlation over the event ring (see
        :func:`correlate_events`) — recomputed per read from the same
        state, so ids are stable across repeated GETs."""
        with self._lock:
            evs = list(self._events)
        return correlate_events(
            evs, window_s=self.incident_window_s if window_s is None
            else float(window_s), min_severity=min_severity)

    def _events_doc(self, query: Dict[str, str]) -> bytes:
        since = query.get("since")
        limit = query.get("limit")
        evs = self.events(
            proc=query.get("proc") or None,
            min_severity=query.get("severity") or None,
            subsystem=query.get("subsystem") or None,
            since=float(since) if since else None,
            limit=int(limit) if limit else None)
        return json.dumps({"time_unix": time.time(), "count": len(evs),
                           "events": evs}).encode()

    def _incidents_doc(self, query: Dict[str, str]) -> bytes:
        window = query.get("window_s")
        incs = self.incidents(
            window_s=float(window) if window else None,
            min_severity=query.get("severity") or "warn")
        return json.dumps({"time_unix": time.time(), "count": len(incs),
                           "incidents": incs}).encode()

    # ------------------------------------------------------------- console
    def _console_html(self) -> bytes:
        """GET /console: ONE self-contained page (inline CSS, zero external
        assets — it must render from a curl dump during the exact outage it
        exists for)."""
        esc = html.escape
        now = time.time()
        led = self.ledger()
        incidents = self.incidents()
        recent = self.events(limit=30)
        reg = self.federated_registry()
        gauges = reg.gauges()
        firing = []
        for key, val in sorted(gauges.items()):
            base, labels = decode_key(key)
            if base == "alerts/firing" and val > 0:
                firing.append((labels.get("rule", "?"), int(val)))
        slo = {k: v for k, v in sorted(gauges.items())
               if decode_key(k)[0] in (
                   "fleet/goodput", "fleet/tokens_per_s",
                   "fleet/step_rate_min", "fleet/processes",
                   "fleet/role_processes")}
        sev_color = {"info": "#8aa", "warn": "#c80", "critical": "#c22"}

        def ts_fmt(ts):
            try:
                return time.strftime("%H:%M:%S", time.localtime(float(ts)))
            except Exception:  # noqa: BLE001
                return "?"

        parts = [
            "<!doctype html><html><head><meta charset='utf-8'>",
            "<title>deepspeed_tpu fleet console</title><style>",
            "body{font:13px/1.4 monospace;margin:1.2em;background:#fafafa;"
            "color:#123}",
            "h1{font-size:17px}h2{font-size:14px;margin:1.2em 0 .3em;"
            "border-bottom:1px solid #ccc}",
            "table{border-collapse:collapse}td,th{padding:2px 9px;"
            "border:1px solid #ddd;text-align:left}",
            ".ok{color:#2b7}.bad{color:#c22;font-weight:bold}"
            ".warn{color:#c80}</style></head><body>",
            f"<h1>fleet console</h1><p>{len(led['processes'])} processes · "
            f"{len(firing)} firing alert(s) · {len(incidents)} incident(s) · "
            f"rendered {ts_fmt(now)}</p>",
        ]
        # firing alerts
        parts.append("<h2>firing alerts</h2>")
        if firing:
            parts.append("<table><tr><th>rule</th><th>instances</th></tr>")
            for rule, n in firing:
                parts.append(f"<tr><td class='bad'>{esc(rule)}</td>"
                             f"<td>{n}</td></tr>")
            parts.append("</table>")
        else:
            parts.append("<p class='ok'>none firing</p>")
        # incidents
        parts.append("<h2>recent incidents</h2>")
        if incidents:
            parts.append("<table><tr><th>id</th><th>severity</th><th>start"
                         "</th><th>dur</th><th>procs</th><th>kinds</th>"
                         "<th>events</th></tr>")
            for inc in incidents[-10:][::-1]:
                cls = "bad" if inc["severity"] == "critical" else "warn"
                parts.append(
                    f"<tr><td>{esc(inc['id'])}</td>"
                    f"<td class='{cls}'>{esc(inc['severity'])}</td>"
                    f"<td>{ts_fmt(inc['start_ts'])}</td>"
                    f"<td>{inc['duration_s']:.1f}s</td>"
                    f"<td>{esc(', '.join(inc['procs']))}</td>"
                    f"<td>{esc(', '.join(inc['kinds']))}</td>"
                    f"<td>{inc['event_count']}</td></tr>")
            parts.append("</table>")
        else:
            parts.append("<p class='ok'>no incidents</p>")
        # health ledger
        parts.append("<h2>health ledger</h2><table><tr><th>proc</th>"
                     "<th>role</th><th>age</th><th>step</th><th>rate</th>"
                     "<th>straggler</th><th>stale</th></tr>")
        for row in led["processes"]:
            hb = row.get("heartbeat") or {}
            stale = ("<td class='bad'>STALE</td>" if row["stale"]
                     else "<td class='ok'>ok</td>")
            strag = ("<td class='warn'>straggler</td>" if row["straggler"]
                     else "<td class='ok'>ok</td>")
            parts.append(
                f"<tr><td>{esc(str(row['proc']))}</td>"
                f"<td>{esc(str(row['identity'].get('role', '?')))}</td>"
                f"<td>{row['last_seen_age_s']:.1f}s</td>"
                f"<td>{esc(str(hb.get('step', '—')))}</td>"
                f"<td>{esc(str(hb.get('step_rate', '—')))}</td>"
                f"{strag}{stale}</tr>")
        parts.append("</table>")
        # SLO rollups
        parts.append("<h2>SLO rollups</h2>")
        if slo:
            parts.append("<table><tr><th>metric</th><th>value</th></tr>")
            for k, v in slo.items():
                parts.append(f"<tr><td>{esc(k)}</td><td>{v:.6g}</td></tr>")
            parts.append("</table>")
        else:
            parts.append("<p>no rollups yet</p>")
        # recent events
        parts.append("<h2>recent events</h2>")
        if recent:
            parts.append("<table><tr><th>ts</th><th>proc</th><th>sev</th>"
                         "<th>event</th><th>message</th><th>n</th></tr>")
            for ev in recent[::-1]:
                color = sev_color.get(str(ev.get("severity")), "#123")
                parts.append(
                    f"<tr><td>{ts_fmt(ev.get('ts'))}</td>"
                    f"<td>{esc(str(ev.get('proc', '?')))}</td>"
                    f"<td style='color:{color}'>"
                    f"{esc(str(ev.get('severity')))}</td>"
                    f"<td>{esc(str(ev.get('subsystem')))}/"
                    f"{esc(str(ev.get('kind')))}</td>"
                    f"<td>{esc(str(ev.get('message', ''))[:140])}</td>"
                    f"<td>{int(ev.get('count', 1))}</td></tr>")
            parts.append("</table>")
        else:
            parts.append("<p class='ok'>no events</p>")
        parts.append("</body></html>")
        return "".join(parts).encode()

    # -------------------------------------------------------------- serve
    def _healthz_doc(self) -> bytes:
        return json.dumps({
            "ok": True, "role": "collector",
            "identity": fleet.get_identity().to_dict(),
            "processes": len(self.processes()),
            "time_unix": time.time()}).encode()

    def start(self) -> "FleetCollector":
        if self._server is None:
            from deepspeed_tpu.telemetry.exposition import RouteServer

            js = "application/json"
            self._server = RouteServer(
                get_routes={
                    "/metrics": lambda: (
                        self.render_prometheus().encode(),
                        "text/plain; version=0.0.4; charset=utf-8"),
                    "/metrics.json": lambda: (
                        self.render_json().encode(), js),
                    "/fleet": lambda: (
                        json.dumps(self.ledger()).encode(), js),
                    "/healthz": lambda: (self._healthz_doc(), js),
                    # incident plane (ISSUE 20): query-taking handlers get
                    # the parsed query dict from RouteServer
                    "/events": lambda query: (self._events_doc(query), js),
                    "/incidents": lambda query: (
                        self._incidents_doc(query), js),
                    "/console": lambda: (
                        self._console_html(),
                        "text/html; charset=utf-8"),
                },
                # register/push/heartbeat/events all share the ingest shape
                # — the paths differ only in what the sender chose to
                # include
                post_routes={p: self.ingest
                             for p in ("/register", "/push", "/heartbeat",
                                       "/events")},
                port=self._requested_port, host=self._host,
                name="dstpu-fleet-collector")
        self._server.start()
        return self

    @property
    def port(self) -> Optional[int]:
        return self._server.port if self._server is not None else None

    def stop(self) -> None:
        if self._server is not None:
            self._server.stop()

    @property
    def url(self) -> Optional[str]:
        if self.port is None:
            return None
        return f"http://{self._host}:{self.port}"


class FleetClient:
    """One process's push side: registers (clock handshake), then pushes
    registry dumps + heartbeats — on demand
    (:meth:`push`) or on a background cadence (:meth:`start`).

    Push failures NEVER raise into the caller (a dead collector must not
    take the training step down with it): they count in ``push_failures``
    and warn once."""

    def __init__(self, url: str, identity: Optional[fleet.ProcessIdentity] = None,
                 registry=None, timeout_s: float = 2.0):
        self.url = url.rstrip("/")
        self._identity = identity
        self._registry = registry
        self.timeout_s = float(timeout_s)
        self.pushes = 0
        self.push_failures = 0
        self.clock_offset_s: Optional[float] = None
        self._warned = False
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # async-push hand-off: hot-path callers snapshot (sub-ms) and the
        # worker thread pays the HTTP round-trip. ONE pending slot, latest
        # wins — snapshots are cumulative, so a newer one strictly
        # supersedes an unsent older one (no queue to bound)
        self._pending: Optional[Dict[str, Any]] = None
        self._pending_lock = threading.Lock()
        self._pending_event = threading.Event()
        self._worker: Optional[threading.Thread] = None
        # event-stream push cursor: advanced only on an ACKED push, so a
        # failed push's events ride the next one (the collector's per-proc
        # seq guard dedups the overlap if the ack was merely lost)
        self._events_sent_seq = 0

    def _identity_dict(self) -> Dict[str, Any]:
        ident = self._identity or fleet.get_identity()
        return ident.to_dict()

    def _post(self, path: str, doc: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        import urllib.request

        body = json.dumps(doc).encode()
        req = urllib.request.Request(
            self.url + path, data=body,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=self.timeout_s) as resp:
                return json.loads(resp.read().decode())
        except Exception as e:  # noqa: BLE001 - collector may be down
            self.push_failures += 1
            if not self._warned:
                self._warned = True
                logger.warning(
                    f"fleet: push to {self.url}{path} failed ({e}); further "
                    "failures count silently in push_failures")
            return None

    def register(self) -> Optional[Dict[str, Any]]:
        ack = self._post("/register", {
            "identity": self._identity_dict(),
            "clock": fleet.clock_sync_doc()})
        if ack is not None and ack.get("clock_offset_s") is not None:
            self.clock_offset_s = float(ack["clock_offset_s"])
        return ack

    def heartbeat_doc(self) -> Dict[str, Any]:
        """The per-process health sample: last step + age, step wall time
        (rate), HBM watermark, serving queue depth, anomaly flags — read
        from the process registry so it costs a few dict lookups, never a
        device fetch."""
        from deepspeed_tpu.telemetry.tracer import get_tracer

        registry = self._registry or get_tracer().registry
        info = fleet.last_step_info()
        hb: Dict[str, Any] = {"step": info["step"],
                              "last_step_age_s": info["age_s"]}
        h = registry.peek_histogram("span/train_batch")
        if h is not None and h.count:
            hb["step_time_ms"] = round(h.last * 1e3, 3)
            if h.last > 0:
                hb["step_rate"] = round(1.0 / h.last, 4)
        gauges = registry.gauges()
        for name, field in (
                ("mem/device_bytes_in_use", "hbm_bytes_in_use"),
                ("mem/device_peak_bytes_in_use", "hbm_peak_bytes"),
                ("mem/live_array_bytes", "hbm_bytes_in_use"),
                ("serving/queue_depth", "queue_depth"),
                ("anomaly/step_straggler", "straggler"),
                ("anomaly/step_regression", "regression"),
                # cross-process divergence comparator (telemetry/numerics.py):
                # the whole-tree xor digest is bit-stable across mesh shapes,
                # so unequal values across processes mean diverged replicas
                ("numerics/digest_checksum", "numerics_checksum")):
            if name in gauges and field not in hb:
                hb[field] = gauges[name]
        return hb

    def _build_doc(self, heartbeat_extra: Optional[Dict[str, Any]],
                   include_registry: bool) -> Dict[str, Any]:
        hb = self.heartbeat_doc()
        if heartbeat_extra:
            hb.update(heartbeat_extra)
        doc: Dict[str, Any] = {
            "identity": self._identity_dict(),
            "clock": fleet.clock_sync_doc(),
            "heartbeat": hb,
        }
        if include_registry:
            doc["registry"] = fleet.registry_dump(
                registry=self._registry,
                identity=self._identity or fleet.get_identity())
        # structured events (ISSUE 20): ship the tail past the acked cursor
        from deepspeed_tpu.telemetry.events import get_event_stream

        stream = get_event_stream()
        tail = stream.drain_since(self._events_sent_seq)
        if tail:
            doc["events"] = tail
            doc["events_high_seq"] = tail[-1]["seq"]
        return doc

    def push(self, heartbeat_extra: Optional[Dict[str, Any]] = None,
             include_registry: bool = True) -> Optional[Dict[str, Any]]:
        """One synchronous snapshot push (background-thread and shutdown
        callers). ``heartbeat_extra`` merges caller facts into the
        heartbeat (the resilience supervisor stamps rewind counts)."""
        return self._send(self._build_doc(heartbeat_extra, include_registry))

    def push_async(self, heartbeat_extra: Optional[Dict[str, Any]] = None,
                   include_registry: bool = True) -> None:
        """Hot-path push: snapshot NOW (sub-millisecond — dump + heartbeat
        are dict walks), pay the HTTP round-trip on the client's worker
        thread. One pending slot, latest-wins: snapshots are cumulative, so
        an unsent older one is strictly superseded — a slow collector
        back-pressures into dropped intermediate snapshots, never into the
        caller's step."""
        doc = self._build_doc(heartbeat_extra, include_registry)
        self._ensure_worker()
        with self._pending_lock:
            self._pending = doc
        self._pending_event.set()

    def _send(self, doc: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        ack = self._post("/push", doc)
        if ack is not None:
            self.pushes += 1
            if ack.get("clock_offset_s") is not None:
                self.clock_offset_s = float(ack["clock_offset_s"])
            high = doc.get("events_high_seq")
            if high is not None and high > self._events_sent_seq:
                self._events_sent_seq = int(high)
        return ack

    def _ensure_worker(self) -> None:
        if self._worker is not None and self._worker.is_alive():
            return

        def drain():
            while True:
                self._pending_event.wait()
                with self._pending_lock:
                    doc, self._pending = self._pending, None
                    self._pending_event.clear()
                    self._inflight = doc is not None
                if doc is not None:
                    try:
                        self._send(doc)
                    finally:
                        self._inflight = False

        self._inflight = False
        self._worker = threading.Thread(
            target=drain, name="dstpu-fleet-push-async", daemon=True)
        self._worker.start()

    def flush(self, timeout_s: float = 5.0) -> None:
        """Wait until the async-pending slot drains (tests, shutdown)."""
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            with self._pending_lock:
                idle = (self._pending is None
                        and not self._pending_event.is_set()
                        and not getattr(self, "_inflight", False))
            if idle:
                return
            time.sleep(0.005)

    # ------------------------------------------------------ background push
    def start(self, interval_s: float = 5.0) -> "FleetClient":
        """Register, then push on a daemon-thread cadence — the zero-touch
        wiring the ``telemetry.fleet_url`` config key turns on."""
        if self._thread is not None:
            return self
        self.register()
        self._stop.clear()

        def loop():
            while not self._stop.wait(interval_s):
                self.push()

        self._thread = threading.Thread(
            target=loop, name="dstpu-fleet-push", daemon=True)
        self._thread.start()
        return self

    def stop(self, final_push: bool = True) -> None:
        if self._thread is not None:
            self._stop.set()
            self._thread.join(timeout=self.timeout_s + 1.0)
            self._thread = None
        if final_push:
            self.push()
