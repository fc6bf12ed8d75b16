#!/usr/bin/env python
"""Post-mortem generator: one markdown timeline per incident.

Joins the incident plane's artifacts around a correlated incident id:

  - **events** from one or more ``event_log.jsonl`` exports
    (``telemetry/events.py``) and/or a live ``FleetCollector`` URL
    (``GET /incidents`` — the collector's correlation is authoritative
    when a URL is given; local JSONL files are correlated here with the
    same ``correlate_events`` join);
  - **flight-recorder dumps** (``flight_record*.jsonl``) whose header
    timestamp falls inside the incident window (+/- margin), with the
    step records nearest the incident inlined;
  - **profiler-capture trace dirs** (``profiling/capture.py`` writes
    ``step{N}`` dirs) whose mtime falls inside the window.

Usage:
  python tools/incident_report.py --events telemetry_out/event_log.jsonl \
      --flight-records 'telemetry_out/flight_record*.jsonl' \
      --captures telemetry_out --out incident_report.md
  python tools/incident_report.py --url http://127.0.0.1:9400 \
      --incident inc-ab12cd34ef
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
import urllib.request
from typing import Any, Dict, List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _load_local_events(paths: List[str]) -> List[Dict[str, Any]]:
    """Event wire dicts from ``export_jsonl`` files, annotated with the
    ``proc`` key the collector would have stamped."""
    out: List[Dict[str, Any]] = []
    for pattern in paths:
        for path in sorted(glob.glob(pattern)) or [pattern]:
            if not os.path.exists(path):
                continue
            with open(path, encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    d = json.loads(line)
                    if d.get("kind") == "process_meta" or "severity" not in d:
                        continue
                    ident = d.get("identity") or {}
                    d.setdefault("proc", f"{ident.get('run_id', '?')}"
                                         f"/p{ident.get('process_index', 0)}")
                    out.append(d)
    return out


def _fetch(url: str, path: str) -> Dict[str, Any]:
    with urllib.request.urlopen(url.rstrip("/") + path, timeout=10) as resp:
        return json.loads(resp.read().decode())


def _flight_dumps(patterns: List[str]) -> List[Dict[str, Any]]:
    """Parsed flight records: header + step records per dump file."""
    dumps = []
    for pattern in patterns:
        for path in sorted(glob.glob(pattern)) or [pattern]:
            if not os.path.exists(path):
                continue
            header, steps = None, []
            try:
                with open(path, encoding="utf-8") as f:
                    for line in f:
                        line = line.strip()
                        if not line:
                            continue
                        d = json.loads(line)
                        if d.get("kind") == "header":
                            header = d
                        elif d.get("kind") == "step_record":
                            steps.append(d)
            except (OSError, ValueError):
                continue
            if header is not None:
                dumps.append({"path": path, "header": header, "steps": steps})
    return dumps


def _capture_dirs(roots: List[str]) -> List[Dict[str, Any]]:
    """Profiler-capture trace dirs (``**/step*/``) with their mtimes."""
    out = []
    for root in roots:
        if not os.path.isdir(root):
            continue
        for dirpath, dirnames, _ in os.walk(root):
            for d in dirnames:
                if d.startswith("step") and d[4:].isdigit():
                    full = os.path.join(dirpath, d)
                    try:
                        out.append({"path": full,
                                    "mtime": os.path.getmtime(full)})
                    except OSError:
                        pass
    return out


def _ts(t: float) -> str:
    return time.strftime("%Y-%m-%d %H:%M:%S", time.localtime(t)) + f".{int((t % 1) * 1000):03d}"


def render_incident(inc: Dict[str, Any], dumps: List[Dict[str, Any]],
                    captures: List[Dict[str, Any]],
                    margin_s: float = 60.0) -> str:
    """One incident -> one markdown section: the event timeline plus every
    artifact whose timestamp lands inside the widened window."""
    lo = float(inc["start_ts"]) - margin_s
    hi = float(inc["end_ts"]) + margin_s
    lines = [
        f"## Incident `{inc['id']}`",
        "",
        f"- **run**: `{inc['run_id']}`  |  **severity**: {inc['severity']}"
        f"  |  **events**: {inc['event_count']}"
        f"  |  **duration**: {inc['duration_s']:.1f}s",
        f"- **window**: {_ts(inc['start_ts'])} — {_ts(inc['end_ts'])}",
        f"- **processes**: {', '.join('`%s`' % p for p in inc['procs'])}",
        f"- **kinds**: {', '.join('`%s`' % k for k in inc['kinds'])}",
        "",
        "### Timeline",
        "",
        "| time | proc | sev | subsystem/kind | message |",
        "|---|---|---|---|---|",
    ]
    for ev in inc["events"]:
        msg = str(ev.get("message", "")).replace("|", "\\|").replace("\n", " ")
        if len(msg) > 160:
            msg = msg[:157] + "..."
        count = int(ev.get("count", 1))
        if count > 1:
            msg += f" (x{count})"
        step = ev.get("step")
        lines.append(
            f"| {_ts(float(ev['ts']))}"
            + (f" (step {step})" if step is not None else "")
            + f" | `{ev.get('proc', '?')}` | {ev.get('severity')} "
            f"| `{ev.get('subsystem')}/{ev.get('kind')}` | {msg} |")

    near_dumps = [d for d in dumps
                  if lo <= float(d["header"].get("time_unix", 0.0)) <= hi]
    if near_dumps:
        lines += ["", "### Flight records", ""]
        for d in near_dumps:
            hdr = d["header"]
            lines.append(
                f"- `{d['path']}` — reason `{hdr.get('reason')}`, "
                f"{hdr.get('n_records', 0)} step records, dumped "
                f"{_ts(float(hdr.get('time_unix', 0.0)))}")
            tail = d["steps"][-3:]
            for s in tail:
                mets = {k: v for k, v in (s.get("metrics") or {}).items()
                        if isinstance(v, (int, float))}
                brief = ", ".join(f"{k}={v:.4g}" for k, v in
                                  sorted(mets.items())[:6])
                lines.append(f"    - step {s.get('step')}: {brief}")

    near_caps = [c for c in captures if lo <= c["mtime"] <= hi]
    if near_caps:
        lines += ["", "### Profiler captures", ""]
        for c in sorted(near_caps, key=lambda c: c["mtime"]):
            lines.append(f"- `{c['path']}` ({_ts(c['mtime'])})")

    lines.append("")
    return "\n".join(lines)


def build_report(incidents: List[Dict[str, Any]],
                 dumps: List[Dict[str, Any]],
                 captures: List[Dict[str, Any]],
                 margin_s: float = 60.0) -> str:
    head = [
        "# Incident report",
        "",
        f"Generated {_ts(time.time())} — {len(incidents)} incident(s).",
        "",
    ]
    if not incidents:
        head.append("No incidents correlated from the provided events.")
        head.append("")
    body = [render_incident(inc, dumps, captures, margin_s)
            for inc in incidents]
    return "\n".join(head + body)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--events", nargs="*", default=[],
                    help="event_log.jsonl export path(s)/glob(s)")
    ap.add_argument("--url", default=None,
                    help="FleetCollector URL (uses its GET /incidents)")
    ap.add_argument("--flight-records", nargs="*", default=[],
                    help="flight_record*.jsonl path(s)/glob(s)")
    ap.add_argument("--captures", nargs="*", default=[],
                    help="dir(s) scanned for profiler capture stepN dirs")
    ap.add_argument("--incident", default=None,
                    help="report only this incident id")
    ap.add_argument("--window", type=float, default=30.0,
                    help="correlation window seconds (local events)")
    ap.add_argument("--severity", default="warn",
                    help="min severity folded into incidents")
    ap.add_argument("--margin", type=float, default=60.0,
                    help="artifact-join margin seconds around the window")
    ap.add_argument("--out", default=None, help="markdown path (default stdout)")
    args = ap.parse_args(argv)

    from deepspeed_tpu.telemetry.collector import correlate_events

    incidents: List[Dict[str, Any]] = []
    if args.url:
        doc = _fetch(args.url, f"/incidents?window_s={args.window}"
                               f"&severity={args.severity}")
        incidents.extend(doc.get("incidents", []))
    local = _load_local_events(args.events)
    if local:
        have = {i["id"] for i in incidents}
        for inc in correlate_events(local, window_s=args.window,
                                    min_severity=args.severity):
            if inc["id"] not in have:
                incidents.append(inc)
    if args.incident:
        incidents = [i for i in incidents if i["id"] == args.incident]
        if not incidents:
            print(f"incident_report: no incident {args.incident!r} found",
                  file=sys.stderr)
            return 2
    incidents.sort(key=lambda i: i["start_ts"])

    dumps = _flight_dumps(args.flight_records)
    captures = _capture_dirs(args.captures)
    report = build_report(incidents, dumps, captures, args.margin)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(report)
        print(f"incident_report: wrote {args.out} "
              f"({len(incidents)} incident(s))")
    else:
        print(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
