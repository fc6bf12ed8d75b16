#!/usr/bin/env python
"""Merge per-process telemetry JSONL streams into ONE Perfetto trace.

Each process of a fleet exports its own ``events.jsonl``
(``telemetry.export_jsonl``): a ``process_meta`` line (identity + the
wall-clock ``origin_unix`` its event ``ts`` values are relative to),
``track_name`` lines, then raw tracer events. This tool joins K such
streams on a shared timeline:

  - every stream gets a DISTINCT, stable Chrome pid (the identity's
    process_index — not the OS pid, which collides across hosts), with a
    ``process_name`` metadata row naming run_id/role/host;
  - timestamps align via each stream's ``origin_unix`` anchor:
    ``merged_ts = (origin_unix + ts) - min(origin_unix)``. Optionally
    ``--ledger fleet.json`` (the collector's ``GET /fleet`` document)
    applies the clock-offset handshake each process performed at collector
    registration — for fleets whose hosts' wall clocks disagree;
  - flow events pass through untouched: both sides of a cross-process
    dispatch derived the SAME flow id from the trace context
    (``fleet.TraceContext``), so the router process's admission arrow
    lands in the replica process's ``serve:dispatch`` slice once the
    streams share a timeline.

Usage:
  python tools/trace_merge.py -o merged_trace.json p0/events.jsonl p1/events.jsonl
  python tools/trace_merge.py -o merged.json --ledger fleet.json telemetry_out/*/events.jsonl

Open the output at https://ui.perfetto.dev.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional


def read_stream(path: str) -> Dict[str, Any]:
    """One JSONL stream -> {"meta", "tracks": {tid: name}, "events": [...]}.
    Streams from pre-fleet exports (no meta line) still merge: identity
    defaults empty and the origin anchor falls back to 0 (events keep
    their relative timeline)."""
    meta: Dict[str, Any] = {}
    tracks: Dict[int, str] = {}
    events: List[Dict[str, Any]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            kind = rec.get("kind")
            if kind == "process_meta":
                meta = rec
            elif kind == "track_name":
                tracks[int(rec["tid"])] = rec.get("track", "")
            elif kind in ("span", "instant", "flow", "counter"):
                events.append(rec)
    return {"path": path, "meta": meta, "tracks": tracks, "events": events}


def _ledger_offsets(ledger_path: Optional[str]) -> Dict[str, float]:
    """proc key -> clock_offset_s from a collector ``GET /fleet`` doc."""
    if not ledger_path:
        return {}
    with open(ledger_path) as f:
        doc = json.load(f)
    out = {}
    for row in doc.get("processes", []):
        if row.get("clock_offset_s") is not None:
            out[row["proc"]] = float(row["clock_offset_s"])
    return out


def merge_streams(paths: List[str], ledger: Optional[str] = None
                  ) -> Dict[str, Any]:
    """K per-process JSONL streams -> one Chrome trace-event JSON object."""
    streams = [read_stream(p) for p in paths]
    offsets = _ledger_offsets(ledger)

    def proc_key(s) -> str:
        ident = s["meta"].get("identity") or {}
        return f"{ident.get('run_id', '?')}/p{ident.get('process_index', 0)}"

    def origin(s) -> float:
        o = float(s["meta"].get("origin_unix", 0.0))
        # the handshake offset maps the sender's clock onto the collector's:
        # adding it places every stream on the COLLECTOR's wall clock
        return o + offsets.get(proc_key(s), 0.0)

    base = min((origin(s) for s in streams), default=0.0)
    out: List[Dict[str, Any]] = []
    used_pids: Dict[int, int] = {}
    for i, s in enumerate(streams):
        ident = s["meta"].get("identity") or {}
        pid = int(ident.get("process_index", i))
        if pid in used_pids:  # two streams claiming one index still separate
            pid = max(used_pids) + 1
        used_pids[pid] = 1
        shift_us = (origin(s) - base) * 1e6
        label = (f"p{ident.get('process_index', i)} "
                 f"{ident.get('role', '?')}@{ident.get('host', '?')} "
                 f"run={ident.get('run_id', '?')}")
        out.append({"name": "process_name", "ph": "M", "pid": pid,
                    "args": {"name": label}})
        out.append({"name": "process_sort_index", "ph": "M", "pid": pid,
                    "args": {"sort_index": pid}})
        for tid, tname in sorted(s["tracks"].items()):
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tid, "args": {"name": tname}})
        for ev in s["events"]:
            ts_us = ev["ts"] * 1e6 + shift_us
            kind = ev["kind"]
            if kind == "span":
                rec: Dict[str, Any] = {
                    "name": ev["name"], "cat": ev.get("cat", "span"),
                    "ph": "X", "ts": ts_us, "dur": ev["dur"] * 1e6,
                    "pid": pid, "tid": ev["tid"]}
                if "args" in ev:
                    rec["args"] = ev["args"]
            elif kind == "instant":
                rec = {"name": ev["name"], "cat": ev.get("cat", "event"),
                       "ph": "i", "s": "t", "ts": ts_us, "pid": pid,
                       "tid": ev["tid"]}
                if "args" in ev:
                    rec["args"] = ev["args"]
            elif kind == "flow":
                rec = {"name": ev["name"], "cat": ev.get("cat", "flow"),
                       "ph": ev["ph"], "id": ev["id"], "ts": ts_us,
                       "pid": pid, "tid": ev["tid"]}
                if ev["ph"] == "f":
                    rec["bp"] = "e"
            else:  # counter
                rec = {"name": ev["name"], "ph": "C", "ts": ts_us,
                       "pid": pid, "args": {"value": ev["value"]}}
            out.append(rec)
    return {
        "traceEvents": out,
        "displayTimeUnit": "ms",
        "otherData": {
            "merged_from": [s["path"] for s in streams],
            "processes": [
                {**(s["meta"].get("identity") or {}),
                 "origin_unix": s["meta"].get("origin_unix")}
                for s in streams],
        },
    }


def linked_flow_pids(trace: Dict[str, Any]) -> Dict[int, List[int]]:
    """flow id -> sorted pids that emitted BINDABLE events for it — keyed
    the way Chrome actually binds arrows, on (cat, name, id), so two
    processes that share an id but disagree on the name (no arrow drawn)
    do NOT count as linked. The smoke's exit-gate asks whether any flow
    links spans from >= 2 processes."""
    by_key: Dict[tuple, set] = {}
    for ev in trace["traceEvents"]:
        if ev.get("ph") in ("s", "t", "f") and "id" in ev:
            key = (ev.get("cat", "flow"), ev.get("name", ""), ev["id"])
            by_key.setdefault(key, set()).add(ev["pid"])
    # per flow id, report the pid set of its most-connected bindable key —
    # events under a DIFFERENT name never merge, exactly like the viewer
    out: Dict[int, List[int]] = {}
    for (_cat, _name, fid), pids in by_key.items():
        if fid not in out or len(pids) > len(out[fid]):
            out[fid] = sorted(pids)
    return out


def migration_links(trace: Dict[str, Any]) -> Dict[int, List[int]]:
    """flow id -> sorted pids, for flows that STEP inside a
    ``serve:migrate`` slice — the disaggregated-serving hand-off arrow
    (ISSUE 14): a request's admission flow starts on the prefill replica's
    stream and steps inside the decode replica's ``serve:migrate`` import
    slice, so the merged trace draws the prefill->decode migration arrow.
    The disagg smoke gates on at least one such link."""
    # one pass each over slices and flow events (a merge can carry
    # thousands of migrations — no per-step rescans of the whole stream)
    slices: Dict[Any, List[Any]] = {}
    for e in trace["traceEvents"]:
        if e.get("ph") == "X" and e.get("name") == "serve:migrate":
            slices.setdefault((e["pid"], e.get("tid")), []).append(
                (e["ts"], e["ts"] + e.get("dur", 0.0)))
    flow_pids: Dict[int, set] = {}  # fid -> pids of EVERY bindable event of
    migrated: set = set()           # the flow — the arrow spans src and dst
    for ev in trace["traceEvents"]:
        if ev.get("ph") not in ("s", "t", "f") or "id" not in ev:
            continue
        fid = ev["id"]
        flow_pids.setdefault(fid, set()).add(ev["pid"])
        if ev["ph"] == "t":
            spans = slices.get((ev["pid"], ev.get("tid")), ())
            if any(a <= ev["ts"] <= b for a, b in spans):
                migrated.add(fid)
    return {f: sorted(flow_pids[f]) for f in migrated}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("inputs", nargs="+", help="per-process events.jsonl files")
    ap.add_argument("-o", "--output", default="merged_trace.json")
    ap.add_argument("--ledger", default=None,
                    help="collector GET /fleet JSON (clock-offset handshake)")
    args = ap.parse_args(argv)
    trace = merge_streams(args.inputs, ledger=args.ledger)
    with open(args.output, "w") as f:
        json.dump(trace, f)
    links = {f: p for f, p in linked_flow_pids(trace).items() if len(p) > 1}
    n_mig = len(migration_links(trace))
    n_ev = len(trace["traceEvents"])
    print(f"wrote {args.output}: {n_ev} events from {len(args.inputs)} "
          f"stream(s); {len(links)} cross-process flow link(s); "
          f"{n_mig} migration flow link(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
