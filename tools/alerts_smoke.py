#!/usr/bin/env python
"""Incident-plane smoke: alerts + incidents, exit-gated BOTH ways.

The proof that ISSUE 20's incident plane actually fires and actually
stays quiet:

  1. **Clean run MUST be quiet** — a 20-step train run with the numerics
     sentinel sampling every step, the default alert rule pack evaluating,
     and events federating to a live :class:`FleetCollector` produces ZERO
     warn+ events, ZERO firing alerts, and ZERO incidents. An alert plane
     that cries wolf gets ignored; a noisy round fails the stage.
  2. **Injected faults MUST correlate into ONE incident** — the classic
     double fault: ``flip_param_bit`` (one mantissa bit on one dp
     replica's param copy -> the numerics divergence sentinel) plus a
     SIGKILLed serving-fabric replica daemon (heartbeat death on the
     ``RemoteReplica`` -> ``fabric/replica_unreachable``). Both typed
     events ship to the collector and MUST correlate into exactly one
     incident naming both kinds, visible at ``GET /incidents``; the
     matching alerts (``numerics_divergence``, ``replica_unreachable``)
     MUST reach the firing state; and ``tools/incident_report.py`` run
     against the collector MUST emit a timeline naming both events.

Prints one JSON line of evidence.

CPU-ONLY: the parent touches jax and then starts children that do too, so
every process here pins ``JAX_PLATFORMS=cpu``. On a TPU a chip belongs to one
process at a time — a fleet on chips is one daemon process per chip with a
parent that stays off jax; this tool never asks for a device.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

CLEAN_STEPS = 20


def _model_spec():
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from unit.simple_model import simple_model_spec

    return simple_model_spec()


def _batch(eng, seed):
    from unit.simple_model import random_batch

    return random_batch(eng.train_batch_size, seed=seed)


def _engine():
    import deepspeed_tpu

    eng, *_ = deepspeed_tpu.initialize(
        model=_model_spec(),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 10_000,
            "numerics": {
                "enabled": True,
                "sample_every": 4,
                "sentinel_sample_every": 1,
                "divergence_policy": "log",
            },
        },
    )
    return eng


def _spawn_daemon(run_id: str, timeout: float = 120.0):
    """One replica daemon subprocess sharing the smoke's run id; returns
    (Popen, url) once it prints its port line."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "deepspeed_tpu.fabric.replica_daemon",
         "--port", "0", "--index", "1", "--run-id", run_id],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO, text=True)
    t0 = time.monotonic()
    # scan past any log lines for the one JSON port announcement
    while time.monotonic() - t0 < timeout:
        line = proc.stdout.readline()
        if not line:
            break
        s = line.strip()
        if s.startswith("{") and '"port"' in s:
            return proc, f"http://127.0.0.1:{json.loads(s)['port']}"
    proc.kill()
    raise RuntimeError("replica daemon failed to announce a port")


def run_smoke() -> dict:
    from deepspeed_tpu.diagnostics.faultinject import FaultInjector
    from deepspeed_tpu.telemetry import alerts as alerts_mod
    from deepspeed_tpu.telemetry import events as events_mod
    from deepspeed_tpu.telemetry import fleet, get_tracer
    from deepspeed_tpu.telemetry import numerics
    from deepspeed_tpu.telemetry.collector import FleetClient, FleetCollector

    evidence: dict = {"clean": {}, "incident": {}}
    gates: dict = {}
    tmp = tempfile.mkdtemp(prefix="dstpu_alerts_smoke_")

    run_id = f"alerts-smoke-{os.getpid():x}"
    ident = fleet.configure_identity(run_id=run_id, process_index=0,
                                     role="train")
    tr = get_tracer()
    tr.configure(enabled=True)
    stream = events_mod.configure_events(capacity=4096)
    stream.clear()
    notif_path = os.path.join(tmp, "alert_notifications.jsonl")
    alert_eng = alerts_mod.configure_alerts(jsonl_path=notif_path)

    collector = FleetCollector(stale_after_s=120.0,
                               incident_window_s=30.0).start()
    client = FleetClient(collector.url, identity=ident, registry=tr.registry)
    ack = client.register()
    if not (ack and ack.get("ok")):
        raise RuntimeError("collector registration failed")

    # ---- gate 1: clean 20-step run is ALL quiet ------------------------
    eng = _engine()
    for s in range(CLEAN_STEPS):
        eng.train_batch(batch=_batch(eng, seed=s))
    alert_eng.evaluate()
    client.push()
    warn_events = stream.events(min_severity="warn")
    clean_incidents = collector.incidents()
    evidence["clean"] = {
        "steps": CLEAN_STEPS,
        "warn_events": [f"{e.subsystem}/{e.kind}" for e in warn_events],
        "firing_alerts": [f["rule"] for f in alert_eng.firing()],
        "incidents": len(clean_incidents),
        "events_total": stream.total_emitted,
    }
    gates["clean_quiet"] = (not warn_events and not alert_eng.firing()
                            and not clean_incidents)

    # ---- gate 2: double fault -> ONE correlated incident ---------------
    from deepspeed_tpu.fabric.remote import (
        RemoteReplica,
        RemoteReplicaDownError,
    )

    daemon, url = _spawn_daemon(run_id)
    replica = RemoteReplica(url, heartbeat_interval_s=0.05,
                            heartbeat_miss_limit=3)
    evidence["incident"]["daemon_url"] = url
    evidence["incident"]["daemon_alive_rpc"] = bool(
        replica.heartbeat_now())

    fi = FaultInjector()
    flipped = fi.flip_param_bit(eng)
    obs = numerics.get_observatory()
    before = obs.divergence_events_seen
    detect_steps = -1
    for extra in range(1, 5):
        eng.train_batch(batch=_batch(eng, seed=100 + extra))
        if obs.divergence_events_seen > before:
            detect_steps = extra
            break
    gates["divergence_detected"] = detect_steps > 0
    evidence["incident"]["flipped_leaf"] = flipped
    evidence["incident"]["divergence_detect_steps"] = detect_steps

    fi.kill_replica_daemon(daemon)
    deadline = time.monotonic() + 10.0
    while replica.alive and time.monotonic() < deadline:
        time.sleep(0.05)
    gates["replica_unreachable_detected"] = not replica.alive
    # a dispatch into the dead daemon: the per-endpoint failure path
    try:
        replica.query(1)
    except (RemoteReplicaDownError, ValueError):
        pass
    replica.close()

    alert_eng.evaluate()
    firing = {f["rule"] for f in alert_eng.firing()}
    evidence["incident"]["firing_alerts"] = sorted(firing)
    gates["alerts_fired"] = {"numerics_divergence",
                             "replica_unreachable"} <= firing

    client.push()
    incidents = collector.incidents()
    evidence["incident"]["incidents"] = [
        {"id": i["id"], "kinds": i["kinds"], "severity": i["severity"],
         "event_count": i["event_count"]} for i in incidents]
    want = {"numerics/divergence", "fabric/replica_unreachable"}
    gates["one_incident_names_both"] = (
        len(incidents) == 1 and want <= set(incidents[0]["kinds"]))

    # ---- gate 3: incident_report joins the artifacts -------------------
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import incident_report

    report_path = os.path.join(tmp, "incident_report.md")
    rc = incident_report.main(["--url", collector.url, "--out", report_path])
    with open(report_path, encoding="utf-8") as f:
        report = f.read()
    gates["report_names_both"] = (
        rc == 0 and bool(incidents)
        and incidents[0]["id"] in report
        and "numerics/divergence" in report
        and "fabric/replica_unreachable" in report)
    evidence["incident"]["report"] = report_path
    evidence["incident"]["alert_notifications"] = (
        os.path.getsize(notif_path) > 0 if os.path.exists(notif_path)
        else False)

    collector.stop()
    return {"gates": gates, "evidence": evidence, "out_dir": tmp}


def main() -> int:
    result = run_smoke()
    ok = all(bool(v) for v in result["gates"].values())
    print(json.dumps({"alerts_smoke": "clean_quiet+double_fault_incident",
                      "ok": ok, **result}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
