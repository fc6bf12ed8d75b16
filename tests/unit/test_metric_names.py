"""Tier-1 lint: registry metric names follow the ``subsystem/name`` convention.

The telemetry registry is get-or-create by string, so a typo'd or
unconventioned name silently creates a new metric family that no dashboard,
exposition scrape, or doc catalogue knows about. Same pattern as
``test_no_bare_shard_map.py``: grep the tree so the regression can't land
quietly.

Rules (docs/telemetry.md "label conventions"):
  - every name passed to ``registry.counter/gauge/histogram``,
    ``tracer.count`` or ``tracer.sample_counter`` is ``subsystem/name``
  - the subsystem prefix is a literal (an f-string may interpolate only
    after ``subsystem/``) and comes from the known set below
  - name characters are ``[a-z0-9_/.:]`` (metric names are registry-side;
    the Prometheus exposition handles identifier mapping)
"""

import os
import re

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# one place to extend when a PR adds a legitimate new subsystem
ALLOWED_SUBSYSTEMS = {
    "alerts",
    "anomaly",
    "ckpt",
    "events",
    "coll",
    "comm",
    "compile",
    "data",
    "fabric",
    "fleet",
    "flops",
    "hbm",
    "health",
    "mem",
    "moe",
    "numerics",
    "program",
    "recompile",
    "router",
    "serving",
    "span",
}

# .counter("x") / .gauge( / .histogram( / .sample_counter( are registry- or
# tracer-specific method names; bare .count( is too generic (str.count), so
# it is matched only on tracer-ish receivers.
CALL_RE = re.compile(
    r"\.(?:counter|gauge|histogram|sample_counter)\(\s*f?\"([^\"]+)\"")
TRACER_COUNT_RE = re.compile(
    r"\b(?:tracer|_tracer|tr)\.count\(\s*f?\"([^\"]+)\"")

NAME_RE = re.compile(r"^[a-z0-9_]+/[a-z0-9_/.:{}]*$")

SCAN_DIRS = ("deepspeed_tpu", "tools")


def _python_files():
    for d in SCAN_DIRS:
        for root, _dirs, files in os.walk(os.path.join(REPO_ROOT, d)):
            if ".jax_cache" in root or "__pycache__" in root:
                continue
            for f in files:
                if f.endswith(".py"):
                    yield os.path.join(root, f)


def _check_name(name: str):
    """Returns a violation string or None. ``name`` is the string literal as
    written; f-string placeholders may only appear after ``subsystem/``."""
    brace = name.find("{")
    slash = name.find("/")
    if slash < 0 or (0 <= brace < slash):
        return f"no literal 'subsystem/' prefix in {name!r}"
    subsystem = name[:slash]
    if subsystem not in ALLOWED_SUBSYSTEMS:
        return (f"unknown subsystem {subsystem!r} in {name!r} "
                f"(extend ALLOWED_SUBSYSTEMS if intentional)")
    if not NAME_RE.match(name):
        return f"bad characters in metric name {name!r}"
    return None


def test_registry_metric_names_follow_convention():
    offenders = []
    for path in _python_files():
        rel = os.path.relpath(path, REPO_ROOT)
        try:
            with open(path, encoding="utf-8") as f:
                src = f.read()
        except OSError:
            continue
        for pat in (CALL_RE, TRACER_COUNT_RE):
            for m in pat.finditer(src):
                err = _check_name(m.group(1))
                if err:
                    line = src.count("\n", 0, m.start()) + 1
                    offenders.append(f"{rel}:{line}: {err}")
    assert not offenders, (
        "registry metric names violating the subsystem/name convention "
        "(docs/telemetry.md):\n  " + "\n  ".join(offenders))


def test_lint_scans_telemetry_and_serving_sources():
    """The files that mint most metric names must be inside the walk —
    guards against a src-layout move silently dropping them."""
    scanned = {os.path.relpath(p, REPO_ROOT) for p in _python_files()}
    expected = {
        os.path.join("deepspeed_tpu", "telemetry", f)
        for f in ("tracer.py", "registry.py", "exposition.py",
                  # fleet telemetry plane (ISSUE 13): the federation layer
                  # mints the fleet/* rollup series
                  "fleet.py", "collector.py",
                  # numerics observatory (ISSUE 17): wire/serving fidelity
                  # + divergence series
                  "numerics.py",
                  # incident plane (ISSUE 20): the event stream mints the
                  # events/* series, the alert engine the alerts/* series
                  "events.py", "alerts.py")
    } | {
        os.path.join("deepspeed_tpu", "inference", f)
        for f in ("engine_v2.py", "lifecycle.py", "router.py",
                  # disagg serving (ISSUE 14): migration transport rides the
                  # serving metric families minted in router/lifecycle
                  "migrate.py")
    } | {
        # cross-process serving fabric (ISSUE 18): the remote proxy and the
        # daemon mint the fabric/* RPC + liveness series
        os.path.join("deepspeed_tpu", "fabric", f)
        for f in ("remote.py", "replica_daemon.py")
    } | {os.path.join("tools", "alerts_smoke.py"),
         os.path.join("tools", "fabric_smoke.py"),
         os.path.join("tools", "incident_report.py"),
         os.path.join("tools", "fleet_smoke.py"),
         os.path.join("tools", "numerics_smoke.py"),
         os.path.join("tools", "trace_merge.py")}
    missing = expected - scanned
    assert not missing, f"metric-minting files escaped the lint walk: {sorted(missing)}"


def test_known_names_pass_and_bad_names_fail():
    """The checker itself: real names from the tree pass, malformed fail."""
    for good in ("serving/ttft_ms", "span/serve:dispatch", "comm/bytes",
                 "mem/device_bytes_in_use", "anomaly/step_straggler",
                 # quantized-serving capacity gauges (ISSUE 10)
                 "serving/kv_pool_dtype", "serving/kv_bytes_per_token",
                 "serving/kv_pool_utilization",
                 # serving-tier metrics (ISSUE 12)
                 "router/shed_requests", "router/replica_queue_depth",
                 "serving/prefix_hit_rate", "serving/spec_accept_rate",
                 "serving/readmit_wait_ms",
                 # fleet telemetry plane (ISSUE 13)
                 "fleet/goodput", "fleet/tokens_per_s", "fleet/step_rate_min",
                 "fleet/straggler", "fleet/clock_offset_s",
                 # disaggregated serving (ISSUE 14)
                 "serving/migration_ms", "serving/migrated_blocks",
                 "serving/migration_failures", "router/migrations",
                 "fleet/role_processes",
                 # MoE at scale (ISSUE 15): capacity autotuning gauges next
                 # to the PR-7 dispatch-health family
                 "moe/capacity_factor_applied", "moe/capacity_factor_target",
                 "moe/token_drop_rate",
                 # numerics observatory (ISSUE 17): residual/serving fidelity,
                 # the divergence sentinel, and the fleet digest comparator
                 "numerics/ef_residual_norm", "numerics/divergence_events",
                 "numerics/digest_checksum", "numerics/digest_gap",
                 "numerics/kv_dequant_rel_err", "numerics/woq_matmul_rel_err",
                 "numerics/spec_accept_alarm",
                 # cross-process serving fabric (ISSUE 18): remote-replica
                 # RPC/liveness series and the router's roster-change events
                 "fabric/rpcs", "fabric/rpc_ms", "fabric/heartbeat_misses",
                 "fabric/dead_replicas", "fabric/wire_migration_ms",
                 "fabric/wire_bytes", "fabric/drains", "fabric/preempts",
                 "router/dead_replicas", "router/drains",
                 # incident plane (ISSUE 20): event-stream accounting, alert
                 # engine state, and the per-endpoint fabric RPC series
                 "events/emitted", "events/deduped", "events/buffered",
                 "events/subscriber_failures",
                 "alerts/firing", "alerts/fired", "alerts/resolved",
                 "alerts/suppressed", "alerts/evaluations",
                 "alerts/rule_errors", "alerts/sink_failures",
                 "fabric/rpc_failures", "fabric/rpc_server_ms",
                 "fabric/rpc_server_failures"):
        assert _check_name(good) is None, good
    for bad in ("ttft", "Serving/ttft", "serving ttft", "{x}/y", "bogus/name"):
        assert _check_name(bad) is not None, bad
