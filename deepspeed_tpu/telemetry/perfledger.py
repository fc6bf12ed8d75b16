"""Unified performance ledger: one versioned row schema for every number.

Fifteen PRs produced ~20 ad-hoc root-level perf artifacts (``BENCH_rNN``,
``SERVING_rNN``, ``COLL_r11``, ``FLEET_r13``, ...) with incompatible schemas
and a hand-written PERF.md — the bench trajectory was not machine-readable,
so nothing would have caught a silent 2x serving regression between rounds.
This module is the landing pad that fixes it:

  - **Row schema (v1).** Every measurement is one flat JSON object::

        {schema, run_id, git_sha, round, backend, suite, metric, value,
         unit, direction, method, samples[, proc, time_unix]}

    ``backend`` is the accelerator the number was measured on (``cpu`` /
    ``interpret`` / a stamp derived from jax's ``device_kind`` such as
    ``tpu-v5-lite``) — the gate NEVER compares across backends.
    ``direction`` says which way is better (``higher`` / ``lower``);
    ``method`` names the measurement discipline (``worst-of-three``,
    ``paired``, ``p99``, ``single``); ``round`` is the PR round the row
    belongs to (0 = unversioned HEAD run).

  - **Append-only JSONL** under ``perf/ledger/<suite>.jsonl``. Rows are
    never rewritten; migration (``perfmigrate.py``) and live emitters
    (bench.py extras, ``tools/bench_serving.py``, ``comm/benchmark.py
    --sweep``) both append here, so the trajectory back to PR 4 and every
    later run land in ONE queryable place.

  - **Identity stamps.** :func:`make_row` stamps :class:`ProcessIdentity`
    (run_id + proc, PR 13) and the tree's git sha onto every fresh row, so
    a number can always be joined back to the process and tree that
    produced it.

Consumers: ``telemetry/perfgate.py`` (noise-aware regression gate),
``tools/perf_report.py`` (PERF.md round tables + trajectory curves),
``profiling/attribution.py`` (step-time decomposition context). See
docs/telemetry.md "Performance ledger & attribution".
"""

from __future__ import annotations

import json
import os
import subprocess
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

SCHEMA_VERSION = 1

DIRECTIONS = ("higher", "lower")

# canonical measurement disciplines (method is free-form; these are the
# spellings the repo's own emitters use)
METHODS = ("single", "paired", "worst-of-three", "p50", "p95", "p99")

REQUIRED_FIELDS = (
    "schema", "run_id", "git_sha", "round", "backend", "suite", "metric",
    "value", "unit", "direction", "method", "samples",
)

_SUITE_OK = frozenset("abcdefghijklmnopqrstuvwxyz0123456789_-")


def default_ledger_root() -> str:
    """The ONE resolution of the ledger directory: ``$DSTPU_PERF_LEDGER_DIR``,
    else ``<repo>/perf/ledger`` (the repo root is the parent of the
    ``deepspeed_tpu`` package — this checkout's layout; installed trees set
    the env var)."""
    env = os.environ.get("DSTPU_PERF_LEDGER_DIR")
    if env:
        return env
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(repo, "perf", "ledger")


_git_sha_cache: Optional[str] = None


def resolve_git_sha() -> str:
    """Tree identity stamp: ``$DSTPU_GIT_SHA``, else ``git rev-parse --short
    HEAD`` of the repo this package lives in (cached; "" when unavailable —
    a missing stamp must never block a measurement)."""
    global _git_sha_cache
    env = os.environ.get("DSTPU_GIT_SHA")
    if env is not None:
        return env
    if _git_sha_cache is None:
        repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        try:
            _git_sha_cache = subprocess.run(
                ["git", "-C", repo, "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, timeout=10,
            ).stdout.strip()
        except (OSError, subprocess.SubprocessError):
            _git_sha_cache = ""
    return _git_sha_cache


def backend_stamp(device_kind: str) -> str:
    """Ledger stamp of an accelerator, derived from the ``device_kind`` jax
    reports: ``"TPU v5 lite"`` -> ``"tpu-v5-lite"``."""
    return "-".join(device_kind.lower().split())


def default_backend() -> str:
    """The accelerator stamp for rows measured in THIS process:
    ``$DSTPU_PERF_BACKEND`` (interpret parity runs export ``interpret``),
    else ``cpu`` on the CPU platform and :func:`backend_stamp` of the first
    device's ``device_kind`` anywhere else — derived, never assumed.
    Emitters that KNOW they ran under the Pallas interpreter pass
    ``backend="interpret"`` explicitly — the env/jax resolution cannot see
    inside a kernel."""
    env = os.environ.get("DSTPU_PERF_BACKEND")
    if env:
        return env
    import jax

    dev = jax.devices()[0]
    return "cpu" if dev.platform == "cpu" else backend_stamp(dev.device_kind)


def default_round() -> int:
    """The PR round fresh rows belong to: ``$DSTPU_PERF_ROUND`` (the nightly
    exports ``rNN``'s NN), else 0 — "unversioned HEAD run"."""
    env = os.environ.get("DSTPU_PERF_ROUND", "")
    digits = "".join(c for c in env if c.isdigit())
    try:
        return int(digits) if digits else 0
    except ValueError:
        return 0


def make_row(suite: str, metric: str, value: float, unit: str,
             direction: str = "higher", method: str = "single",
             samples: int = 1, backend: Optional[str] = None,
             round: Optional[int] = None, run_id: Optional[str] = None,
             git_sha: Optional[str] = None,
             time_unix: Optional[float] = None) -> Dict[str, Any]:
    """One schema-v1 row, identity-stamped from the process defaults.
    Everything the caller omits resolves here (ProcessIdentity run_id/proc,
    git sha, backend, round) so emitters stay one-liners."""
    if run_id is None or time_unix is None:
        from deepspeed_tpu.telemetry.fleet import get_identity

        ident = get_identity()
        run_id = run_id if run_id is not None else ident.run_id
        proc = ident.proc
    else:
        proc = None
    row: Dict[str, Any] = {
        "schema": SCHEMA_VERSION,
        "run_id": run_id,
        "git_sha": git_sha if git_sha is not None else resolve_git_sha(),
        "round": int(round) if round is not None else default_round(),
        "backend": backend if backend is not None else default_backend(),
        "suite": suite,
        "metric": metric,
        "value": float(value),
        "unit": unit,
        "direction": direction,
        "method": method,
        "samples": int(samples),
        "time_unix": (round_time(time_unix) if time_unix is not None
                      else round_time(time.time())),
    }
    if proc:
        row["proc"] = proc
    validate_row(row)
    return row


def round_time(t: float) -> float:
    return round(float(t), 3)


def validate_row(row: Dict[str, Any]) -> Dict[str, Any]:
    """Schema check — raises ``ValueError`` with the offending field.
    Direction is a closed enum (the gate's comparisons depend on it);
    backend/method are open sets with canonical spellings."""
    for f in REQUIRED_FIELDS:
        if f not in row:
            raise ValueError(f"ledger row missing field {f!r}: {row!r}")
    if int(row["schema"]) != SCHEMA_VERSION:
        raise ValueError(
            f"ledger row schema {row['schema']!r} != {SCHEMA_VERSION} "
            f"(metric {row.get('metric')!r})")
    if row["direction"] not in DIRECTIONS:
        raise ValueError(
            f"ledger row direction {row['direction']!r} not in {DIRECTIONS}")
    if not isinstance(row["value"], (int, float)) or isinstance(row["value"], bool):
        raise ValueError(f"ledger row value not numeric: {row!r}")
    if not row["suite"] or set(str(row["suite"])) - _SUITE_OK:
        raise ValueError(f"ledger row suite {row['suite']!r} not a file-safe slug")
    return row


def row_key(row: Dict[str, Any]) -> Tuple[str, str, str]:
    """The history key the gate compares within: (backend, suite, metric).
    Backends never mix — a cpu row must never gate a tpu row."""
    return (str(row["backend"]), str(row["suite"]), str(row["metric"]))


def row_identity(row: Dict[str, Any]) -> Tuple:
    """Dedupe identity for idempotent migration: everything measurement-
    defining, nothing stamp-volatile (time_unix/proc/git_sha excluded —
    re-migrating the same artifact from a different checkout must produce
    the same identity)."""
    return (row["suite"], int(row["round"]), row["backend"], row["metric"],
            float(row["value"]), row["method"], int(row["samples"]),
            row["run_id"])


class PerfLedger:
    """Append-only JSONL ledger under one directory, one file per suite.

    Append never rewrites: a row, once written, is history. Thread-safe
    appends (one lock; emitters may append from bench worker threads).
    Loading tolerates an empty/missing directory (fresh checkout before
    migration) but NOT malformed rows — a corrupt ledger must fail loudly,
    not silently shrink the gate's history.
    """

    def __init__(self, root: Optional[str] = None):
        self.root = root or default_ledger_root()
        self._lock = threading.Lock()

    def path_for(self, suite: str) -> str:
        return os.path.join(self.root, f"{suite}.jsonl")

    def suites(self) -> List[str]:
        try:
            names = sorted(os.listdir(self.root))
        except OSError:
            return []
        return [n[:-len(".jsonl")] for n in names if n.endswith(".jsonl")]

    # ------------------------------------------------------------- writing
    def append(self, rows: Iterable[Dict[str, Any]]) -> int:
        """Validate + append rows, grouped into their suite files. Returns
        the number written. Partial-failure honest: validation runs on ALL
        rows before the first byte is written."""
        by_suite: Dict[str, List[str]] = {}
        n = 0
        for row in rows:
            validate_row(row)
            by_suite.setdefault(str(row["suite"]), []).append(
                json.dumps(row, sort_keys=True))
            n += 1
        if not n:
            return 0
        with self._lock:
            os.makedirs(self.root, exist_ok=True)
            for suite, lines in by_suite.items():
                with open(self.path_for(suite), "a", encoding="utf-8") as f:
                    f.write("\n".join(lines) + "\n")
        return n

    # ------------------------------------------------------------- reading
    def rows(self, suite: Optional[str] = None) -> List[Dict[str, Any]]:
        suites = [suite] if suite is not None else self.suites()
        out: List[Dict[str, Any]] = []
        for s in suites:
            path = self.path_for(s)
            try:
                with open(path, encoding="utf-8") as f:
                    text = f.read()
            except OSError:
                continue
            for i, line in enumerate(text.splitlines()):
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except ValueError as e:
                    raise ValueError(f"{path}:{i + 1}: unparseable ledger row: {e}")
                validate_row(row)
                out.append(row)
        return out

    def identities(self) -> set:
        return {row_identity(r) for r in self.rows()}

    def history(self, backend: str, suite: str, metric: str,
                before_round: Optional[int] = None) -> List[Dict[str, Any]]:
        """Rows for one (backend, suite, metric) key, oldest round first.
        ``before_round`` drops rows of that round and later — the gate
        compares a round's rows only against STRICTLY older history."""
        key = (backend, suite, metric)
        rows = [r for r in self.rows(suite) if row_key(r) == key]
        if before_round is not None:
            rows = [r for r in rows if int(r["round"]) < before_round]
        return sorted(rows, key=lambda r: (int(r["round"]),
                                           float(r.get("time_unix", 0.0))))
