"""Perf ledger: schema round-trip + lossless migration of every legacy
artifact family.

The migration tests pin exact row counts AND exact values per family so a
loader regression (a renamed metric path, a dropped sub-tree, a direction
flip) cannot land silently — the committed ``perf/ledger/*.jsonl`` files
are generated by this same code path.
"""

import json
import os

import pytest

from deepspeed_tpu.telemetry import perfmigrate
from deepspeed_tpu.telemetry.perfledger import (
    PerfLedger,
    SCHEMA_VERSION,
    make_row,
    row_identity,
    row_key,
    validate_row,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ----------------------------------------------------------------- schema
def test_make_row_round_trip(tmp_path):
    led = PerfLedger(str(tmp_path))
    row = make_row("perf", "probe/tokens_per_sec", 123.5, "tokens/s",
                   direction="higher", method="worst-of-three", samples=3,
                   backend="cpu", round=16)
    assert row["schema"] == SCHEMA_VERSION
    assert row["run_id"]  # ProcessIdentity stamp
    assert row["round"] == 16
    assert led.append([row]) == 1
    (back,) = led.rows()
    assert back == row
    assert row_key(back) == ("cpu", "perf", "probe/tokens_per_sec")
    # append is append-only: a second append lands a second line
    led.append([row])
    assert len(led.rows("perf")) == 2
    assert led.path_for("perf").endswith("perf.jsonl")


def test_validate_rejects_bad_rows():
    good = make_row("perf", "m", 1.0, "x", backend="cpu")
    validate_row(dict(good))
    with pytest.raises(ValueError, match="direction"):
        validate_row(dict(good, direction="up"))
    with pytest.raises(ValueError, match="not numeric"):
        validate_row(dict(good, value="fast"))
    with pytest.raises(ValueError, match="not numeric"):
        validate_row(dict(good, value=True))
    with pytest.raises(ValueError, match="suite"):
        validate_row(dict(good, suite="Bad Suite!"))
    with pytest.raises(ValueError, match="missing field"):
        bad = dict(good)
        del bad["method"]
        validate_row(bad)
    with pytest.raises(ValueError, match="schema"):
        validate_row(dict(good, schema=99))


def test_corrupt_ledger_fails_loudly(tmp_path):
    led = PerfLedger(str(tmp_path))
    led.append([make_row("perf", "m", 1.0, "x", backend="cpu")])
    with open(led.path_for("perf"), "a", encoding="utf-8") as f:
        f.write("{not json\n")
    with pytest.raises(ValueError, match="unparseable"):
        led.rows()


def test_history_sorts_and_respects_before_round(tmp_path):
    led = PerfLedger(str(tmp_path))
    for rnd, val in ((3, 30.0), (1, 10.0), (2, 20.0)):
        led.append([make_row("perf", "m", val, "x", backend="cpu", round=rnd)])
    # a different backend must never appear in the key's history
    led.append([make_row("perf", "m", 99.0, "x", backend="tpu-v5e", round=1)])
    hist = led.history("cpu", "perf", "m")
    assert [r["value"] for r in hist] == [10.0, 20.0, 30.0]
    hist = led.history("cpu", "perf", "m", before_round=3)
    assert [r["value"] for r in hist] == [10.0, 20.0]


# -------------------------------------------------------------- migration
#: pinned per-family row counts — changing a loader changes these on purpose
EXPECTED_COUNTS = {
    "bench": 6,       # BENCH_r03..r05: headline + vs_baseline each
    "multichip": 5,   # MULTICHIP_r01..r05: n_devices each
    "serving": 376,   # SERVING_r06/07/10/12/14 full metric trees
    "coll": 12,       # COLL_r11 worst-of-three + runs + probe table
    "fleet": 11,      # FLEET_r13
    "compile": 26,    # COMPILE_r09 three paired runs
    "elastic": 20,    # ELASTIC_r08
    "moe": 8,         # MOE_r15 ep_tp losses + flags
}


@pytest.fixture(scope="module")
def migrated(tmp_path_factory):
    led = PerfLedger(str(tmp_path_factory.mktemp("ledger")))
    stats = perfmigrate.migrate(REPO_ROOT, led)
    return led, stats


def test_migrate_family_counts(migrated):
    led, stats = migrated
    counts = {}
    for r in led.rows():
        counts[r["suite"]] = counts.get(r["suite"], 0) + 1
    assert counts == EXPECTED_COUNTS
    assert stats["found"] == sum(EXPECTED_COUNTS.values())
    assert stats["appended"] == stats["found"]


def _one(rows, suite, round, metric):
    hits = [r for r in rows if r["suite"] == suite
            and int(r["round"]) == round and r["metric"] == metric]
    assert len(hits) == 1, f"{suite} r{round} {metric}: {len(hits)} rows"
    return hits[0]


def test_migrate_pinned_values(migrated):
    led, _ = migrated
    rows = led.rows()

    r = _one(rows, "bench", 5, "tokens_per_sec_cpu_smoke_seq128")
    assert r["value"] == 8602.1
    assert (r["direction"], r["unit"]) == ("higher", "tokens/s/chip")

    r = _one(rows, "bench", 3, "tokens_per_sec_cpu_smoke_seq128")
    assert r["value"] == 11710.5

    r = _one(rows, "coll", 11, "overhead_pct")
    assert r["value"] == 1.53
    assert (r["method"], r["samples"], r["direction"]) == ("worst-of-three", 3, "lower")

    r = _one(rows, "compile", 9, "overhead_pct_max")
    assert (r["value"], r["method"]) == (1.03, "paired")

    r = _one(rows, "serving", 14, "disagg/disagg_1p_1d/tpot_ms/p99")
    assert (r["value"], r["method"], r["direction"]) == (86.672, "p99", "lower")

    r = _one(rows, "serving", 12, "end_to_end/chained/tokens_per_sec")
    assert r["value"] == 3278.5

    r = _one(rows, "moe", 15, "moe_smoke/ep_tp_losses/0")
    assert r["value"] == 6.0539

    r = _one(rows, "multichip", 1, "n_devices")
    assert r["value"] == 8.0


def test_migrate_legacy_stamps_deterministic(migrated):
    """Re-migrating from any checkout must produce identical rows: legacy
    rows carry fixed stamps, not this process's identity."""
    led, _ = migrated
    for r in led.rows():
        assert r["run_id"] == perfmigrate.LEGACY_RUN_ID
        assert r["backend"] == perfmigrate.LEGACY_BACKEND
        assert r["git_sha"] == ""
        assert r["time_unix"] == 0.0


def test_migrate_idempotent_and_check_subset(migrated, tmp_path):
    led, _ = migrated
    again = perfmigrate.migrate(REPO_ROOT, led)
    assert again["appended"] == 0
    assert perfmigrate.check(REPO_ROOT, led) == []
    # live rows appended on top never break the subset check
    led.append([make_row("perf", "live_probe", 1.0, "x", backend="cpu")])
    assert perfmigrate.check(REPO_ROOT, led) == []


def test_check_reports_missing_rows(tmp_path):
    led = PerfLedger(str(tmp_path))  # empty: everything is missing
    missing = perfmigrate.check(REPO_ROOT, led)
    assert len(missing) == sum(EXPECTED_COUNTS.values())


def test_committed_ledger_contains_legacy_rows():
    """The repo's own perf/ledger must be migration-complete at HEAD —
    the same invariant the nightly's ``perf_ledger.py migrate --check``
    stage enforces."""
    led = PerfLedger(os.path.join(REPO_ROOT, "perf", "ledger"))
    assert perfmigrate.check(REPO_ROOT, led) == []


def test_row_identity_ignores_volatile_stamps():
    a = make_row("perf", "m", 1.0, "x", backend="cpu", round=1,
                 run_id="r", time_unix=1.0, git_sha="aaa")
    b = make_row("perf", "m", 1.0, "x", backend="cpu", round=1,
                 run_id="r", time_unix=2.0, git_sha="bbb")
    assert row_identity(a) == row_identity(b)


def test_direction_and_unit_heuristics():
    assert perfmigrate.direction_for("tokens_per_sec_x") == "higher"
    assert perfmigrate.direction_for("p2p_latency_ms") == "lower"
    assert perfmigrate.direction_for("telemetry_overhead_pct") == "lower"
    # _HIGHER markers win over a lower-looking suffix
    assert perfmigrate.direction_for("decode_tpot_p99_improvement") == "higher"
    assert perfmigrate.unit_for("busbw_gbps") == "GB/s"


def test_flatten_numeric_skips_non_numbers():
    flat = dict(perfmigrate.flatten_numeric(
        {"a": {"b": 1.5, "skip": "str", "flag": True, "none": None},
         "runs": [0.1, 0.2]}))
    assert flat == {"a/b": 1.5, "runs/0": 0.1, "runs/1": 0.2}
