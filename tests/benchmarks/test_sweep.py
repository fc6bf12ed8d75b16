"""The rate sweep at a tiny size on the CPU: one row a rate, then the knee."""

import json

from benchmarks import sweep
from benchmarks.lib import harness
from tests.benchmarks.conftest import tiny_serve_workload


def test_sweep_prints_a_row_per_rate_and_a_knee(cpu_counts_as_chip, tiny_config, monkeypatch, capsys):
    workload = tiny_serve_workload("chat")
    monkeypatch.setattr(harness, "load_workload", lambda name: workload)
    monkeypatch.setattr(harness, "load_config", lambda name: tiny_config)
    assert sweep.main(["--workload", "tiny.serve.chat", "--rates", "5,20", "--seconds", "1.5"]) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert [r["rate_per_s"] for r in rows[:2]] == [5.0, 20.0]
    assert [r["offered"] for r in rows[:2]] == [8, 30]
    for r in rows[:2]:
        assert 0 < r["done_in_window_share"] <= 1.0 and r["compiles_in_window"] == 0
        assert r["ttft_p95_ms"] >= r["ttft_p50_ms"] > 0 and r["tpot_p95_ms"] > 0
    assert rows[2]["knee_rate_per_s"] in (5.0, 20.0, None)


def test_queue_depth_is_a_time_average():
    rows = [{"due_s": 0.0, "queue_wait_s": 2.0}, {"due_s": 1.0, "queue_wait_s": None},
            {"due_s": 5.0, "queue_wait_s": 0.1}]
    # over [0, 4]: the first waits 2 s, the second (never admitted) 3 s
    assert sweep.queue_depth(rows, 0.0, 4.0) == (2.0 + 3.0) / 4.0
    assert abs(sweep.queue_depth(rows, 5.0, 6.0) - 1.1) < 1e-9
