"""Adding a cell, a configuration or a per-layer metric is adding files: a
throw-away cell and metric in a copy of benchmarks/, no file there edited."""

import filecmp
import os
import time

import jax

from benchmarks.lib import harness, xplane
from tests.benchmarks.conftest import TINY_MODEL, tiny_train_workload, write_json

NEW_READER = '''
def read(run, trace):
    return len(run["step_s"]) if trace.n_devices else None
'''


def add_to_benchmark(bench_copy, cell, config, metric):
    """Entries added to the copy's BENCHMARK.json, none there changed but the
    lists of cells that the metrics already there are reported in."""
    path = os.path.join(os.path.dirname(bench_copy), "BENCHMARK.json")
    bench = harness.load_json(path)
    bench["configs"].append({"name": config, "source": "test", "reduced": [], "why": "test",
                             "file": f"benchmarks/configs/{config}.json"})
    bench["workloads"].append({"name": cell, "config": config, "chips": 1, "why": "test",
                               "traffic": cell.partition(".")[2]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "pythia-410m.train.seq2048" in m.get("workloads", []):
            m["workloads"].append(cell)
    bench["per_layer"].append({"name": metric, "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "engine",
                               "moves": "train_tokens_per_s_chip", "workloads": [cell]})
    write_json(path, bench)
    return bench


def test_a_new_cell_config_and_metric_are_found_by_name(cpu_counts_as_chip, bench_copy):
    config = dict(harness.load_config("pythia-410m"), name="tiny", **TINY_MODEL)
    write_json(os.path.join(bench_copy, "configs", "tiny.json"), config)
    workload = dict(tiny_train_workload(), name="tiny.train.extra", config="tiny")
    write_json(os.path.join(bench_copy, "workloads", "tiny.train.extra.json"), workload)
    with open(os.path.join(bench_copy, "metrics", "losses_seen.py"), "w") as f:
        f.write(NEW_READER)
    add_to_benchmark(bench_copy, "tiny.train.extra", "tiny", "losses_seen.train")

    held = harness.load_workload("tiny.train.extra", bench_copy)
    cfg = harness.load_config(held["config"], bench_copy)
    runner = harness.load_runner(held["kind"], bench_copy)
    run = runner.run(workload=held, config=cfg,
                     reference=harness.load_reference(cfg["architecture"], bench_copy),
                     seed=3, seconds=1.0, devices=jax.devices()[:1], trace_dir=None,
                     compiles=harness.CompileCounter(), t_process_start=time.perf_counter())
    run.update(workload=held, config=cfg, device_kind=jax.devices()[0].device_kind)
    assert run["correct"]

    trace = xplane.Reduced(n_devices=1, window_s=2.0, busy_s=1.5, modules={}, ops=[],
                           collective_s=0.0, collective_exposed_s=0.0, collective_by_kind={},
                           idle_gaps=[])
    bench = harness.load_benchmark(bench_copy)
    assert [m["name"] for m in harness.cell_metrics(bench, "end_to_end", "tiny.train.extra")] == [
        "train_tokens_per_s_chip", "setup_s"]
    got = harness.read_metrics(harness.cell_metrics(bench, "per_layer", "tiny.train.extra"),
                               run, trace, bench_copy)
    assert got["losses_seen.train"] == {"value": float(run["attempted"]), "unit": "count"}
    assert got["idle_share.train"]["value"] == 25.0  # a metric already there, now here too
    assert got["step_ms.train"]["value"] > 0 and "flash_roofline.train" not in got  # nothing to read
    assert not any(".chat" in name or ".batch" in name for name in got)

    same = filecmp.dircmp(harness.BENCH_DIR, bench_copy, ignore=["__pycache__"])
    assert not same.diff_files and not same.left_only
    assert same.subdirs["metrics"].right_only == ["losses_seen.py"]
    assert same.subdirs["workloads"].right_only == ["tiny.train.extra.json"]


def test_a_metric_without_a_reader_is_an_error(bench_copy):
    import pytest

    assert callable(harness.load_reader("idle_share.any-suffix", bench_copy))
    with pytest.raises(FileNotFoundError, match="no reader"):
        harness.load_reader("never_written.train", bench_copy)
