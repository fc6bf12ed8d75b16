"""Tiny cells for the CPU: the published Pythia files with every size cut, so
that each runner goes end to end in seconds. Nothing here describes a chip."""

import gzip
import json
import os
import re
import shutil

import pytest

from benchmarks.lib import harness, peaks

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# what an architecture file gives the runners and the readers
ARCHITECTURE_FILE = ("WIDTH_KEYS", "reference_weights", "matmul_params", "total_params", "layers",
                     "heads", "kv_heads", "head_dim")
TINY_MODEL = dict(hidden_size=64, intermediate_size=256, num_hidden_layers=2,
                  num_attention_heads=4, vocab_size=512, max_position_embeddings=256)
# the tiny configuration's own tolerances, for the CPU: Pythia's on the chip,
# which its bf16 error at two layers and hidden 64 stays well inside
TINY_CHECK = {"logit_rel_tol": 0.010, "loss_rel_tol": 2e-4,
              "why": "tests/benchmarks: a tiny gpt_neox on the CPU"}


@pytest.fixture
def cpu_counts_as_chip(monkeypatch):
    """Steer the device check (and the peaks table) so a runner takes the CPU."""
    import jax

    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(peaks.DEVICE_PEAKS, kind, peaks.DEVICE_PEAKS["TPU v5 lite"])
    monkeypatch.setattr(harness, "require_devices", lambda chips: jax.devices()[:chips])


@pytest.fixture
def tiny_config():
    cfg = harness.load_config("pythia-410m")
    cfg.update(TINY_MODEL, check=TINY_CHECK)
    return cfg


def run_cell(workload, config, seed=2**31 + 5, seconds=1.5, bench_dir=harness.BENCH_DIR):
    """One run of a cell's runner, its files found by name under ``bench_dir``
    as ``run.py`` finds them, with what ``run.py`` adds for the readers."""
    import time

    runner = harness.load_runner(workload["kind"], bench_dir)
    architecture = harness.load_architecture(config["architecture"], bench_dir)
    devices = harness.require_devices(workload["chips"])
    run = runner.run(workload=workload, config=config,
                     reference=harness.load_reference(config["architecture"], bench_dir),
                     architecture=architecture, seed=seed, seconds=seconds, devices=devices,
                     trace_dir=None, compiles=harness.CompileCounter(),
                     t_process_start=time.perf_counter())
    run.update(workload=workload, config=config, architecture=architecture,
               device_kind=devices[0].device_kind)
    return run


def tiny_train_workload(chips=1, mesh=None):
    wl = harness.load_workload("pythia-410m.train.seq2048")
    wl["chips"] = chips
    wl["traffic"].update(sequences=4, seq_len=64)
    wl["engine"].update(train_micro_batch_size_per_gpu=2 if chips == 1 else 1,
                        gradient_accumulation_steps=2 if chips == 1 else 1,
                        mesh=mesh or {"dp": 1})
    return wl


def tiny_serve_workload(kind):
    wl = harness.load_workload("pythia-1.4b.serve.batch")
    wl["engine"].update(kv_pool_bytes=None, num_kv_blocks=256, max_seqs=8,
                        chunk_bucket=32, row_bucket=4)
    if kind == "batch":
        wl["traffic"].update(wave=8, prompt_len={"dist": "uniform", "min": 8, "max": 32},
                             output_tokens=12)
        wl["warm"] = {"prefill": [], "chain_rows": [8], "chain_prompt_len": 32}
    else:
        wl["traffic"] = {"kind": "open_loop", "rate_per_s": 10,
                         "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                                        "min": 4, "max": 64},
                         "output_tokens": 12}
        wl["warm"] = {"prefill": [[4, 64], [8, 32], [8, 64]], "chain_rows": [4, 8],
                      "chain_prompt_len": 32}
    return wl


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of BENCHMARK.json and benchmarks/ that a test may add to."""
    dst = tmp_path / "benchmarks"
    shutil.copytree(harness.BENCH_DIR, dst, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(os.path.dirname(harness.BENCH_DIR), "BENCHMARK.json"), tmp_path)
    return str(dst)


def unpack_span_trace(directory) -> str:
    """The v5e trace ``record_span_trace.py`` took, unpacked into ``directory``."""
    path = os.path.join(str(directory), "v5e_1chip_spans.xplane.pb")
    packed = os.path.join(os.path.dirname(__file__), "data", "v5e_1chip_spans.xplane.pb.gz")
    with gzip.open(packed, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def config_rules(entry, held, bench, bench_dir=harness.BENCH_DIR):
    """What holds for a configuration of ANY architecture: its entry in
    ``BENCHMARK.json`` against its file and the files that file names."""
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmarks/configs/{entry['name']}.json"
    assert held["name"] == entry["name"] and held["source"] == entry["source"]
    # the file says of every cut: key, published value, value used; the entry lists the keys
    assert [set(r) for r in held["reduced"]] == [{"key", "published", "used"}] * len(entry["reduced"])
    assert [r["key"] for r in held["reduced"]] == entry["reduced"]
    architecture = harness.load_architecture(held["architecture"], bench_dir)
    assert all(hasattr(architecture, name) for name in ARCHITECTURE_FILE)
    for cut in held["reduced"]:
        assert NAME.match(cut["key"]) and cut["key"] not in architecture.WIDTH_KEYS  # a width is never cut
        assert held[cut["key"]] == cut["used"] != cut["published"]
    assert all(k in held for k in architecture.WIDTH_KEYS)
    assert held["reference"] == f"benchmarks/reference/{held['architecture']}.py"
    assert os.path.isfile(os.path.join(bench_dir, "reference", held["architecture"] + ".py"))
    assert isinstance(held["check"]["why"], str) and len(held["check"]) >= 2
    assert all(0 < v < 0.1 for k, v in held["check"].items() if k != "why")
    assert any(w["config"] == entry["name"] for w in bench["workloads"])
