"""Tiny cells for the CPU: the published Pythia files with every size cut, so
that each runner goes end to end in seconds. Nothing here describes a chip."""

import json
import os
import shutil

import pytest

from benchmarks.lib import harness, peaks

TINY_MODEL = dict(hidden_size=64, intermediate_size=256, num_hidden_layers=2,
                  num_attention_heads=4, vocab_size=512, max_position_embeddings=256)


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
    cfg.update(TINY_MODEL)
    return cfg


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


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)
