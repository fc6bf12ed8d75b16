"""The EVA cell's check at a toy size on the CPU: a sound program is correct,
and the planted faults of ``tools/eva_controls.py`` that concern EVA attention
itself (mean pooling, ``mu`` dropped, an open window's own chunks visible) each
read ``correct: false`` through the runner's own comparison."""

import importlib.util
import os

import pytest

from benchmarks.lib import harness
from tests.benchmarks.conftest import run_cell

REPO = os.path.dirname(harness.BENCH_DIR)
TOY = dict(vocab_size=64, hidden_size=64, intermediate_size=160, num_hidden_layers=3, num_attention_heads=4,
           num_key_value_heads=4, max_position_embeddings=512, window_size=32, chunk_size=4)
# sound on the CPU over these seeds 0.0096-0.0151 (bf16 activations at hidden 64), the three faults 0.034 and more
TOY_CHECK = {"logit_rel_tol": 0.022, "why": "tests/benchmarks: a toy evabyte on the CPU"}


@pytest.fixture(scope="module")
def controls():
    spec = importlib.util.spec_from_file_location("eva_controls", os.path.join(REPO, "tools", "eva_controls.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def toy_cell():
    config = dict(harness.load_config("evabyte"), **TOY, check=TOY_CHECK)
    workload = harness.load_workload("evabyte.serve.long-batch")
    workload["engine"].update(kv_pool_bytes=None, num_kv_blocks=8 * 20, max_seqs=8, chunk_bucket=128,
                              row_bucket=4, max_ragged_batch_size=512, max_seq_len=256, kv_block_size=4)
    workload["traffic"].update(wave=8, prompt_len={"dist": "uniform", "min": 64, "max": 128}, output_tokens=24)
    workload["warm"] = {"prefill": [[4, 128]], "chain_rows": [8], "chain_prompt_len": 64}
    return workload, config


@pytest.mark.parametrize("control", [None, "mean_pooling", "no_mu", "own_chunks_visible"])
def test_the_check_tells_eva_attention_s_faults_from_a_sound_program(cpu_counts_as_chip, controls, monkeypatch,
                                                                   control):
    from deepspeed_tpu.ops import eva

    # what a control wraps is put back when the test ends
    monkeypatch.setattr(harness, "load_runner", harness.load_runner)
    monkeypatch.setattr(harness, "load_architecture", harness.load_architecture)
    monkeypatch.setattr(eva, "_summaries_seen", eva._summaries_seen)
    if control in controls.LEAF_CONTROLS:
        controls.plant_leaves(*controls.LEAF_CONTROLS[control])
    elif control:
        controls.plant_own_chunks()
    workload, config = toy_cell()
    run = run_cell(workload, config, seed=2**31 + 35, seconds=0.5)
    found, limit = run["compared"]["logit_rel_err"]
    print(control, found, limit)
    assert run["correct"] is (control is None), (control, found, limit)
    assert run["failed"] == 0 and run["compiles_in_window"] == 0
