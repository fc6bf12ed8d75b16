"""Adding a cell, a configuration, a per-layer metric or a whole second
architecture is adding files: throw-away ones in a copy of benchmarks/, no
file there edited."""

import filecmp
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import costs, harness, kernels, peaks, program, xplane
from tests.benchmarks.conftest import (ROUTED_TOY, TINY_CHECK, TINY_MODEL, add_routed_toy,
                                       add_to_benchmark, config_rules, routed_stand_in, run_cell,
                                       tiny_serve_workload, tiny_train_workload, unpack_span_trace,
                                       write_json)

DATA = os.path.join(os.path.dirname(__file__), "data")

NEW_READER = '''
def read(run, trace):
    return len(run["step_s"]) if trace.n_devices else None
'''


def test_a_new_cell_config_and_metric_are_found_by_name(cpu_counts_as_chip, bench_copy):
    config = dict(harness.load_config("pythia-410m"), name="tiny", check=TINY_CHECK, **TINY_MODEL)
    write_json(os.path.join(bench_copy, "configs", "tiny.json"), config)
    workload = dict(tiny_train_workload(), name="tiny.train.extra", config="tiny")
    write_json(os.path.join(bench_copy, "workloads", "tiny.train.extra.json"), workload)
    with open(os.path.join(bench_copy, "metrics", "losses_seen.py"), "w") as f:
        f.write(NEW_READER)
    add_to_benchmark(bench_copy, "tiny.train.extra", "tiny", "pythia-410m.train.seq2048",
                     "losses_seen.train")

    held = harness.load_workload("tiny.train.extra", bench_copy)
    cfg = harness.load_config(held["config"], bench_copy)
    run = run_cell(held, cfg, seed=3, seconds=1.0, bench_dir=bench_copy)
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


# --- a second architecture: other config keys, another parameter tree -----
#
# The nearest thing the program already maps to what comes next (OLMoE): the
# ``mixtral`` branch of ``config_from_hf``, here at 2 layers, hidden 64, 4
# heads over 2 KV heads, 4 experts at top-2. Its plain reference and its
# architecture file are fixtures under ``data/second_architecture/``.
SECOND = os.path.join(DATA, "second_architecture")
TOY_MOE = {
    "name": "toy-moe", "source": "tests/benchmarks/data/second_architecture",
    "architecture": "mixtral", "reference": "benchmarks/reference/mixtral.py",
    "deployment": "a CPU test", "reduced": [], "assumed": {"weights": "random, from --seed"},
    "check": {
        "logit_rel_tol": 0.025,
        "why": "bf16 against fp32 on the CPU at this size: 0.0053-0.0082 in six of eight seeds, 0.0105 "
               "and 0.0181 in two, where a rounding changed which experts a token visits (PERF.md, "
               "section 7): 1.4 x the largest. No loss_rel_tol: the program's training loss carries "
               "the router's auxiliary term, which the plain loss lacks, so no train cell"},
    "model_type": "mixtral", "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "max_position_embeddings": 256, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
    "num_local_experts": 4, "num_experts_per_tok": 2, "tie_word_embeddings": False,
    "router_aux_loss_coef": 0.02, "sliding_window": None,
}


@pytest.fixture
def second_architecture(bench_copy):
    """``benchmarks/`` with a second architecture added by new files alone."""
    for kind in ("architectures", "reference"):
        shutil.copy(os.path.join(SECOND, kind.rstrip("s") + ".py"),
                    os.path.join(bench_copy, kind, "mixtral.py"))
    write_json(os.path.join(bench_copy, "configs", "toy-moe.json"), TOY_MOE)
    workload = dict(tiny_serve_workload("batch"), name="toy-moe.serve.batch", config="toy-moe")
    write_json(os.path.join(bench_copy, "workloads", "toy-moe.serve.batch.json"), workload)
    add_to_benchmark(bench_copy, "toy-moe.serve.batch", "toy-moe", "pythia-1.4b.serve.batch",
                     source=TOY_MOE["source"])
    return bench_copy


def test_a_second_architecture_is_served_from_new_files_alone(cpu_counts_as_chip, second_architecture,
                                                              tmp_path, monkeypatch):
    bench_copy = second_architecture
    bench = harness.load_benchmark(bench_copy)
    held = harness.load_workload("toy-moe.serve.batch", bench_copy)
    cfg = harness.load_config(held["config"], bench_copy)
    config_rules(bench["configs"][-1], cfg, bench, bench_copy)
    model_cfg = program.model_config(cfg, jnp.bfloat16)  # keys no whitelist of gpt_neox's let through
    assert (model_cfg.num_experts, model_cfg.moe_top_k, model_cfg.kv_heads, model_cfg.norm,
            model_cfg.rope_theta) == (4, 2, 2, "rmsnorm", 1e6)

    run = run_cell(held, cfg, bench_dir=bench_copy)
    assert run["correct"] and run["failed"] == 0 and run["compiles_in_window"] == 0
    assert run["kv_pool_shape"] == (2 * 256, 16, 2 * 16)  # [layers x blocks, block, kvH x hd]

    # the per-layer readers, through the architecture file: the CPU gives the
    # profiler no device plane, so they read the v5e recording beside this file
    recorded = unpack_span_trace(tmp_path)
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: recorded)
    trace = xplane.reduce_trace(recorded)
    run["calls"] = [{"kind": "decode_chain", "traced": True, "rows": 8, "row_steps": 32,
                     "context_tokens": 32 * 30.0}]
    got = harness.read_metrics(harness.cell_metrics(bench, "per_layer", "toy-moe.serve.batch"),
                               run, trace, bench_copy)
    # 4 heads over 2 KV heads of 16, 2 layers: keys and values are read once a KV head
    bytes_ = 2 * 960 * 2 * 16 * 2 + 2 * 32 * 4 * 16 * 2
    least = 2 * bytes_ / peaks.device_peaks("TPU v5 lite").hbm_bytes_per_s
    assert got["paged_roofline.batch"]["value"] == pytest.approx(
        100 * least / kernels.paged_seconds(run, trace))
    assert costs.paged_decode_cost(960, 32, 4, 2, 16)[1] == bytes_
    # kv_write of the recording; nothing there has this engine's pool's shape
    assert got["pool_copy_time_share.batch"]["value"] == pytest.approx(7.76, rel=0.01)
    assert got["rows_per_chain.batch"]["value"] == 8.0 and not any(".train" in name for name in got)

    same = filecmp.dircmp(harness.BENCH_DIR, bench_copy, ignore=["__pycache__"])
    assert not same.diff_files and not same.left_only and not same.right_only
    added = {name: sub.right_only for name, sub in same.subdirs.items() if sub.right_only}
    assert added == {"architectures": ["mixtral.py"], "reference": ["mixtral.py"],
                     "configs": ["toy-moe.json"], "workloads": ["toy-moe.serve.batch.json"]}
    assert not any(sub.diff_files or sub.left_only for sub in same.subdirs.values())


def test_a_routed_architecture_is_checked_from_new_files_alone(bench_copy, capsys):
    """What the next ``model_config`` PR does for a routed model that serves:
    an architecture file WITH the routed members, a reference that takes the
    picks and audits them, a configuration with both tolerances, a cell, and
    entries appended to ``BENCHMARK.json``. The runner of the copy, found by
    name, decides ``correct`` at the picks; the program's half is the stand-in
    engine (the program cannot run this architecture yet: PERF.md, section 7)."""
    add_routed_toy(bench_copy)
    bench = harness.load_benchmark(bench_copy)
    held = harness.load_workload("routed-toy.serve.batch", bench_copy)
    cfg = harness.load_config(held["config"], bench_copy)
    assert cfg == ROUTED_TOY
    config_rules(bench["configs"][-1], cfg, bench, bench_copy)  # the routed rules among them
    architecture = harness.load_architecture(cfg["architecture"], bench_copy)
    reference = harness.load_reference(cfg["architecture"], bench_copy)
    assert program.routing(architecture, cfg)[:3] == (4, 32, 4)
    assert [m["name"] for m in harness.cell_metrics(bench, "end_to_end", held["name"])] == [
        "serve_out_tokens_per_s", "setup_s"]

    seed = 2**31 + 29
    ok, compared = harness.load_runner(held["kind"], bench_copy).check(
        routed_stand_in(seed), reference, architecture, cfg, seed)
    assert ok and compared["logit_rel_err"][0] <= cfg["check"]["logit_rel_tol"]
    assert 0 < compared["route_shortfall"][0] <= cfg["check"]["route_shortfall_tol"]
    said = capsys.readouterr().out
    assert all(word in said for word in ("check_logit_rel_err=", "check_route_shortfall=",
                                         "check_flip_share=", "ok=True"))

    same = filecmp.dircmp(harness.BENCH_DIR, bench_copy, ignore=["__pycache__"])
    assert not same.diff_files and not same.left_only and not same.right_only
    added = {name: sub.right_only for name, sub in same.subdirs.items() if sub.right_only}
    assert added == {"architectures": ["routed_toy.py"], "reference": ["routed_toy.py"],
                     "configs": ["routed-toy.json"], "workloads": ["routed-toy.serve.batch.json"]}
    assert not any(sub.diff_files or sub.left_only for sub in same.subdirs.values())


@pytest.fixture(scope="module")
def toy_moe_in_fp32():
    from deepspeed_tpu.models import CausalLM

    model_cfg = program.model_config(TOY_MOE, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, TOY_MOE["vocab_size"], (3, 48), dtype=np.int32)
    params = CausalLM(model_cfg).init({"params": jax.random.PRNGKey(1)},
                                      {"input_ids": jnp.asarray(tokens)}, train=False)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree_util.tree_unflatten(
        tree, [l + 0.05 * jax.random.normal(k, l.shape) for l, k in zip(leaves, keys)])
    loss, logits = CausalLM(model_cfg).apply({"params": params}, {"input_ids": jnp.asarray(tokens)})
    return params, jnp.asarray(tokens), float(loss), logits


def test_the_second_architecture_s_reference_agrees_with_the_programs_model(toy_moe_in_fp32):
    """fp32 on both sides: the fixture is a sound reference, and the program's
    training loss is the plain one PLUS the router's auxiliary term, which is
    why the second architecture brings no train cell."""
    params, tokens, program_loss, program_logits = toy_moe_in_fp32
    reference = harness._load_module(os.path.join(SECOND, "reference.py"), "second_reference")
    architecture = harness._load_module(os.path.join(SECOND, "architecture.py"), "second_architecture")
    weights = architecture.reference_weights(params)
    cfg = program.published(TOY_MOE)
    assert program.relative_error(reference.forward(weights, cfg, tokens), program_logits) < 1e-5
    plain = float(reference.loss(weights, cfg, tokens))
    aux = program_loss - plain
    # 0.0108 on a loss of 6.98: seven times the loss tolerance Pythia's cells hold
    assert 0.005 < aux < 0.05 and aux / plain > 5 * TINY_CHECK["loss_rel_tol"]
    src = open(os.path.join(SECOND, "reference.py")).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]


def test_the_second_architecture_s_file_counts_the_programs_parameters(toy_moe_in_fp32):
    params = toy_moe_in_fp32[0]
    architecture = harness._load_module(os.path.join(SECOND, "architecture.py"), "second_architecture")
    assert architecture.total_params(TOY_MOE) == sum(a.size for a in jax.tree_util.tree_leaves(params))
    # a token meets two of the four experts: half the expert weights are not in its products
    idle = TOY_MOE["num_hidden_layers"] * 2 * 3 * 64 * 128
    embedding = 512 * 64
    norms = 2 * 2 * 64 + 64
    assert architecture.matmul_params(TOY_MOE) == architecture.total_params(TOY_MOE) - idle - embedding - norms
    assert (architecture.heads(TOY_MOE), architecture.kv_heads(TOY_MOE), architecture.head_dim(TOY_MOE),
            architecture.layers(TOY_MOE)) == (4, 2, 16, 2)


def test_a_metric_without_a_reader_is_an_error(bench_copy):
    assert callable(harness.load_reader("idle_share.any-suffix", bench_copy))
    with pytest.raises(FileNotFoundError, match="no reader"):
        harness.load_reader("never_written.train", bench_copy)
