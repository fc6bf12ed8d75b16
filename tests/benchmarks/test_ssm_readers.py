"""PR 42's files: the ``granitemoehybrid`` configuration, its cell, its
architecture file's counts, ``ssm_decode_cost`` and ``ssd_scan_cost`` by hand,
and the readers ``ssm_time_share`` and ``ssm_decode_roofline``
(``benchmarks/lib/ssm.py``) on a synthetic trace whose numbers can be checked by
hand and on the recorded v5e trace of a program that has none of their names
(nothing found, nothing raised). The configuration's and the cell's facts are
held by MEMBERSHIP, never by position or count: the next appended cell breaks
nothing here."""

import types

import pytest

from benchmarks.lib import harness, program, scopes, spans, ssm, xplane
from tests.benchmarks.conftest import config_rules, unpack_span_trace

BENCH = harness.load_benchmark()
CONFIG, CELL = "granite-4.0-h-micro", "granite-4.0-h-micro.serve.long-output-batch"
NEW = ["ssm_time_share.batch", "ssm_decode_roofline.batch"]
SHARED = ["compiles_in_window.batch", "decode_chain_ms.batch", "hbm_live_peak_gib.batch",
          "hbm_reserved_peak_gib.batch", "idle_share.batch", "rows_per_chain.batch",
          "pool_copy_time_share.batch", "sched_host_ms.batch", "chain_live_rows.batch"]
HELD = harness.load_config(CONFIG)
CFG = program.published(HELD)
ARCH = harness.load_architecture("granitemoehybrid")


def test_the_configuration_is_the_catalog_s_whole():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    config_rules(entry, HELD, BENCH)
    assert entry["reduced"] == HELD["reduced"] == []
    assert HELD["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
    catalog = {
        "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid", "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "shared_intermediate_size": 8192, "tie_word_embeddings": True, "vocab_size": 100352}
    assert {k: CFG[k] for k in catalog} == catalog
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert CFG["layer_types"] == period * 4
    assert set(CFG) - set(catalog) == {"layer_types", "dtype"} and CFG["dtype"] == "bfloat16"
    for said in ("time_step_limit", "gate_before_norm", "conv_over_xBC", "in_proj_order", "state_dtype", "dtype",
                 "weights", "multipliers", "attention", "mlp"):
        assert len(HELD["assumed"][said]) > 40, said
    read = HELD["check"]["readings"]["logit_rel_tol"]
    assert read["sound_max"] < HELD["check"]["logit_rel_tol"] < read["control_min"]
    assert "deployment" in HELD and "64 state slots" in HELD["deployment"]


def test_the_cell_is_issue_42_s():
    cell = harness.load_workload(CELL)
    assert cell["config"] == CONFIG and cell["kind"] == "serve" and cell["chips"] == 1
    assert CELL in [w["name"] for w in BENCH["workloads"]]
    assert cell["traffic"] == {"kind": "closed_waves", "wave": 64,
                               "prompt_len": {"dist": "uniform", "min": 64, "max": 256}, "output_tokens": 512}
    engine = cell["engine"]
    assert {k: engine[k] for k in ("dtype", "kv_cache_dtype", "max_seqs", "decode_chain", "kv_block_size",
                                   "row_bucket", "chunk_bucket", "kv_pool_bytes", "max_seq_len", "hbm_check",
                                   "flight_recorder")} == {
        "dtype": "bf16", "kv_cache_dtype": "bf16", "max_seqs": 64, "decode_chain": 8, "kv_block_size": 16,
        "row_bucket": 8, "chunk_bucket": 256, "kv_pool_bytes": 536870912, "max_seq_len": 1024,
        "hbm_check": "off", "flight_recorder": True}
    assert "max_ragged_batch_size" not in engine  # one (64, 256) prefill a wave
    assert cell["warm"]["chain_rows"] == [64] and cell["warm"]["chain_prompt_len"] == 256
    # what the traffic can hold fits what the engine is given
    pages = -(-(256 + 512) // 16) + 1
    assert 64 * pages * 16 * 8192 <= engine["kv_pool_bytes"] and 256 + 512 <= engine["max_seq_len"]
    (e2e,) = [m for m in BENCH["end_to_end"] if m["name"] == "serve_out_tokens_per_s"]
    assert CELL in e2e["workloads"]
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == set(NEW) | set(SHARED)
    assert {m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]} == set(NEW)
    assert {m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", CELL)} == {"serve_out_tokens_per_s", "setup_s"}


def test_the_architecture_file_counts_the_program_s_parameters():
    from deepspeed_tpu.checkpoint.hf import config_from_hf

    assert ARCH.ssm_matmul_params(CFG) == 2048 * 8512 + 4096 * 2048 == 25_821_184
    assert ARCH.ssm_params(CFG) == 25_847_232 and ARCH.attention_params(CFG) == 10_485_760
    assert ARCH.mlp_params(CFG) == 50_331_648
    assert ARCH.total_params(CFG) == config_from_hf(CFG).num_params() == 3_191_396_096
    assert (ARCH.layers(CFG), ARCH.ssm_layers(CFG), ARCH.attention_layers(CFG), ARCH.heads(CFG), ARCH.kv_heads(CFG),
            ARCH.head_dim(CFG)) == (40, 36, 4, 32, 8, 64)
    assert ARCH.matmul_params(CFG) == 36 * 25_821_184 + 4 * 10_485_760 + 40 * 50_331_648 + 2048 * 100352
    assert not hasattr(ARCH, "routed_layers")  # it says nothing of routing
    assert set(ARCH.WIDTH_KEYS) >= {"hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
                                    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_expand",
                                    "mamba_n_groups"}


def test_ssm_decode_cost_by_hand():
    assert ARCH.state_bytes(CFG) == 64 * 64 * 128 * 4 + 3 * 4352 * 2 == 2_097_152 + 26_112
    flops, bytes_ = ARCH.ssm_decode_cost(CFG, 1.0, 0.0)
    # a live row a step: its state and tail read once and written once in each of 36 layers: 152.9 MB
    assert bytes_ == 36 * 2 * 2_123_264 == 152_875_008
    assert flops == 36 * (2 * 25_821_184 + 6 * 64 * 64 * 128)
    # a step: the 36 mixers' weights once, in bf16: 1.86 GB
    assert ARCH.ssm_decode_cost(CFG, 0.0, 1.0) == (0.0, 36 * 25_847_232 * 2.0) == (0.0, 1_861_000_704.0)
    # the cell's full step: 64 rows
    _, full = ARCH.ssm_decode_cost(CFG, 64.0, 1.0)
    assert full == 64 * 152_875_008 + 1_861_000_704 and 14.1e-3 < full / 819e9 < 14.3e-3


def test_ssd_scan_cost_by_hand():
    # one sequence of one chunk of 256: the group's scores 256^2 x 128, a head 256^2 x 64 + 4 x 256 x 64 x 128
    flops, bytes_ = ARCH.ssd_scan_cost(CFG, 1.0, 256)
    assert flops == 256 * 256 * 128 + 64 * (256 * 256 * 64 + 4 * 256 * 64 * 128)
    assert bytes_ == 256 * (2 * 4096 * 2 + 2 * 128 * 2 + 64 * 4) + 2 * 64 * 64 * 128 * 4
    # 300 tokens: a chunk of 256 and one of 44; rows multiply
    more, _ = ARCH.ssd_scan_cost(CFG, 3.0, 300)
    tail = 44 * 44 * 128 + 64 * (44 * 44 * 64 + 4 * 44 * 64 * 128)
    assert more == 3 * (flops + tail)


# ---- the readers on a synthetic trace ------------------------------------------------------------

CHAIN = "jit(chain)/while/body/pool_scan/while/body/layer/"


def instruction(program_name, name, op_name, seconds):
    return scopes.Instruction(program_name, name, "fusion", f"%{name} = bf16[8] fusion()", op_name, seconds, 1)


INSTRUCTIONS = (
    instruction("chain", "fusion.1", CHAIN + "ssm/ssm_update/mul", 0.40),
    instruction("chain", "fusion.2", CHAIN + "ssm/ssm_in_proj/dot_general", 0.10),
    instruction("chain", "fusion.3", CHAIN + "mlp/w_up/dot_general", 0.30),
    instruction("chain", "fusion.7", CHAIN + "nossm/ssm_like/add", 1.0),  # a component, not a substring
    instruction("step", "fusion.1", "jit(step)/pool_scan/while/body/layer/ssm/ssm_scan/dot_general", 0.20),
    instruction("train_step", "fusion.1", "jit(train_step)/layers/layer_0/ssm/ssm_scan/dot_general", 9.0),  # no serving program
)


def event(name, start_s, seconds, **stats):
    return types.SimpleNamespace(name=name, start_ns=start_s * 1e9, duration_ns=seconds * 1e9, stats=stats.items())


def op(name, start_s, seconds):
    return event(f"%{name} = bf16[8] fusion()", start_s, seconds)


def profile_of(host, modules, ops):
    lines = [types.SimpleNamespace(name=xplane.MODULES_LINE, events=modules),
             types.SimpleNamespace(name=xplane.OPS_LINE, events=ops)]
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[types.SimpleNamespace(name="main", events=host)]),
        types.SimpleNamespace(name="/device:TPU:0", lines=lines)])


# the window is [10, 13]. Chain 5 dispatched ahead (during chain 4's run), whole inside the window: 64 rows
# x 8 steps, its run 10.62-10.82 with 0.05 + 0.01 s under ssm; chain 6 the last of a wave: 64 rows x 7
# steps, its run 10.90-11.08 with 0.04 s; chain 4 dispatched before the window started; chain 7 fetched
# after its end; chain 8 of a program without recurrent state says no state_rows
HOST = [
    event("bench:window", 10.0, 3.0),
    event("dstpu:serve:fetch", 9.90, 0.15, kind="chain", chain=4),            # cut by the window's start
    event("dstpu:serve:dispatch", 10.50, 0.01, kind="chain", rows=64, live=64, k=8, chain=5, ahead=1, state_rows=512),
    event("dstpu:serve:fetch", 10.60, 0.225, kind="chain", chain=5),
    event("dstpu:serve:dispatch", 10.70, 0.01, kind="chain", rows=64, live=64, k=8, chain=6, ahead=1, state_rows=448),
    event("dstpu:serve:fetch", 10.85, 0.24, kind="chain", chain=6),
    event("dstpu:serve:dispatch", 12.80, 0.01, kind="chain", rows=64, live=64, k=8, chain=7, ahead=0, state_rows=512),
    event("dstpu:serve:fetch", 12.81, 0.30, kind="chain", chain=7),           # cut by the window's end
    event("dstpu:serve:dispatch", 11.50, 0.01, kind="chain", rows=64, live=64, k=8, chain=8, ahead=0),
    event("dstpu:serve:fetch", 11.51, 0.30, kind="chain", chain=8),
]
MODULES = [event("jit_chain(7)", 9.95, 0.10), event("jit_chain(7)", 10.51, 0.10), event("jit_chain(7)", 10.62, 0.20),
           event("jit_chain(7)", 10.90, 0.18), event("jit_chain(7)", 11.52, 0.20), event("jit_chain(7)", 12.82, 0.20),
           event("jit_step(3)", 11.90, 0.30)]
OPS = [op("fusion.1", 9.96, 0.05),                                           # chain 4's: not paired
       op("fusion.1", 10.52, 0.05),                                          # the chain before 5, in 5's own span: not its run
       op("fusion.1", 10.63, 0.05), op("fusion.2", 10.70, 0.01), op("fusion.3", 10.72, 0.03), op("fusion.7", 10.76, 0.02),
       op("fusion.1", 10.91, 0.04),
       op("fusion.1", 11.53, 0.05),                                          # chain 8's: says no state_rows
       op("fusion.1", 11.91, 0.20),                                          # a prefill's fusion.1: another program's
       op("fusion.1", 12.83, 0.05)]                                          # chain 7's: not paired


@pytest.fixture
def synthetic(monkeypatch):
    path = "synthetic-ssm.xplane.pb"
    monkeypatch.setattr(spans, "trace_file", lambda run: path)
    monkeypatch.setattr(spans, "profile", lambda p: profile_of(HOST, MODULES, OPS))
    monkeypatch.setattr(scopes, "instructions", lambda p: INSTRUCTIONS)
    spans.read_spans.cache_clear()
    yield {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    spans.read_spans.cache_clear()


class Trace:
    busy_s, n_devices = 2.0, 1


def test_the_time_share_is_what_lies_under_ssm_in_the_two_serving_programs(synthetic):
    assert harness.load_reader("ssm_time_share.batch")(synthetic, Trace()) == pytest.approx(100 * 0.70 / 2.0)


def test_the_roofline_pairs_chains_with_their_own_runs(synthetic):
    chains = ssm.paired_chains(synthetic)
    assert [(c["state_rows"], c["steps"]) for c in chains] == [(512.0, 8.0), (448.0, 7.0)]
    assert [c["ssm_s"] for c in chains] == pytest.approx([0.06, 0.04])
    assert [c["run_s"] for c in chains] == pytest.approx([0.20, 0.18])
    _, bytes_ = ARCH.ssm_decode_cost(CFG, 960.0, 15.0)
    assert bytes_ == 960 * 152_875_008 + 15 * 1_861_000_704
    least = bytes_ / 819e9  # memory-bound: 14 ms of bytes a full step against 0.05 ms of FLOPs
    assert harness.load_reader("ssm_decode_roofline.batch")(synthetic, Trace()) == pytest.approx(100 * least / 0.10)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scope_reads_nothing(name, tmp_path, monkeypatch):
    """The recorded v5e trace is of PR 25's program: no ``ssm`` scope, no
    ``state_rows`` on a dispatch. As the parent of PR 42 reads the new metrics."""
    path = unpack_span_trace(tmp_path)
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: path)
    scopes.report.cache_clear(), spans.report_idle.cache_clear()
    run = {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    trace = xplane.reduce_trace(path)
    assert harness.load_reader(name)(run, trace) is None
    assert ssm.paired_chains(run) == []
    other = dict(run, architecture=harness.load_architecture("gpt_neox"))
    assert harness.load_reader("ssm_decode_roofline.batch")(other, trace) is None  # a file without ssm_decode_cost


@pytest.mark.parametrize("metric", [m for m in BENCH["per_layer"] if m["name"] in NEW], ids=lambda m: m["name"])
def test_the_new_entries(metric):
    assert metric["workloads"] == [CELL] and metric["moves"] == "serve_out_tokens_per_s" and metric["unit"] == "%"
    assert metric["source"] == "device_trace"
    assert metric["layer"] == ("kernels" if "roofline" in metric["name"] else "model")
    assert metric["better"] == ("higher" if "roofline" in metric["name"] else "lower")


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import os

    path = os.path.join(harness.BENCH_DIR, "reference", "granitemoehybrid.py")
    tree = ast.parse(open(path).read())
    imported = {(n.module if isinstance(n, ast.ImportFrom) else a.name) for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom)) for a in n.names}
    assert imported <= {"__future__", "jax", "jax.numpy"}, imported
