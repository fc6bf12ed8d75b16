"""PR 39's files: the ``xing4_0`` configuration, its cell, its architecture
file's counts and ``mhc_cost`` by hand, and the readers ``mhc_time_share`` and
``mhc_roofline`` (``benchmarks/lib/mhc.py``) with ``prefill_ms`` on a synthetic
trace whose numbers can be checked by hand and on the recorded v5e trace of a
program that has none of their names (nothing found, nothing raised)."""

import os
import types

import pytest

from benchmarks.lib import harness, mhc, program, scopes, spans, xplane
from tests.benchmarks.conftest import config_rules, unpack_span_trace

BENCH = harness.load_benchmark()
CONFIG, CELL = "xing4.0-29b-a4b", "xing4.0-29b-a4b.serve.long-prompt-batch"
NEW = ["mhc_time_share.batch", "mhc_roofline.batch"]
HELD = harness.load_config(CONFIG)
CFG = program.published(HELD)
ARCH = harness.load_architecture("xing4_0")


def test_the_configuration_is_the_catalog_s_cut_to_seven_layers():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    config_rules(entry, HELD, BENCH)
    assert entry["reduced"] == ["num_hidden_layers"] and BENCH["configs"][-1] is entry
    assert HELD["reduced"] == [{"key": "num_hidden_layers", "published": 40, "used": 7}]
    published = {"hidden_size": 3584, "intermediate_size": 9216, "moe_intermediate_size": 1024, "num_attention_heads": 32,
                 "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "n_routed_experts": 64, "num_experts_per_tok": 4, "n_shared_experts": 1,
                 "first_k_dense_replace": 2, "routed_scaling_factor": 2, "vocab_size": 131072, "hc_mult": 4,
                 "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
                 "rope_theta": 10000, "rms_norm_eps": 1e-06, "max_position_embeddings": 262144,
                 "num_nextn_predict_layers": 1, "model_type": "xing4_0", "scoring_func": "sigmoid"}
    assert {k: HELD[k] for k in published} == published
    assert HELD["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                                    "original_max_position_embeddings": 4096, "type": "yarn"}
    for said in ("streams_in_and_out", "h_res_side", "sinkhorn_order", "clamp", "hc_eps", "stream_norm", "h_post",
                 "yarn", "rope_pairs", "num_nextn_predict_layers", "dtype", "weights"):
        assert said in HELD["assumed"]
    assert "pipeline stage" in HELD["deployment"] and "larger shares" in HELD["deployment"]
    for key in ("logit_rel_tol", "route_shortfall_tol"):
        read = HELD["check"]["readings"][key]
        assert read["sound_max"] < HELD["check"][key] < read["control_min"]
    src = open(os.path.join(harness.BENCH_DIR, "reference", "xing4_0.py")).read()
    assert "deepspeed_tpu" not in src and "import" in src  # imports nothing of the program


def test_the_cell_is_issue_39_s():
    held = harness.load_workload(CELL)
    assert held["kind"] == "serve" and held["chips"] == 1 and held["config"] == CONFIG
    assert held["traffic"] == {"kind": "closed_waves", "wave": 64, "output_tokens": 32,
                               "prompt_len": {"dist": "uniform", "min": 1024, "max": 2048}}
    engine = held["engine"]
    assert {k: engine[k] for k in ("dtype", "kv_cache_dtype", "max_seqs", "decode_chain", "kv_block_size",
                                   "row_bucket", "chunk_bucket", "flight_recorder")} == {
        "dtype": "bf16", "kv_cache_dtype": "bf16", "max_seqs": 64, "decode_chain": 8, "kv_block_size": 16,
        "row_bucket": 8, "chunk_bucket": 2048, "flight_recorder": True}
    # a token's slot is 7 layers x 640 columns x 2 B; the traffic's worst is 64 rows of 2,048 + 31 tokens
    assert engine["kv_pool_bytes"] >= 64 * 131 * 16 * 7 * 640 * 2 and engine["max_seq_len"] >= 2048 + 32
    assert engine["max_ragged_batch_size"] % engine["chunk_bucket"] == 0
    assert [engine["max_ragged_batch_size"] // engine["chunk_bucket"], 2048] in held["warm"]["prefill"]
    assert 64 in held["warm"]["chain_rows"]
    assert BENCH["workloads"][-1]["name"] == CELL and len(BENCH["workloads"]) == 6
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    assert set(NEW) <= listed and {m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]} == set(NEW)
    assert listed - set(NEW) == {"compiles_in_window.batch", "decode_chain_ms.batch", "hbm_live_peak_gib.batch",
                                 "hbm_reserved_peak_gib.batch", "idle_share.batch", "rows_per_chain.batch",
                                 "pool_copy_time_share.batch", "sched_host_ms.batch", "chain_live_rows.batch"}
    assert [m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", CELL)] == ["serve_out_tokens_per_s", "setup_s"]


def test_the_architecture_file_counts_the_program_s_parameters():
    from deepspeed_tpu.checkpoint.hf import config_from_hf

    assert ARCH.attention_params(CFG) == 28_411_136 and ARCH.hyper_connection_params(CFG) == 344_091
    assert ARCH.expert_params(CFG) == 11_010_048
    assert ARCH.total_params(CFG) == config_from_hf(CFG).num_params() == 4_920_866_746
    whole = dict(CFG, num_hidden_layers=40)
    assert ARCH.total_params(whole) == config_from_hf(whole).num_params()
    assert (ARCH.layers(CFG), ARCH.routed_layers(CFG), ARCH.heads(CFG), ARCH.kv_heads(CFG), ARCH.head_dim(CFG),
            ARCH.routed_experts(CFG), ARCH.experts_per_token(CFG)) == (7, 5, 32, 1, 192, 64, 4)
    assert not set(r["key"] for r in HELD["reduced"]) & set(ARCH.WIDTH_KEYS)
    # what a token meets in a product: a routed layer's 28.4 M of attention + 5 experts + the router + two phi
    routed = 28_411_136 - 768 - 512 + 5 * 11_010_048 + 3584 * 64 + 2 * 4 * 3584 * 24
    dense = 28_411_136 - 768 - 512 + 3 * 3584 * 9216 + 2 * 4 * 3584 * 24
    assert ARCH.matmul_params(CFG) == 2 * dense + 5 * routed + 3584 * 131072 + 7 * (768 + 512)


def test_mhc_cost_by_hand():
    flops, bytes_ = ARCH.mhc_cost(CFG, 1.0, 1.0)
    # a token a layer: two sublayers, each 3 passes over 4 x 3,584 and 2 over 3,584, in bf16: 172 KB
    assert bytes_ == 2 * (3 * 4 * 3584 + 2 * 3584) * 2 == 200_704
    assert flops == 2 * (2 * 14336 + 2 * 14336 * 24 + 2 * 14336 + 2 * 16 * 3584 + 2 * 14336 + 20 * 4 * 16)
    many = ARCH.mhc_cost(CFG, 98_304.0, 7.0)
    assert many == (flops * 98_304 * 7, bytes_ * 98_304 * 7)
    assert 0.2e-6 < bytes_ / 819e9 < 0.3e-6  # the issue's 0.21 us a token a layer, rounded


# ---- the readers on a synthetic trace ------------------------------------------------------------

LAYER = "jit(step)/pool_scan/while/body/layer/"


def instruction(program_name, name, op_name, seconds):
    return scopes.Instruction(program_name, name, "fusion", f"%{name} = bf16[8] fusion()", op_name, seconds, 1)


INSTRUCTIONS = (
    instruction("step", "fusion.1", LAYER + "attn_hc/mhc/mhc_mix/dot_general", 0.20),
    instruction("step", "fusion.2", LAYER + "mlp_hc/mhc/mhc_post/add", 0.10),
    instruction("step", "fusion.3", LAYER + "moe/moe_experts/gmm", 0.50),
    instruction("chain", "fusion.1", "jit(chain)/while/body/pool_scan/while/body/layer/attn_hc/mhc/mhc_pre/mul", 0.04),
    instruction("chain", "fusion.9", "jit(chain)/while/body/pool_scan/while/body/layer/mla/wo/dot_general", 0.30),
    instruction("train_step", "fusion.1", "jit(train_step)/layers/attn_hc/mhc/mhc_mix/dot_general", 9.0),  # no serving program
    instruction("step", "fusion.7", LAYER + "attn_hc/nomhc/add", 1.0),  # a component, not a substring
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


# the window is [10, 13]. Prefill A whole inside it: 12,000 tokens, its run 10.11-10.39 with 0.03 + 0.02 s
# under mhc; chain 5 dispatched ahead (during chain 4's run), its run 10.62-10.72 with 0.004 s; prefill B
# dispatched inside the window and fetched after its end; chain 4 dispatched before the window started
HOST = [
    event("bench:window", 10.0, 3.0),
    event("dstpu:serve:dispatch", 10.10, 0.01, kind="prefill", rows=8, live=8, tokens=12000, rids="1,2"),
    event("dstpu:serve:fetch", 10.11, 0.29, kind="prefill"),
    event("dstpu:serve:fetch", 9.90, 0.15, kind="chain", chain=4),            # cut by the window's start
    event("dstpu:serve:accept", 10.06, 0.001, kind="chain", chain=4, emitted=512),
    event("dstpu:serve:dispatch", 10.50, 0.01, kind="chain", rows=64, live=64, k=8, chain=5, ahead=1),
    event("dstpu:serve:fetch", 10.60, 0.125, kind="chain", chain=5),
    event("dstpu:serve:accept", 10.73, 0.001, kind="chain", chain=5, emitted=448),
    event("dstpu:serve:dispatch", 12.80, 0.01, kind="prefill", rows=8, live=8, tokens=11000, rids="3"),
    event("dstpu:serve:fetch", 12.81, 0.30, kind="prefill"),                   # cut by the window's end
]
MODULES = [event("jit_chain(7)", 9.95, 0.10), event("jit_step(3)", 10.11, 0.28), event("jit_chain(7)", 10.51, 0.10),
           event("jit_chain(7)", 10.62, 0.10), event("jit_step(3)", 12.82, 0.28)]
OPS = [op("fusion.1", 9.96, 0.004),                                          # chain 4's: not paired
       op("fusion.1", 10.12, 0.03), op("fusion.2", 10.20, 0.02), op("fusion.3", 10.25, 0.1), op("fusion.7", 10.36, 0.01),
       op("fusion.1", 10.52, 0.004),                                         # the chain before 5, in 5's own span: not its run
       op("fusion.1", 10.63, 0.004), op("fusion.9", 10.64, 0.05),
       op("fusion.1", 12.83, 0.03)]                                          # prefill B's: not paired


@pytest.fixture
def synthetic(monkeypatch):
    path = "synthetic-mhc.xplane.pb"
    monkeypatch.setattr(spans, "trace_file", lambda run: path)
    monkeypatch.setattr(spans, "profile", lambda p: profile_of(HOST, MODULES, OPS))
    monkeypatch.setattr(scopes, "instructions", lambda p: INSTRUCTIONS)
    spans.read_spans.cache_clear()
    yield {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    spans.read_spans.cache_clear()


class Trace:
    busy_s, n_devices = 2.0, 1
    modules = {"step": [0.28, 0.30, 0.26], "chain": [0.1]}


def test_the_time_share_is_what_lies_under_mhc_in_the_two_serving_programs(synthetic):
    assert harness.load_reader("mhc_time_share.batch")(synthetic, Trace()) == pytest.approx(100 * 0.34 / 2.0)


def test_the_roofline_pairs_calls_with_their_own_runs(synthetic):
    calls = mhc.paired_calls(synthetic)
    assert [(c["kind"], c["tokens"]) for c in calls] == [("prefill", 12000.0), ("chain", 448.0)]
    assert [c["mhc_s"] for c in calls] == pytest.approx([0.05, 0.004]) and calls[0]["run_s"] == pytest.approx(0.28)
    _, bytes_ = ARCH.mhc_cost(CFG, 12448.0, 7.0)
    least = bytes_ / 819e9  # memory-bound: 0.25 us of bytes against 0.004 us of FLOPs a token a layer
    assert harness.load_reader("mhc_roofline.batch")(synthetic, Trace()) == pytest.approx(100 * least / 0.054)


def test_prefill_ms_is_the_median_run_of_the_prefill_program(synthetic):
    assert harness.load_reader("prefill_ms.batch")(synthetic, Trace()) == pytest.approx(280.0)
    assert harness.load_reader("prefill_ms.batch")(synthetic, types.SimpleNamespace(modules={})) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scope_reads_nothing(name, tmp_path, monkeypatch):
    """The recorded v5e trace is of PR 25's program: no ``mhc`` scope, no
    ``tokens`` on a dispatch. As the parent of PR 39 reads the new metrics."""
    path = unpack_span_trace(tmp_path)
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: path)
    scopes.report.cache_clear(), spans.report_idle.cache_clear()
    run = {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    trace = xplane.reduce_trace(path)
    assert harness.load_reader(name)(run, trace) is None
    assert mhc.paired_calls(run) == []
    other = dict(run, architecture=harness.load_architecture("glm4_moe_lite"))
    assert harness.load_reader("mhc_roofline.batch")(other, trace) is None  # an architecture file without mhc_cost


@pytest.mark.parametrize("metric", [m for m in BENCH["per_layer"] if m["name"] in NEW], ids=lambda m: m["name"])
def test_the_new_entries(metric):
    assert metric["workloads"] == [CELL] and metric["moves"] == "serve_out_tokens_per_s" and metric["unit"] == "%"
    assert metric["source"] == "device_trace"
    assert metric["layer"] == ("kernels" if "roofline" in metric["name"] else "model")
    assert metric["better"] == ("higher" if "roofline" in metric["name"] else "lower")
