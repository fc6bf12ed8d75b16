"""PR 48's files: the ``qwen3_next`` configuration (one chip's share of an
eight-way expert-parallel stage), its cell, its architecture file's counts,
``gdn_decode_cost``, ``gdn_chunk_cost`` and the share's ``routed_decode_cost``
by hand, and the readers ``gdn_time_share`` and ``gdn_decode_roofline``
(``benchmarks/lib/paired.py``, the pairing with the scope as a parameter) on a
synthetic trace whose numbers can be checked by hand and on the recorded v5e
trace of a program that has none of their names (nothing found, nothing
raised); the routed layer's three ``.ep`` metrics read by the existing readers
through their stem. The configuration's and the cell's facts are held by
MEMBERSHIP, never by position or count: the next appended cell breaks nothing
here."""

import json
import os
import types

import pytest

from benchmarks.lib import harness, paired, program, scopes, spans, xplane
from tests.benchmarks.conftest import config_rules, unpack_span_trace

BENCH = harness.load_benchmark()
CONFIG, CELL = "qwen3-next-80b-a3b", "qwen3-next-80b-a3b.serve.long-output-wave128"
NEW = ["gdn_time_share.batch", "gdn_decode_roofline.batch"]
ROUTED = ["moe_time_share.ep", "moe_experts_roofline.ep", "moe_experts_touched.ep"]
SHARED = ["compiles_in_window.batch", "decode_chain_ms.batch", "hbm_live_peak_gib.batch",
          "hbm_reserved_peak_gib.batch", "idle_share.batch", "rows_per_chain.batch",
          "pool_copy_time_share.batch", "sched_host_ms.batch", "chain_live_rows.batch"]
HELD = harness.load_config(CONFIG)
CFG = program.published(HELD)
ARCH = harness.load_architecture("qwen3_next")
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
CUTS = {"num_hidden_layers": 12, "num_experts": 64, "vocab_size": 18992}


def test_the_configuration_is_the_catalog_s_cut_to_one_chip_s_share():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    config_rules(entry, HELD, BENCH)
    assert entry["reduced"] == list(CUTS) == [r["key"] for r in HELD["reduced"]]
    assert HELD["reduced"] == [{"key": k, "published": CATALOG[k], "used": v} for k, v in CUTS.items()]
    assert HELD["source"] == "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
    assert {k: CFG[k] for k in CATALOG} == dict(CATALOG, **CUTS)  # every other key as published
    assert CFG["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 3
    assert CFG["expert_parallel"] == {"size": 8, "rank": 0} and CFG["dtype"] == "bfloat16"
    assert set(CFG) - set(CATALOG) == {"layer_types", "dtype", "expert_parallel"}
    assert not set(CUTS) & set(ARCH.WIDTH_KEYS)  # no width is cut; the share's size is a width
    assert "expert_parallel" in ARCH.WIDTH_KEYS and "num_experts_per_tok" in ARCH.WIDTH_KEYS
    for said in ("32 v5e chips", "4 pipeline stages of 12 layers", "8 chips", "rank 0", "experts 0-63 of 512", "18,992"):
        assert said in HELD["deployment"], said
    for said in ("expert_parallel", "vocab_size", "num_hidden_layers", "layer_types", "state_dtype", "in_proj_layout",
                 "gated_norm", "attention", "router", "mtp", "dtype", "weights", "max_position_embeddings"):
        assert len(HELD["assumed"][said]) > 40, said
    for key in ("logit_rel_tol", "route_shortfall_tol"):
        read = HELD["check"]["readings"][key]
        assert read["sound_max"] < HELD["check"][key] < read["control_min"]


def test_the_cell_is_issue_48_s():
    cell = harness.load_workload(CELL)
    assert cell["config"] == CONFIG and cell["kind"] == "serve" and cell["chips"] == 1
    (listed,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert listed == {"name": CELL, "config": CONFIG, "traffic": "serve.long-output-wave128", "chips": 1,
                      "why": cell["why"]}
    assert "8x their share" in cell["why"] and len(cell["why"]) <= 200  # the mixers see eight times their share: said
    assert cell["traffic"] == {"kind": "closed_waves", "wave": 128,
                               "prompt_len": {"dist": "uniform", "min": 64, "max": 256}, "output_tokens": 512}
    engine = cell["engine"]
    assert {k: engine[k] for k in ("dtype", "kv_cache_dtype", "max_seqs", "decode_chain", "kv_block_size",
                                   "row_bucket", "chunk_bucket", "kv_pool_bytes", "max_seq_len", "hbm_check",
                                   "flight_recorder")} == {
        "dtype": "bf16", "kv_cache_dtype": "bf16", "max_seqs": 128, "decode_chain": 8, "kv_block_size": 16,
        "row_bucket": 8, "chunk_bucket": 256, "kv_pool_bytes": 671088640, "max_seq_len": 1024,
        "hbm_check": "off", "flight_recorder": True}
    assert "max_ragged_batch_size" not in engine  # one (128, 256) prefill a wave: a row is its state slot
    assert cell["warm"] == {"prefill": [[128, 256]], "chain_rows": [128], "chain_prompt_len": 256}
    # what the traffic can hold fits what the engine is given
    pages = -(-(256 + 512) // 16) + 1
    assert 128 * pages * 16 * 6144 <= engine["kv_pool_bytes"] and 256 + 512 <= engine["max_seq_len"]
    (e2e,) = [m for m in BENCH["end_to_end"] if m["name"] == "serve_out_tokens_per_s"]
    assert CELL in e2e["workloads"]
    on_cell = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", ())}
    assert on_cell == set(NEW) | set(ROUTED) | set(SHARED)
    assert {m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]} == set(NEW) | set(ROUTED)
    assert {m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", CELL)} == {"serve_out_tokens_per_s", "setup_s"}
    # the .batch names of the routed and state-space readers stay their own cells'
    for m in BENCH["per_layer"]:
        if m["name"].startswith(("moe_", "mla_", "ssm_")) and m["name"].endswith(".batch"):
            assert CELL not in m["workloads"], m["name"]


def test_the_architecture_file_counts_the_program_s_parameters():
    from deepspeed_tpu.checkpoint.hf import config_from_hf

    # ISSUE 48's arithmetic
    assert ARCH.gdn_params(CFG) == 2048 * 12288 + 2048 * 64 + 8192 * 4 + 4096 * 2048 + 192 == 33_718_464
    assert ARCH.attention_params(CFG) == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 512 == 27_263_488
    assert 2048 * 512 + ARCH.shared_params(CFG) == 1_048_576 + 3_145_728 + 2_048 == 4_196_352
    assert ARCH.expert_params(CFG) == 3_145_728
    in_experts, outside, embed = 12 * 64 * 3_145_728, 9 * 37_918_912 + 3 * 31_463_936, 2 * 18_992 * 2_048
    assert (in_experts, outside, embed) == (2_415_919_104, 435_662_016, 77_791_232)
    assert ARCH.total_params(CFG) == config_from_hf(CFG).num_params() == in_experts + outside + embed + 2048 == 2_929_374_400
    assert (ARCH.layers(CFG), ARCH.gdn_layers(CFG), ARCH.attention_layers(CFG), ARCH.heads(CFG), ARCH.kv_heads(CFG),
            ARCH.head_dim(CFG)) == (12, 9, 3, 16, 2, 256)
    assert (ARCH.routed_layers(CFG), ARCH.routed_experts(CFG), ARCH.held_experts(CFG), ARCH.experts_per_token(CFG)) == (
        12, 512, 64, 10)
    # a token's products here: every mixer, router and shared expert, 10 / 8 expert visits a layer, the head's slice
    assert ARCH.matmul_params(CFG) == (9 * ARCH.gdn_params(CFG) + 3 * 27_263_488 + 12 * 4_196_352
                                       + int(12 * 1.25 * 3_145_728) + 2048 * 18_992)
    routing = program.routing(ARCH, HELD)
    assert (routing.layers, routing.experts, routing.k) == (12, 512, 10)  # picks in the PUBLISHED numbering


def test_gdn_decode_cost_by_hand():
    assert ARCH.state_bytes(CFG) == 32 * 128 * 128 * 4 + 3 * 8192 * 2 == 2_097_152 + 49_152 == 2_146_304
    flops, bytes_ = ARCH.gdn_decode_cost(CFG, 1.0, 0.0)
    # a live row a step: its state and tail read once and written once in each of 9 layers: 38.6 MB
    assert bytes_ == 9 * 2 * 2_146_304 == 38_633_472
    assert flops == 9 * (2 * (33_718_464 - 8192 * 4 - 192) + 7 * 32 * 128 * 128)
    # a step: the 9 mixers' weights once, in bf16: 0.61 GB
    assert ARCH.gdn_decode_cost(CFG, 0.0, 1.0) == (0.0, 9 * 33_718_464 * 2.0) == (0.0, 606_932_352.0)
    # the cell's full step: 128 rows: 4.95 GB of state (ISSUE 48) + the weights
    _, full = ARCH.gdn_decode_cost(CFG, 128.0, 1.0)
    assert full == 128 * 38_633_472 + 606_932_352 and 4.94e9 < 128 * 38_633_472 < 4.95e9
    assert 6.7e-3 < full / 819e9 < 6.8e-3


def test_gdn_chunk_cost_by_hand():
    # one sequence of one chunk of 64, a value head: k k^T and q k^T 2 x 64^2 x 128, the solve 64^2 x 256,
    # q k^T times the new values 64^2 x 128, three products with the state 6 x 64 x 128 x 128
    flops, bytes_ = ARCH.gdn_chunk_cost(CFG, 1.0, 64)
    assert flops == 32 * (2 * 64 * 64 * 128 + 64 * 64 * 256 + 64 * 64 * 128 + 6 * 64 * 128 * 128)
    assert bytes_ == 64 * (2 * 2048 * 2 + 2 * 4096 * 2 + 2 * 32 * 4) + 2 * 32 * 128 * 128 * 4
    # 300 tokens: four chunks of 64 and one of 44; rows multiply
    more, _ = ARCH.gdn_chunk_cost(CFG, 3.0, 300)
    tail = 32 * (2 * 44 * 44 * 128 + 44 * 44 * 256 + 44 * 44 * 128 + 6 * 44 * 128 * 128)
    assert more == 3 * (4 * flops + tail)


def test_the_share_s_routed_decode_cost_by_hand():
    # a (step, layer): the router's 512 columns, the shared expert and its gate once; 59 held experts read
    flops, bytes_ = ARCH.routed_decode_cost(CFG, 59.0, 128.0, 1.0)
    assert bytes_ == (59 * 3_145_728 + 4_196_352) * 2
    # a token: router and shared expert, and 10 / 8 visits to held experts on average
    assert flops == 2.0 * 128 * (1.25 * 3_145_728 + 4_196_352)
    # ISSUE 48's step: 12 layers x 59 experts x 6.29 MB = 4.45 GB
    _, step = ARCH.routed_decode_cost(CFG, 12 * 59.0, 12 * 128.0, 12.0)
    assert 4.4e9 < 12 * 59 * 3_145_728 * 2 < 4.5e9 and 4.5e9 < step < 4.6e9


# ---- the readers on a synthetic trace ------------------------------------------------------------

CHAIN = "jit(chain)/while/body/pool_scan/while/body/layer/"


def instruction(program_name, name, op_name, seconds):
    return scopes.Instruction(program_name, name, "fusion", f"%{name} = bf16[8] fusion()", op_name, seconds, 1)


INSTRUCTIONS = (
    instruction("chain", "gdn_update.1", CHAIN + "gdn/gdn_update/pallas_call", 0.40),
    instruction("chain", "fusion.2", CHAIN + "gdn/gdn_in_proj/dot_general", 0.10),
    instruction("chain", "fusion.3", CHAIN + "moe/moe_experts/jit(gmm)/pallas_call", 0.30),
    instruction("chain", "fusion.7", CHAIN + "nogdn/gdn_like/add", 1.0),  # a component, not a substring
    instruction("chain", "fusion.8", CHAIN + "ssm/ssm_update/mul", 1.0),  # another mixer's scope
    instruction("step", "fusion.1", "jit(step)/pool_scan/while/body/layer/gdn/gdn_chunk/dot_general", 0.20),
    instruction("train_step", "fusion.1", "jit(train_step)/layers/layer_0/gdn/gdn_chunk/dot_general", 9.0),  # no serving program
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


# the window is [10, 13]. Chain 5 dispatched ahead (during chain 4's run), whole inside the window: 128 rows
# x 8 steps, its run 10.62-10.82 with 0.05 + 0.01 s under gdn; chain 6 the last of a wave: 128 rows x 7
# steps, its run 10.90-11.08 with 0.04 s; chain 4 dispatched before the window started; chain 7 fetched
# after its end; chain 8 of a program without recurrent state says no state_rows
HOST = [
    event("bench:window", 10.0, 3.0),
    event("dstpu:serve:fetch", 9.90, 0.15, kind="chain", chain=4),            # cut by the window's start
    event("dstpu:serve:dispatch", 10.50, 0.01, kind="chain", rows=128, live=128, k=8, chain=5, ahead=1, state_rows=1024),
    event("dstpu:serve:fetch", 10.60, 0.225, kind="chain", chain=5),
    event("dstpu:serve:dispatch", 10.70, 0.01, kind="chain", rows=128, live=128, k=8, chain=6, ahead=1, state_rows=896),
    event("dstpu:serve:fetch", 10.85, 0.24, kind="chain", chain=6),
    event("dstpu:serve:dispatch", 12.80, 0.01, kind="chain", rows=128, live=128, k=8, chain=7, ahead=0, state_rows=1024),
    event("dstpu:serve:fetch", 12.81, 0.30, kind="chain", chain=7),           # cut by the window's end
    event("dstpu:serve:dispatch", 11.50, 0.01, kind="chain", rows=128, live=128, k=8, chain=8, ahead=0),
    event("dstpu:serve:fetch", 11.51, 0.30, kind="chain", chain=8),
    event("dstpu:serve:accept", 10.83, 0.001, kind="chain", chain=5, emitted=1024, experts_touched=58.5, held_visits=160.0),
    event("dstpu:serve:accept", 11.09, 0.001, kind="chain", chain=6, emitted=896, experts_touched=57.5, held_visits=158.0),
]
MODULES = [event("jit_chain(7)", 9.95, 0.10), event("jit_chain(7)", 10.51, 0.10), event("jit_chain(7)", 10.62, 0.20),
           event("jit_chain(7)", 10.90, 0.18), event("jit_chain(7)", 11.52, 0.20), event("jit_chain(7)", 12.82, 0.20),
           event("jit_step(3)", 11.90, 0.30)]
OPS = [op("gdn_update.1", 9.96, 0.05),                                       # chain 4's: not paired
       op("gdn_update.1", 10.52, 0.05),                                      # the chain before 5, in 5's own span: not its run
       op("gdn_update.1", 10.63, 0.05), op("fusion.2", 10.70, 0.01), op("fusion.3", 10.72, 0.03), op("fusion.7", 10.76, 0.02),
       op("gdn_update.1", 10.91, 0.04),
       op("gdn_update.1", 11.53, 0.05),                                      # chain 8's: says no state_rows
       op("fusion.1", 11.91, 0.20),                                          # a prefill's fusion.1: another program's
       op("gdn_update.1", 12.83, 0.05)]                                      # chain 7's: not paired


@pytest.fixture
def synthetic(monkeypatch):
    path = "synthetic-gdn.xplane.pb"
    monkeypatch.setattr(spans, "trace_file", lambda run: path)
    monkeypatch.setattr(spans, "profile", lambda p: profile_of(HOST, MODULES, OPS))
    monkeypatch.setattr(scopes, "instructions", lambda p: INSTRUCTIONS)
    spans.read_spans.cache_clear()
    yield {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    spans.read_spans.cache_clear()


class Trace:
    busy_s, n_devices = 2.0, 1


def test_the_time_share_is_what_lies_under_gdn_in_the_two_serving_programs(synthetic):
    assert harness.load_reader("gdn_time_share.batch")(synthetic, Trace()) == pytest.approx(100 * 0.70 / 2.0)


def test_the_roofline_pairs_chains_with_their_own_runs(synthetic):
    chains = paired.paired_chains(synthetic, "gdn")
    assert [(c["state_rows"], c["steps"]) for c in chains] == [(1024.0, 8.0), (896.0, 7.0)]
    assert [c["scope_s"] for c in chains] == pytest.approx([0.06, 0.04])
    assert [c["run_s"] for c in chains] == pytest.approx([0.20, 0.18])
    _, bytes_ = ARCH.gdn_decode_cost(CFG, 1920.0, 15.0)
    assert bytes_ == 1920 * 38_633_472 + 15 * 606_932_352
    least = bytes_ / 819e9  # memory-bound: 6.8 ms of bytes a full step against 0.05 ms of FLOPs
    assert harness.load_reader("gdn_decode_roofline.batch")(synthetic, Trace()) == pytest.approx(100 * least / 0.10)


def test_the_pairing_takes_its_scope_as_a_parameter(synthetic):
    """The same chains under another mixer's scope: ``lib/paired.py`` is what
    ``lib/ssm.py`` and ``lib/mhc.py`` each write out for one name."""
    assert paired.paired_chains(synthetic, "mhc") == []  # no instruction under it: nothing to pair
    assert paired.seconds(synthetic, Trace(), "ssm") == pytest.approx(1.0)
    assert paired.seconds(synthetic, Trace(), "gdn") == pytest.approx(0.70)


def test_the_routed_readers_serve_the_share_through_their_stem(synthetic):
    """``harness.load_reader`` strips the last suffix: ``moe_*.ep`` are the
    existing readers, fed this architecture's ``routed_decode_cost`` and the
    chains' HELD experts."""
    for name in ROUTED:
        assert not os.path.exists(os.path.join(harness.BENCH_DIR, "metrics", name + ".py"))
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics", name.rpartition(".")[0] + ".py"))
    assert harness.load_reader("moe_experts_touched.ep")(synthetic, Trace()) == pytest.approx(58.0)
    assert harness.load_reader("moe_time_share.ep")(synthetic, Trace()) == pytest.approx(100 * 0.30 / 2.0)
    # chains 5 and 6: 8 and 7 steps of 12 routed layers at 58.5 and 57.5 held experts read
    experts = (58.5 * 8 + 57.5 * 7) * 12
    flops, bytes_ = ARCH.routed_decode_cost(CFG, experts, (1024 + 896) * 12, 15 * 12)
    assert bytes_ > flops / 240  # memory-bound on the v5e
    assert harness.load_reader("moe_experts_roofline.ep")(synthetic, Trace()) == pytest.approx(100 * bytes_ / 819e9 / 0.30)


@pytest.mark.parametrize("name", NEW + ROUTED)
def test_a_program_without_the_names_reads_nothing(name, tmp_path, monkeypatch):
    """The recorded v5e trace is of PR 25's program: no ``gdn`` or ``moe`` scope,
    no ``state_rows`` on a dispatch. As the parent of PR 48 would read the new metrics."""
    path = unpack_span_trace(tmp_path)
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: path)
    scopes.report.cache_clear(), spans.report_idle.cache_clear()
    run = {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    trace = xplane.reduce_trace(path)
    assert harness.load_reader(name)(run, trace) is None
    assert paired.paired_chains(run, "gdn") == []
    other = dict(run, architecture=harness.load_architecture("gpt_neox"))
    assert harness.load_reader("gdn_decode_roofline.batch")(other, trace) is None  # a file without gdn_decode_cost


@pytest.mark.parametrize("metric", [m for m in BENCH["per_layer"] if m["name"] in NEW + ROUTED], ids=lambda m: m["name"])
def test_the_new_entries(metric):
    assert metric["workloads"] == [CELL] and metric["moves"] == "serve_out_tokens_per_s"
    assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    if metric["name"] == "moe_experts_touched.ep":
        assert (metric["unit"], metric["source"], metric["layer"], metric["better"]) == (
            "count", "program_span", "serving loop", "lower")
    else:
        assert metric["unit"] == "%" and metric["source"] == "device_trace"
        assert metric["layer"] == ("kernels" if "roofline" in metric["name"] else "model")
        assert metric["better"] == ("higher" if "roofline" in metric["name"] else "lower")


def test_the_benchmark_only_grew():
    """Against the parent's ``BENCHMARK.json`` as git has it, where git is
    there: every entry that was there is there, in place, changed by nothing
    but this cell's name appended to a list of cells."""
    import subprocess

    root = os.path.dirname(harness.BENCH_DIR)
    shown = subprocess.run(["git", "-C", root, "show", "06ecbc90331c0a9fda2638040d9b699cb633df8a:BENCHMARK.json"],
                           capture_output=True, text=True)
    if shown.returncode:
        pytest.skip("no git history here: the driver's check holds the same")
    before = json.loads(shown.stdout)
    assert {k: BENCH[k] for k in ("command", "paths", "run_seconds")} == {k: before[k] for k in ("command", "paths", "run_seconds")}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(before[group], BENCH[group]):
            grown = dict(now)
            if "workloads" in was and grown.get("workloads", [])[-1:] == [CELL]:
                grown["workloads"] = grown["workloads"][:-1]
            assert grown == was, was["name"]


def test_the_reference_imports_nothing_of_the_program():
    import ast

    path = os.path.join(harness.BENCH_DIR, "reference", "qwen3_next.py")
    tree = ast.parse(open(path).read())
    imported = {(n.module if isinstance(n, ast.ImportFrom) else a.name) for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom)) for a in n.names}
    assert imported <= {"__future__", "jax", "jax.numpy"}, imported
