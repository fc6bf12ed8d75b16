"""PR 55's files: the ``glm_moe_dsa`` configuration (GLM-5: in every layer a
learned indexer that keeps 2,048 cached tokens a query for latent attention;
one chip's share of a sixteen-way expert-parallel stage), its cell, its
architecture file's counts, ``dsa_index_cost`` and ``dsa_attend_cost`` by hand
(positions under and over ``index_topk``), and the four new readers
(``dsa_time_share``, ``dsa_index_roofline``, ``dsa_attend_roofline``,
``dsa_kept_share``) on a synthetic trace whose numbers can be checked by hand
and on the recorded v5e trace of a program that has none of their names
(nothing found, nothing raised). The configuration's and the cell's facts are
held by MEMBERSHIP, never by position or count: the next appended cell, and the
next cell appended to a list this one is on, breaks nothing here."""

import json
import os
import types

import pytest

from benchmarks.lib import dsa, harness, program, scopes, spans, xplane
from tests.benchmarks.conftest import config_rules, unpack_span_trace

BENCH = harness.load_benchmark()
CONFIG, CELL = "glm-5", "glm-5.serve.long-prompt-wave8"
NEW = ["dsa_time_share.batch", "dsa_index_roofline.batch", "dsa_attend_roofline.batch", "dsa_kept_share.batch"]
# what a traced window of this cell ALWAYS holds something for: a whole prefill call, and mostly a decode chain
LISTED = ["moe_time_share.ep"]
SHARED = ["compiles_in_window.batch", "hbm_live_peak_gib.batch", "hbm_reserved_peak_gib.batch", "idle_share.batch",
          "rows_per_chain.batch", "pool_copy_time_share.batch", "stall_s.batch", "gc_pause_ms.batch"]
# what reads a decode chain's own run or span alone: in one traced run of six the window held two prefills of 1.55 s
# and NO chain (PERF.md, section 7), so the cell is not on these lists
CHAIN_ONLY = ["decode_chain_ms.batch", "sched_host_ms.batch", "chain_live_rows.batch", "moe_experts_roofline.ep",
              "moe_experts_touched.ep", "moe_held_visits.ep"]
HELD = harness.load_config(CONFIG)
CFG = program.published(HELD)
ARCH = harness.load_architecture("glm_moe_dsa")
CATALOG = {  # the catalog row's ``config`` (model-configs guide, architectures.jsonl), ``rope_parameters`` apart
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3, "hidden_act": "silu", "head_dim": 64,
    "hidden_size": 6144, "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
    "indexer_rope_interleave": True, "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 202752, "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "model_type": "glm_moe_dsa", "n_group": 1, "n_routed_experts": 256, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts_per_tok": 8, "num_hidden_layers": 78,
    "num_key_value_heads": 64, "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_interleave": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 256, "vocab_size": 154880}
CUTS = {"num_hidden_layers": 6, "first_k_dense_replace": 1, "n_routed_experts": 16, "vocab_size": 19360}


def test_the_configuration_is_the_catalog_row_with_the_four_cuts_and_nothing_else():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    config_rules(entry, HELD, BENCH)
    assert entry["reduced"] == list(CUTS) == [r["key"] for r in HELD["reduced"]]
    assert HELD["reduced"] == [{"key": k, "published": CATALOG[k], "used": v} for k, v in CUTS.items()]
    assert HELD["source"] == entry["source"] == "https://huggingface.co/zai-org/GLM-5/blob/main/config.json"
    assert {k: CFG[k] for k in CATALOG} == dict(CATALOG, **CUTS)  # every other key as published
    assert CFG["rope_parameters"] == {"rope_theta": 1000000, "rope_type": "default"}  # the nested group, copied whole
    assert CFG["expert_parallel"] == {"size": 16, "rank": 0}
    assert set(CFG) - set(CATALOG) == {"rope_parameters", "expert_parallel"}  # no top-level dtype (``assumed.dtype``)
    assert HELD["architecture"] == "glm_moe_dsa" and HELD["reference"] == "benchmarks/reference/glm_moe_dsa.py"
    assert not set(CUTS) & set(ARCH.WIDTH_KEYS)  # no width is cut: the leading dense layers are depth here
    for width in ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "index_n_heads",
                  "index_head_dim", "index_topk", "num_experts_per_tok", "moe_intermediate_size",
                  "routed_scaling_factor", "expert_parallel"):
        assert width in ARCH.WIDTH_KEYS, width
    for said in ("16 v5e chips", "4 x 4 slice", "sixteen ways", "rank 0", "experts 0-15", "19,360"):
        assert said in HELD["deployment"], said
    for said in ("indexer", "index_columns", "index_key_norm", "index_scores", "ties", "hadamard_and_fp8", "head_dim",
                 "num_nextn_predict_layers", "kv_b_proj", "vocab_size", "expert_parallel", "dtype", "weights",
                 "max_position_embeddings", "rope_pairs"):
        assert len(HELD["assumed"][said]) > 40, said
    for key in ("logit_rel_tol", "route_shortfall_tol"):
        read = HELD["check"]["readings"][key]
        assert read["sound_max"] < HELD["check"][key] < read["control_min"]


def test_the_cell_is_issue_55_s():
    cell = harness.load_workload(CELL)
    assert cell["config"] == CONFIG and cell["kind"] == "serve" and cell["chips"] == 1
    (listed,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert listed == {"name": CELL, "config": CONFIG, "traffic": "serve.long-prompt-wave8", "chips": 1,
                      "why": cell["why"]}
    assert "16x their share" in cell["why"] and len(cell["why"]) <= 200
    assert cell["traffic"] == {"kind": "closed_waves", "wave": 8,
                               "prompt_len": {"dist": "uniform", "min": 4096, "max": 8192}, "output_tokens": 64}
    engine = cell["engine"]
    assert {k: engine[k] for k in ("dtype", "kv_cache_dtype", "max_seqs", "decode_chain", "kv_block_size",
                                   "chunk_bucket", "max_ragged_batch_size", "kv_pool_bytes", "max_seq_len",
                                   "flight_recorder")} == {
        "dtype": "bf16", "kv_cache_dtype": "bf16", "max_seqs": 8, "decode_chain": 8, "kv_block_size": 16,
        "chunk_bucket": 8192, "max_ragged_batch_size": 16384, "kv_pool_bytes": 2 ** 30, "max_seq_len": 8256,
        "flight_recorder": True}
    assert cell["warm"] == {"prefill": [[2, 8192]], "chain_rows": [8], "chain_prompt_len": 4096}
    # what the traffic can hold fits what the engine is given: a latent slab and an index key a token a layer
    per_token = 6 * (640 + 128) * 2
    assert per_token == 9216 and 8 * (8192 + 64) * per_token <= engine["kv_pool_bytes"]
    assert 8192 + 64 <= engine["max_seq_len"] and engine["max_seq_len"] > CFG["index_topk"]
    assert min(cell["traffic"]["prompt_len"]["min"], engine["chunk_bucket"] // 2) > CFG["index_topk"]  # never the identity
    (e2e,) = [m for m in BENCH["end_to_end"] if m["name"] == "serve_out_tokens_per_s"]
    assert CELL in e2e["workloads"]
    on_cell = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", ())}
    assert on_cell >= set(NEW) | set(LISTED) | set(SHARED)  # the nineteen of ISSUE 55; a later PR may list it on more
    assert {m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", CELL)} == {"serve_out_tokens_per_s", "setup_s"}
    for m in BENCH["per_layer"]:  # the .batch names of the latent and routed readers stay the cells' that had them
        if m["name"].startswith(("moe_", "mla_", "gdn_", "eva_", "mhc_", "ssm_")) and m["name"].endswith(".batch"):
            assert CELL not in m["workloads"], m["name"]


def test_the_architecture_file_counts_the_program_s_parameters():
    from deepspeed_tpu.checkpoint.hf import config_from_hf

    # ISSUE 55's arithmetic
    assert ARCH.indexer_params(CFG) == 2048 * 4096 + 6144 * 128 + 6144 * 32 + 2 * 128 == 9_371_904
    assert ARCH.attention_params(CFG) - ARCH.indexer_params(CFG) == 165_022_208
    assert ARCH.expert_params(CFG) == 3 * 6144 * 2048 == 37_748_736
    routed_outside = 165_022_208 + 9_371_904 + 12_288 + 37_748_736 + 1_572_864 + 256
    assert routed_outside == 213_728_256
    dense = 165_022_208 + 9_371_904 + 12_288 + 3 * 6144 * 12288
    assert dense == 400_898_816
    total = dense + 5 * (routed_outside + 16 * 37_748_736) + 2 * 19_360 * 6144 + 6144
    assert ARCH.total_params(CFG) == config_from_hf(CFG).num_params() == total == 4_727_340_800
    assert (ARCH.layers(CFG), ARCH.heads(CFG), ARCH.kv_heads(CFG), ARCH.head_dim(CFG)) == (6, 64, 1, 256)
    assert (ARCH.routed_layers(CFG), ARCH.routed_experts(CFG), ARCH.held_experts(CFG), ARCH.experts_per_token(CFG)) == (
        5, 256, 16, 8)
    routing = program.routing(ARCH, HELD)
    assert (routing.layers, routing.experts, routing.k) == (5, 256, 8)  # picks in the PUBLISHED numbering
    # a token's products here: 8 / 16 expert visits a routed layer on average
    assert ARCH.matmul_params(CFG) == (6 * ARCH.attention_params(CFG) + 3 * 6144 * 12288
                                       + 5 * (6144 * 256 + 1.5 * 37_748_736) + 6144 * 19_360)
    # the uncut row: the published 744 B
    whole = dict(CFG, num_hidden_layers=78, first_k_dense_replace=3, n_routed_experts=256, vocab_size=154880)
    del whole["expert_parallel"]
    assert 743.8e9 < ARCH.total_params(whole) == config_from_hf(whole).num_params() < 744.0e9


def test_dsa_index_cost_by_hand():
    # a query at t scores t + 1 keys: one row of 4 queries from position 0 scores 1 + 2 + 3 + 4
    flops, bytes_ = ARCH.dsa_index_cost(CFG, [(0, 4)])
    # the row's 4 keys once for its 4 queries (256 B each), and the queries' 32 heads of 128
    assert flops == 10 * 2 * 32 * 128 and bytes_ == 4 * 256 + 4 * 32 * 128 * 2
    # a prompt of 4,096 from 0: 4096 x 4097 / 2 scores; two rows add; a decode step at 5,000 scores 5,001
    assert ARCH.dsa_index_cost(CFG, [(0, 4096)])[0] == 8_390_656 * 8192
    assert ARCH.dsa_index_cost(CFG, [(0, 4096), (5000, 1)])[0] == (8_390_656 + 5001) * 8192
    # one query at t reads its t + 1 keys at 256 B: memory-bound; a prompt's queries share them: compute-bound
    assert ARCH.dsa_index_cost(CFG, [(5000, 1)]) == (5001 * 8192.0, 5001 * 256 + 8192)
    flops, bytes_ = ARCH.dsa_index_cost(CFG, [(5000, 1)])
    assert flops / 197e12 < bytes_ / 819e9
    flops, bytes_ = ARCH.dsa_index_cost(CFG, [(0, 8192)])
    assert bytes_ == 8192 * 256 + 8192 * 8192 and flops / 197e12 > bytes_ / 819e9


def test_dsa_attend_cost_by_hand():
    per_token_flops, per_query_bytes = 2 * 64 * (576 + 512), 64 * (576 + 512) * 2
    # under index_topk a query attends every candidate: 1 + 2 + 3 + 4; the row's 4 tokens are read once
    assert ARCH.dsa_attend_cost(CFG, [(0, 4)]) == (10 * per_token_flops, 4 * 1152 + 4 * per_query_bytes)
    # across it: positions 2046, 2047, 2048, 2049 attend 2047, 2048, 2048, 2048
    assert ARCH.dsa_attend_cost(CFG, [(2046, 4)])[0] == (2047 + 3 * 2048) * per_token_flops
    # a prompt of 4,096: the first 2,048 queries all their candidates, the rest 2,048 each
    assert ARCH.dsa_attend_cost(CFG, [(0, 4096)])[0] == (2048 * 2049 // 2 + 2048 * 2048) * per_token_flops
    # a decode step at 6,000 attends 2,048 of its 6,001 candidates: 2.4 MB of latents
    assert ARCH.dsa_attend_cost(CFG, [(6000, 1)]) == (2048 * per_token_flops, 2048 * 1152 + per_query_bytes)
    flops, bytes_ = ARCH.dsa_attend_cost(CFG, [(6000, 1)])
    assert flops / 197e12 < bytes_ / 819e9  # one query: bound by the bytes of what it kept
    flops, bytes_ = ARCH.dsa_attend_cost(CFG, [(0, 8192)])
    assert bytes_ == 8192 * 1152 + 8192 * per_query_bytes and flops / 197e12 > bytes_ / 819e9  # a prompt: by FLOPs
    # what a walk over all t + 1 under a mask does at 8k over what this counts: over twice
    walked = 8192 * 8193 / 2
    assert 2.0 < walked / (ARCH.dsa_attend_cost(CFG, [(0, 8192)])[0] / per_token_flops) < 2.4


def test_the_share_s_routed_decode_cost_by_hand():
    # a (step, layer): the router's 256 columns, its bias and the shared expert once; all 16 held experts read
    flops, bytes_ = ARCH.routed_decode_cost(CFG, 16.0, 8.0, 1.0)
    assert bytes_ == (16 * 37_748_736 + 37_748_736 + 6144 * 256 + 256) * 2
    # a token: router and shared expert, and 8 / 16 visits to held experts on average
    assert flops == 2.0 * 8 * (0.5 * 37_748_736 + 37_748_736 + 6144 * 256 + 256)
    assert bytes_ / 819e9 > flops / 197e12  # memory-bound on the v5e


# ---- the readers on a synthetic trace ------------------------------------------------------------

STEP = "jit(step)/pool_scan/while/body/layer/attn/"
CHAIN = "jit(chain)/while/body/pool_scan/while/body/layer/attn/"


def instruction(program_name, name, op_name, seconds):
    return scopes.Instruction(program_name, name, "fusion", f"%{name} = bf16[8] fusion()", op_name, seconds, 1)


INSTRUCTIONS = (
    instruction("step", "dsa_index.1", STEP + "mla/dsa_index/dsa_index/pallas_call", 0.10),
    instruction("step", "fusion.2", STEP + "mla/dsa_index/idx_wq/dot_general", 0.02),
    instruction("step", "fusion.3", STEP + "mla/dsa_select/while/body/reduce_sum", 0.08),
    instruction("step", "dsa_paged_attn.4", STEP + "mla/dsa_attend/dsa_paged_attn/pallas_call", 0.50),
    instruction("step", "fusion.5", STEP + "mla/wq_b/dot_general", 0.30),            # latent attention's own
    instruction("step", "fusion.6", STEP + "mla/nodsa_index/add", 1.0),              # a component, not a substring
    instruction("chain", "fusion.7", CHAIN + "mla/dsa_index/dot_general", 0.03),
    instruction("chain", "fusion.8", CHAIN + "mla/dsa_select/top_k", 0.02),
    instruction("chain", "fusion.9", CHAIN + "mla/dsa_attend/gather", 0.05),
    instruction("train_step", "fusion.1", "jit(train_step)/layers/attn/dsa_index/dot_general", 9.0),  # no serving program
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


# the window is [10, 13]. Prefill A (two rows, 4,096 and 6,000 tokens) whole inside it, its run 10.12-11.02 with
# 0.04 s of dsa_index and 0.40 s of dsa_paged_attn; prefill B cut by the window's end; a prefill of a program
# that says nothing of what it fed (the parent's) is not paired; chains 5 and 6 say their counters
HOST = [
    event("bench:window", 10.0, 3.0),
    event("dstpu:serve:dispatch", 10.10, 0.01, kind="prefill", rows=2, live=2, tokens=10096, fed="0:4096 0:6000"),
    event("dstpu:serve:fetch", 10.11, 0.92, kind="prefill"),
    event("dstpu:serve:dispatch", 11.10, 0.01, kind="prefill", rows=2, live=2, tokens=9000),   # no ``fed``
    event("dstpu:serve:fetch", 11.11, 0.50, kind="prefill"),
    event("dstpu:serve:dispatch", 12.70, 0.01, kind="prefill", rows=2, live=2, tokens=12000, fed="0:6000 0:6000"),
    event("dstpu:serve:fetch", 12.71, 0.40, kind="prefill"),                                    # cut by the window's end
    event("dstpu:serve:accept", 11.80, 0.001, kind="chain", chain=5, emitted=64, tokens_scored=6000.0, tokens_kept=2048.0),
    event("dstpu:serve:accept", 11.90, 0.001, kind="chain", chain=6, emitted=64, tokens_scored=4240.0, tokens_kept=2048.0),
    event("dstpu:serve:accept", 11.95, 0.001, kind="chain", chain=7, emitted=64, experts_touched=3.0),  # says neither
    event("dstpu:serve:accept", 11.62, 0.001, kind="prefill", emitted=2, queries=12000, tokens_scored=3000.5,
          tokens_kept=1698.5),
    event("dstpu:serve:accept", 11.00, 0.001, kind="prefill", emitted=2),  # the program before the counters: says neither
]
MODULES = [event("jit_step(3)", 10.12, 0.90), event("jit_step(3)", 11.12, 0.45), event("jit_step(3)", 12.72, 0.50),
           event("jit_chain(7)", 11.70, 0.08)]
OPS = [op("dsa_index.1", 10.13, 0.04), op("dsa_paged_attn.4", 10.20, 0.40), op("fusion.5", 10.70, 0.20),
       op("dsa_index.1", 11.13, 0.03), op("dsa_paged_attn.4", 11.20, 0.30),     # the prefill that says nothing
       op("dsa_index.1", 12.73, 0.05),                                         # prefill B's: not paired
       op("fusion.7", 11.71, 0.01)]


@pytest.fixture
def synthetic(monkeypatch):
    path = "synthetic-dsa.xplane.pb"
    monkeypatch.setattr(spans, "trace_file", lambda run: path)
    monkeypatch.setattr(spans, "profile", lambda p: profile_of(HOST, MODULES, OPS))
    monkeypatch.setattr(scopes, "instructions", lambda p: INSTRUCTIONS)
    spans.read_spans.cache_clear()
    yield {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    spans.read_spans.cache_clear()


class Trace:
    busy_s, n_devices = 2.0, 1


def test_the_time_share_is_what_lies_under_the_three_scopes_in_the_two_serving_programs(synthetic):
    under = 0.10 + 0.02 + 0.08 + 0.50 + 0.03 + 0.02 + 0.05
    assert harness.load_reader("dsa_time_share.batch")(synthetic, Trace()) == pytest.approx(100 * under / 2.0)


def test_the_rooflines_pair_a_prefill_with_its_own_run_and_count_the_rows_it_fed(synthetic):
    calls = dsa.paired_prefills(synthetic)
    assert [(c["rows"], c["index_s"], c["attend_s"]) for c in calls] == [
        ([(0, 4096), (0, 6000)], pytest.approx(0.04), pytest.approx(0.40))]
    assert dsa.fed_rows("0:4096 17:1") == [(0, 4096), (17, 1)]
    # index: 4096 x 4097 / 2 + 6000 x 6001 / 2 scores of 8,192 FLOPs, in six layers; compute-bound by these counts?
    scored = 4096 * 4097 // 2 + 6000 * 6001 // 2
    flops, bytes_ = scored * 8192.0, 10096 * 256.0 + 10096 * 8192.0
    least = 6 * max(flops / 197e12, bytes_ / 819e9)
    assert flops / 197e12 > bytes_ / 819e9
    assert harness.load_reader("dsa_index_roofline.batch")(synthetic, Trace()) == pytest.approx(100 * least / 0.04)
    kept = (2048 * 2049 // 2 + 2048 * 2048) + (2048 * 2049 // 2 + 3952 * 2048)
    flops, bytes_ = kept * 2.0 * 64 * 1088, 10096 * 1152.0 + 10096 * 64 * 1088 * 2.0
    least = 6 * max(flops / 197e12, bytes_ / 819e9)
    assert harness.load_reader("dsa_attend_roofline.batch")(synthetic, Trace()) == pytest.approx(100 * least / 0.40)


def test_the_kept_share_is_kept_over_scored_of_the_calls_that_say_both_weighed_by_their_queries(synthetic):
    kept, scored = 64 * 2048.0 * 2 + 12000 * 1698.5, 64 * (6000.0 + 4240.0) + 12000 * 3000.5
    assert harness.load_reader("dsa_kept_share.batch")(synthetic, Trace()) == pytest.approx(100 * kept / scored)
    assert sorted(dsa.counters(synthetic)) == [(64.0, 4240.0, 2048.0), (64.0, 6000.0, 2048.0), (12000.0, 3000.5, 1698.5)]
    for name in NEW:
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics", name.rpartition(".")[0] + ".py"))


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_names_reads_nothing(name, tmp_path, monkeypatch):
    """The recorded v5e trace is of PR 25's program: no ``dsa_*`` scope or kernel, no ``fed`` on a dispatch, no
    counters on an accept. As the parent of this PR reads the new metrics."""
    path = unpack_span_trace(tmp_path)
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: path)
    scopes.report.cache_clear(), spans.report_idle.cache_clear()
    run = {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    trace = xplane.reduce_trace(path)
    assert harness.load_reader(name)(run, trace) is None
    assert dsa.paired_prefills(run) == [] and dsa.counters(run) == []


def test_the_entries_of_this_pr():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, unit, better, source, layer in [
            ("dsa_time_share.batch", "%", "higher", "device_trace", "model"),
            ("dsa_index_roofline.batch", "%", "higher", "device_trace", "kernels"),
            ("dsa_attend_roofline.batch", "%", "higher", "device_trace", "kernels"),
            ("dsa_kept_share.batch", "%", "lower", "program_counter", "serving loop")]:
        new = by_name[name]
        assert new == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer,
                       "moves": "serve_out_tokens_per_s", "workloads": new["workloads"]}
        assert new["workloads"][0] == CELL  # a list compared by its prefix: a later cell may follow
    for name in LISTED + SHARED:
        assert CELL in by_name[name]["workloads"] and by_name[name]["moves"] == "serve_out_tokens_per_s", name
    for name in CHAIN_ONLY:
        assert CELL not in by_name[name]["workloads"], name


def test_the_benchmark_only_grew():
    """Against the parent's ``BENCHMARK.json`` as git has it, where git is there: every entry that was there is
    there, in place, changed by nothing but cells appended to a list of cells."""
    import subprocess

    root = os.path.dirname(harness.BENCH_DIR)
    shown = subprocess.run(["git", "-C", root, "show", "8ac75ae078b4b45bfb22000abc67d578e101cf81:BENCHMARK.json"],
                           capture_output=True, text=True)
    if shown.returncode:
        pytest.skip("no git history here: the driver's check holds the same")
    before = json.loads(shown.stdout)
    assert {k: BENCH[k] for k in ("command", "paths", "run_seconds")} == {k: before[k] for k in ("command", "paths", "run_seconds")}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(BENCH[group]) >= len(before[group])
        for was, now in zip(before[group], BENCH[group]):
            grown = dict(now)
            if "workloads" in was:
                assert grown["workloads"][:len(was["workloads"])] == was["workloads"], was["name"]
                grown["workloads"] = was["workloads"]
            assert grown == was, was["name"]


def test_the_reference_imports_nothing_of_the_program():
    import ast

    path = os.path.join(harness.BENCH_DIR, "reference", "glm_moe_dsa.py")
    tree = ast.parse(open(path).read())
    imported = {(n.module if isinstance(n, ast.ImportFrom) else a.name) for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom)) for a in n.names}
    assert imported <= {"__future__", "importlib.util", "math", "os", "jax", "jax.numpy"}, imported
