"""PR 51's files: the ROUTED ``granitemoehybrid`` configuration (one chip's share
of a two-way expert-parallel stage), its cell, its architecture file's counts,
``ssm_decode_cost``, ``ssd_scan_cost`` and the share's ``routed_decode_cost`` by
hand, the five readers the cell is listed on beside the shared nine
(``ssm_time_share``, ``ssm_decode_roofline``, ``moe_time_share``,
``moe_experts_roofline``, ``moe_experts_touched``, each through its stem and
this architecture's file, with NO reader code of theirs added) and the one new
reader ``moe_held_visits`` on a synthetic trace whose numbers can be checked by
hand and on the recorded v5e trace of a program that has none of their names
(nothing found, nothing raised). The configuration's and the cell's facts are
held by MEMBERSHIP, never by position or count: the next appended cell, and
the next cell appended to a list this one is on, breaks nothing here."""

import json
import os
import types

import pytest

from benchmarks.lib import harness, program, scopes, spans, ssm, xplane
from tests.benchmarks.conftest import config_rules, unpack_span_trace

BENCH = harness.load_benchmark()
CONFIG, CELL = "granite-4.0-h-small", "granite-4.0-h-small.serve.long-output-wave64"
QWEN_CELL = "qwen3-next-80b-a3b.serve.long-output-wave128"
NEW = ["moe_held_visits.ep"]
LISTED = ["ssm_time_share.batch", "ssm_decode_roofline.batch", "moe_time_share.ep", "moe_experts_roofline.ep",
          "moe_experts_touched.ep"]
SHARED = ["compiles_in_window.batch", "decode_chain_ms.batch", "hbm_live_peak_gib.batch",
          "hbm_reserved_peak_gib.batch", "idle_share.batch", "rows_per_chain.batch",
          "pool_copy_time_share.batch", "sched_host_ms.batch", "chain_live_rows.batch"]
HELD = harness.load_config(CONFIG)
CFG = program.published(HELD)
ARCH = harness.load_architecture("granitemoehybrid_routed")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
CATALOG = {  # the catalog row's ``config`` (model-configs guide, architectures.jsonl), ``layer_types`` apart
    "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 768, "logits_scaling": 16, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 10, "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 72,
    "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 1536, "tie_word_embeddings": True, "vocab_size": 100352}
CUTS = {"num_hidden_layers": 10, "num_local_experts": 36, "vocab_size": 50176}


def test_the_configuration_is_the_catalog_row_with_the_three_cuts_and_nothing_else():
    """Three cuts, and beside ``num_hidden_layers`` the nested group it cuts
    with it: ``layer_types`` (a changed group is named by its top-level key)."""
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    config_rules(entry, HELD, BENCH)
    assert entry["reduced"] == list(CUTS) + ["layer_types"] == [r["key"] for r in HELD["reduced"]]
    assert HELD["reduced"] == [{"key": k, "published": CATALOG[k], "used": v} for k, v in CUTS.items()] + [
        {"key": "layer_types", "published": PERIOD * 4, "used": PERIOD}]
    assert HELD["source"] == entry["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json"
    assert {k: CFG[k] for k in CATALOG} == dict(CATALOG, **CUTS)  # every other key as published
    assert CFG["layer_types"] == PERIOD  # one whole period: the published list's first ten
    assert CFG["expert_parallel"] == {"size": 2, "rank": 0}
    # no top-level ``dtype``: the parameters are drawn in float32 and rounded to bf16 once by the harness, because
    # normals DRAWN in bf16 carry a mean of -1.77% of a standard deviation in every matrix (``assumed.dtype``)
    assert set(CFG) - set(CATALOG) == {"layer_types", "expert_parallel"}
    assert "float32" in HELD["assumed"]["dtype"] and "float32" in HELD["assumed"]["weights"]
    assert HELD["architecture"] == "granitemoehybrid_routed" and HELD["reference"] == "benchmarks/reference/granitemoehybrid_routed.py"
    assert not (set(CUTS) | {"layer_types"}) & set(ARCH.WIDTH_KEYS)  # no width is cut; the share's size is a width
    for width in ("intermediate_size", "shared_intermediate_size", "num_experts_per_tok", "expert_parallel",
                  "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv"):
        assert width in ARCH.WIDTH_KEYS, width
    for said in ("8 v5e chips", "4 pipeline stages of 10 layers", "2 chips", "rank 0", "experts 0-35 of 72", "50,176"):
        assert said in HELD["deployment"], said
    for said in ("intermediate_size", "shared_intermediate_size", "router", "expert_parallel", "vocab_size",
                 "num_hidden_layers", "state_dtype", "in_proj_order", "gate_before_norm", "conv_over_xBC", "attention",
                 "multipliers", "dtype", "weights", "max_position_embeddings"):
        assert len(HELD["assumed"][said]) > 40, said
    for key in ("logit_rel_tol", "route_shortfall_tol"):
        read = HELD["check"]["readings"][key]
        assert read["sound_max"] < HELD["check"][key] < read["control_min"]


def test_the_cell_is_issue_51_s():
    cell = harness.load_workload(CELL)
    assert cell["config"] == CONFIG and cell["kind"] == "serve" and cell["chips"] == 1
    (listed,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert listed == {"name": CELL, "config": CONFIG, "traffic": "serve.long-output-wave64", "chips": 1,
                      "why": cell["why"]}
    assert "2x their share" in cell["why"] and "320 visits" in cell["why"] and len(cell["why"]) <= 200
    # the traffic of the dense granite cell letter for letter: the two differ in the architecture alone
    assert cell["traffic"] == harness.load_workload("granite-4.0-h-micro.serve.long-output-batch")["traffic"] == {
        "kind": "closed_waves", "wave": 64, "prompt_len": {"dist": "uniform", "min": 64, "max": 256},
        "output_tokens": 512}
    engine = cell["engine"]
    assert {k: engine[k] for k in ("dtype", "kv_cache_dtype", "max_seqs", "decode_chain", "kv_block_size",
                                   "row_bucket", "chunk_bucket", "kv_pool_bytes", "max_seq_len", "hbm_check",
                                   "flight_recorder")} == {
        "dtype": "bf16", "kv_cache_dtype": "bf16", "max_seqs": 64, "decode_chain": 8, "kv_block_size": 16,
        "row_bucket": 8, "chunk_bucket": 256, "kv_pool_bytes": 268435456, "max_seq_len": 1024,
        "hbm_check": "off", "flight_recorder": True}
    assert "max_ragged_batch_size" not in engine  # one (64, 256) prefill a wave: a row is its state slot
    assert cell["warm"] == {"prefill": [[64, 256]], "chain_rows": [64], "chain_prompt_len": 256}
    # what the traffic can hold fits what the engine is given: 4,096 B a token in the one attention layer
    pages = -(-(256 + 512) // 16) + 1
    assert 64 * pages * 16 * 4096 <= engine["kv_pool_bytes"] and 256 + 512 <= engine["max_seq_len"]
    # the prefill's routed layers take the sorted dispatch, a decode step's the decode product
    assert 64 * 256 >= 2 * ARCH.routed_experts(CFG) > 64
    (e2e,) = [m for m in BENCH["end_to_end"] if m["name"] == "serve_out_tokens_per_s"]
    assert CELL in e2e["workloads"]
    on_cell = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", ())}
    assert on_cell >= set(NEW) | set(LISTED) | set(SHARED)  # the fifteen of ISSUE 51; a later PR may list it on more
    assert {m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", CELL)} == {"serve_out_tokens_per_s", "setup_s"}
    # the .batch names of the routed readers stay the cells' that hold every expert; no DeltaNet metric here
    for m in BENCH["per_layer"]:
        if m["name"].startswith(("moe_", "mla_", "gdn_", "eva_", "mhc_")) and m["name"].endswith(".batch"):
            assert CELL not in m["workloads"], m["name"]


def test_the_architecture_file_counts_the_program_s_parameters():
    from deepspeed_tpu.checkpoint.hf import config_from_hf

    # ISSUE 51's arithmetic
    assert ARCH.ssm_params(CFG) == 4096 * 16768 + 8192 * 4096 + 8448 * 4 + 8448 + 3 * 128 + 8192 == 102_286_976
    assert ARCH.attention_params(CFG) == 2 * 4096 * 128 * (32 + 8) == 41_943_040
    assert (ARCH.expert_params(CFG), ARCH.shared_params(CFG), ARCH.router_params(CFG)) == (9_437_184, 18_874_368, 294_912)
    mamba_layer, attention_layer = 102_286_976 + 8_192 + 18_874_368 + 294_912, 41_943_040 + 8_192 + 18_874_368 + 294_912
    assert (mamba_layer, attention_layer) == (121_464_448, 61_120_512)
    in_experts, outside, embed = 10 * 36 * 9_437_184, 9 * mamba_layer + attention_layer, 50_176 * 4096 + 4096
    assert (in_experts, outside, embed) == (3_397_386_240, 1_154_300_544, 205_524_992)
    assert ARCH.total_params(CFG) == config_from_hf(CFG).num_params() == in_experts + outside + embed == 4_757_211_776
    assert (ARCH.layers(CFG), ARCH.ssm_layers(CFG), ARCH.attention_layers(CFG), ARCH.heads(CFG), ARCH.kv_heads(CFG),
            ARCH.head_dim(CFG)) == (10, 9, 1, 32, 8, 128)
    assert (ARCH.routed_layers(CFG), ARCH.routed_experts(CFG), ARCH.held_experts(CFG), ARCH.experts_per_token(CFG)) == (
        10, 72, 36, 10)
    # a token's products here: every mixer's projections, router and shared MLP, 10 / 2 expert visits a layer, the head
    assert ARCH.matmul_params(CFG) == (9 * ARCH.ssm_matmul_params(CFG) + 41_943_040 + 10 * (294_912 + 18_874_368)
                                       + 10 * 5 * 9_437_184 + 4096 * 50_176)
    routing = program.routing(ARCH, HELD)
    assert (routing.layers, routing.experts, routing.k) == (10, 72, 10)  # picks in the PUBLISHED numbering
    # the uncut row: all 72 experts in all 40 layers, the whole vocabulary
    whole = dict(CFG, num_hidden_layers=40, layer_types=PERIOD * 4, num_local_experts=72, vocab_size=100352)
    del whole["expert_parallel"]
    assert ARCH.total_params(whole) == config_from_hf(whole).num_params() == 32_207_337_984
    assert (ARCH.routed_experts(whole), ARCH.held_experts(whole)) == (72, 72)


def test_ssm_decode_cost_by_hand():
    assert ARCH.state_bytes(CFG) == 128 * 64 * 128 * 4 + 3 * 8448 * 2 == 4_194_304 + 50_688 == 4_244_992
    flops, bytes_ = ARCH.ssm_decode_cost(CFG, 1.0, 0.0)
    # a live row a step: its state and tail read once and written once in each of 9 layers: 76.4 MB
    assert bytes_ == 9 * 2 * 4_244_992 == 76_409_856
    assert flops == 9 * (2 * 4096 * (16768 + 8192) + 6 * 128 * 64 * 128)
    # a step: the 9 mixers' weights once, in bf16: 1.84 GB
    assert ARCH.ssm_decode_cost(CFG, 0.0, 1.0) == (0.0, 9 * 102_286_976 * 2.0) == (0.0, 1_841_165_568.0)
    # the cell's full step: 64 rows: 4.89 GB of state (ISSUE 51) + the weights
    _, full = ARCH.ssm_decode_cost(CFG, 64.0, 1.0)
    assert full == 64 * 76_409_856 + 1_841_165_568 and 4.89e9 < 64 * 76_409_856 < 4.90e9
    assert 8.2e-3 < full / 819e9 < 8.3e-3


def test_ssd_scan_cost_by_hand():
    # one sequence of one chunk of 256: a group's scores 256^2 x 128, a head 256^2 x 64 + 4 x 256 x 64 x 128
    flops, bytes_ = ARCH.ssd_scan_cost(CFG, 1.0, 256)
    assert flops == 256 * 256 * 128 + 128 * (256 * 256 * 64 + 4 * 256 * 64 * 128)
    assert bytes_ == 256 * (2 * 8192 * 2 + 2 * 128 * 2 + 128 * 4) + 2 * 128 * 64 * 128 * 4
    # 300 tokens: a chunk of 256 and one of 44; rows multiply
    more, _ = ARCH.ssd_scan_cost(CFG, 64.0, 300)
    assert more == 64 * (flops + 44 * 44 * 128 + 128 * (44 * 44 * 64 + 4 * 44 * 64 * 128))


def test_the_share_s_routed_decode_cost_by_hand():
    # a (step, layer): the router's 72 columns and the shared MLP once (37.7 MB + 0.6); all 36 held experts read
    flops, bytes_ = ARCH.routed_decode_cost(CFG, 36.0, 64.0, 1.0)
    assert bytes_ == (36 * 9_437_184 + 18_874_368 + 294_912) * 2 and 9_437_184 * 2 == 18_874_368
    # a token: router and shared MLP, and 10 / 2 visits to held experts on average
    assert flops == 2.0 * 64 * (5 * 9_437_184 + 18_874_368 + 294_912)
    # ISSUE 51's step: 10 layers x 36 experts x 18.9 MB = 6.79 GB, + 0.38 GB of shared MLPs and routers
    _, step = ARCH.routed_decode_cost(CFG, 10 * 36.0, 10 * 64.0, 10.0)
    assert 6.79e9 < 10 * 36 * 9_437_184 * 2 < 6.80e9 and 7.17e9 < step < 7.19e9
    assert step > ARCH.routed_decode_cost(CFG, 10 * 36.0, 10 * 64.0, 10.0)[0] / 240  # memory-bound on the v5e


# ---- the readers on a synthetic trace ------------------------------------------------------------

CHAIN = "jit(chain)/while/body/pool_scan/while/body/layer/"


def instruction(program_name, name, op_name, seconds):
    return scopes.Instruction(program_name, name, "fusion", f"%{name} = bf16[8] fusion()", op_name, seconds, 1)


INSTRUCTIONS = (
    instruction("chain", "ssm_update.1", CHAIN + "ssm/ssm_update/ssm_update/pallas_call", 0.40),
    instruction("chain", "fusion.2", CHAIN + "ssm/ssm_in_proj/dot_general", 0.10),
    instruction("chain", "moe_decode.3", CHAIN + "moe/moe_experts/moe_decode/pallas_call", 0.30),
    instruction("chain", "fusion.4", CHAIN + "moe/moe_shared/w_up/dot_general", 0.06),
    instruction("chain", "fusion.5", CHAIN + "moe/moe_router/dot_general", 0.04),
    instruction("chain", "fusion.7", CHAIN + "nossm/ssm_like/add", 1.0),  # a component, not a substring
    instruction("chain", "fusion.8", CHAIN + "gdn/gdn_update/mul", 1.0),  # another mixer's scope
    instruction("step", "fusion.1", "jit(step)/pool_scan/while/body/layer/ssm/ssm_scan/dot_general", 0.20),
    instruction("step", "fusion.6", "jit(step)/pool_scan/while/body/layer/moe/moe_experts/jit(gmm)/pallas_call", 0.10),
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


# the window is [10, 13]. Chain 5 dispatched ahead (during chain 4's run), whole inside the window: 64 rows x 8
# steps, its run 10.62-10.82 with 0.05 + 0.01 s under ssm; chain 6 the last of a wave: 64 rows x 7 steps, its
# run 10.90-11.08 with 0.04 s; chain 4 dispatched before the window started; chain 7 fetched after its end
HOST = [
    event("bench:window", 10.0, 3.0),
    event("dstpu:serve:fetch", 9.90, 0.15, kind="chain", chain=4),            # cut by the window's start
    event("dstpu:serve:dispatch", 10.50, 0.01, kind="chain", rows=64, live=64, k=8, chain=5, ahead=1, state_rows=512),
    event("dstpu:serve:fetch", 10.60, 0.225, kind="chain", chain=5),
    event("dstpu:serve:dispatch", 10.70, 0.01, kind="chain", rows=64, live=64, k=8, chain=6, ahead=1, state_rows=448),
    event("dstpu:serve:fetch", 10.85, 0.24, kind="chain", chain=6),
    event("dstpu:serve:dispatch", 12.80, 0.01, kind="chain", rows=64, live=64, k=8, chain=7, ahead=0, state_rows=512),
    event("dstpu:serve:fetch", 12.81, 0.30, kind="chain", chain=7),           # cut by the window's end
    event("dstpu:serve:accept", 10.83, 0.001, kind="chain", chain=5, emitted=512, experts_touched=36.0,
          experts_read=36.0, held_visits=322.0),
    event("dstpu:serve:accept", 11.09, 0.001, kind="chain", chain=6, emitted=448, experts_touched=35.5,
          experts_read=36.0, held_visits=318.0),
    event("dstpu:serve:accept", 11.20, 0.001, kind="prefill", emitted=64, held_visits=9999.0),  # no chain's
    event("dstpu:serve:accept", 11.30, 0.001, kind="chain", chain=9, emitted=64, experts_touched=50.0),  # holds every expert
]
MODULES = [event("jit_chain(7)", 9.95, 0.10), event("jit_chain(7)", 10.51, 0.10), event("jit_chain(7)", 10.62, 0.20),
           event("jit_chain(7)", 10.90, 0.18), event("jit_chain(7)", 12.82, 0.20), event("jit_step(3)", 11.90, 0.30)]
OPS = [op("ssm_update.1", 9.96, 0.05),                                       # chain 4's: not paired
       op("ssm_update.1", 10.52, 0.05),                                      # the chain before 5, in 5's own span: not its run
       op("ssm_update.1", 10.63, 0.05), op("fusion.2", 10.70, 0.01), op("moe_decode.3", 10.72, 0.03),
       op("fusion.7", 10.76, 0.02),
       op("ssm_update.1", 10.91, 0.04),
       op("fusion.1", 11.91, 0.20),                                          # a prefill's fusion.1: another program's
       op("ssm_update.1", 12.83, 0.05)]                                      # chain 7's: not paired


@pytest.fixture
def synthetic(monkeypatch):
    path = "synthetic-granite-routed.xplane.pb"
    monkeypatch.setattr(spans, "trace_file", lambda run: path)
    monkeypatch.setattr(spans, "profile", lambda p: profile_of(HOST, MODULES, OPS))
    monkeypatch.setattr(scopes, "instructions", lambda p: INSTRUCTIONS)
    spans.read_spans.cache_clear()
    yield {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    spans.read_spans.cache_clear()


class Trace:
    busy_s, n_devices = 2.0, 1


def test_the_time_shares_are_what_lies_under_ssm_and_under_moe_in_the_two_serving_programs(synthetic):
    assert harness.load_reader("ssm_time_share.batch")(synthetic, Trace()) == pytest.approx(100 * 0.70 / 2.0)
    assert harness.load_reader("moe_time_share.ep")(synthetic, Trace()) == pytest.approx(100 * 0.50 / 2.0)


def test_the_ssm_roofline_pairs_chains_with_their_own_runs_at_this_file_s_cost(synthetic):
    chains = ssm.paired_chains(synthetic)
    assert [(c["state_rows"], c["steps"]) for c in chains] == [(512.0, 8.0), (448.0, 7.0)]
    assert [c["ssm_s"] for c in chains] == pytest.approx([0.06, 0.04])
    _, bytes_ = ARCH.ssm_decode_cost(CFG, 960.0, 15.0)
    assert bytes_ == 960 * 76_409_856 + 15 * 1_841_165_568
    least = bytes_ / 819e9  # memory-bound
    assert harness.load_reader("ssm_decode_roofline.batch")(synthetic, Trace()) == pytest.approx(100 * least / 0.10)


def test_the_routed_readers_serve_the_share_through_their_stem(synthetic):
    """``harness.load_reader`` strips the last suffix: ``moe_*.ep`` and
    ``ssm_*.batch`` are the existing readers, fed THIS architecture's cost
    functions and the chains' HELD experts; no reader of theirs was added."""
    for name in LISTED:
        assert not os.path.exists(os.path.join(harness.BENCH_DIR, "metrics", name + ".py"))
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics", name.rpartition(".")[0] + ".py"))
    # the chains whose accept says experts_touched and whose dispatch lies in the window: 5 and 6
    assert harness.load_reader("moe_experts_touched.ep")(synthetic, Trace()) == pytest.approx(35.75)
    # chains 5 and 6: 8 and 7 steps of 10 routed layers at 36 and 35.5 held experts read
    experts = (36.0 * 8 + 35.5 * 7) * 10
    flops, bytes_ = ARCH.routed_decode_cost(CFG, experts, (512 + 448) * 10, 15 * 10)
    assert bytes_ > flops / 240  # memory-bound on the v5e
    scopes_s = 0.30 + 0.06 + 0.04  # moe_experts + moe_shared + moe_router in the chain program
    assert harness.load_reader("moe_experts_roofline.ep")(synthetic, Trace()) == pytest.approx(
        100 * bytes_ / 819e9 / scopes_s)


def test_held_visits_is_the_median_over_the_chains_that_say_it(synthetic):
    """Chains 5 and 6 say ``held_visits``; a prefill's span and a chain of a
    program that holds every expert (no such arg) are left out."""
    assert harness.load_reader("moe_held_visits.ep")(synthetic, Trace()) == pytest.approx(320.0)
    assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics", "moe_held_visits.py"))


@pytest.mark.parametrize("name", NEW + LISTED)
def test_a_program_without_the_names_reads_nothing(name, tmp_path, monkeypatch):
    """The recorded v5e trace is of PR 25's program: no ``ssm`` or ``moe`` scope,
    no ``state_rows`` on a dispatch, no ``held_visits`` on an accept. As a
    parent without the share reads the new metric."""
    path = unpack_span_trace(tmp_path)
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: path)
    scopes.report.cache_clear(), spans.report_idle.cache_clear()
    run = {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    trace = xplane.reduce_trace(path)
    assert harness.load_reader(name)(run, trace) is None
    assert ssm.paired_chains(run) == []


def test_the_entries_of_this_pr():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    new = by_name["moe_held_visits.ep"]
    assert new == {"name": "moe_held_visits.ep", "unit": "count", "better": "higher", "source": "program_span",
                   "layer": "serving loop", "moves": "serve_out_tokens_per_s", "workloads": new["workloads"]}
    assert {CELL, QWEN_CELL} <= set(new["workloads"])  # the two share cells: 320 visits here, 160 there
    for name in LISTED + SHARED:
        assert CELL in by_name[name]["workloads"] and by_name[name]["moves"] == "serve_out_tokens_per_s", name
    assert QWEN_CELL in by_name["moe_time_share.ep"]["workloads"]  # and the cells that were there are there
    assert "granite-4.0-h-micro.serve.long-output-batch" in by_name["ssm_time_share.batch"]["workloads"]


def test_the_benchmark_only_grew():
    """Against the parent's ``BENCHMARK.json`` as git has it, where git is
    there: every entry that was there is there, in place, changed by nothing
    but cells appended to a list of cells (this PR's, and whatever later PRs
    append behind it)."""
    import subprocess

    root = os.path.dirname(harness.BENCH_DIR)
    shown = subprocess.run(["git", "-C", root, "show", "accb7d60f7b91037bca71eda970fb744aa79b789:BENCHMARK.json"],
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

    path = os.path.join(harness.BENCH_DIR, "reference", "granitemoehybrid_routed.py")
    tree = ast.parse(open(path).read())
    imported = {(n.module if isinstance(n, ast.ImportFrom) else a.name) for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom)) for a in n.names}
    assert imported <= {"__future__", "importlib.util", "os", "jax", "jax.numpy"}, imported
