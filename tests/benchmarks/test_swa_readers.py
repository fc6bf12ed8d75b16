"""PR 57's files: the ``cohere2_moe`` configuration (Command A+: three
sliding-window layers on a ring of pages to one full layer, one parallel block
with a routed and an averaged shared MLP; one chip's share of an eight-way
expert-parallel stage), its cell, its architecture file's counts,
``swa_prefill_cost``, ``full_prefill_cost``, ``swa_decode_cost`` and the pages by
class by hand, and the new readers (``swa_time_share``, ``attn_full_time_share``,
``swa_prefill_roofline``, ``full_prefill_roofline``, ``swa_pages_share``,
``swa_decode_step_ms`` over every chain of a run, and the unlisted decode roofline, ``lib/swa.py::decode_roofline``) on a synthetic trace whose numbers can be
checked by hand and on the recorded v5e trace of a program that has none of
their names (nothing found, nothing raised). The configuration's and the cell's
facts are held by MEMBERSHIP and by PREFIX, never by position or count: the
next appended cell, and the next cell appended to a list this one is on, breaks
nothing here."""

import json
import os
import types

import pytest

from benchmarks.lib import harness, program, scopes, spans, swa, xplane
from tests.benchmarks.conftest import config_rules, unpack_span_trace

BENCH = harness.load_benchmark()
CONFIG, CELL = "command-a-plus-05-2026", "command-a-plus-05-2026.serve.long-prompt-wave8"
NEW = ["swa_time_share.batch", "attn_full_time_share.batch", "swa_prefill_roofline.batch",
       "full_prefill_roofline.batch", "swa_pages_share.batch", "swa_decode_step_ms.batch"]
# reads whole chains alone, and a traced window of two prefills holds none: no entry and no file under metrics/
# (test_contract.py wants every reader there listed); ``tools/swa_controls.py --control decode_roofline`` prints it
UNLISTED = "swa_decode_roofline.batch"
SHARED = ["idle_share.batch", "hbm_live_peak_gib.batch", "hbm_reserved_peak_gib.batch", "unnamed_time_share.batch",
          "compiles_in_window.batch", "gc_pause_ms.batch", "stall_s.batch", "moe_time_share.ep", "rows_per_chain.batch"]
HELD = harness.load_config(CONFIG)
CFG = program.published(HELD)
ARCH = harness.load_architecture("cohere2_moe")
PERIOD = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]
CATALOG = {  # the catalog row's ``config`` (model-configs guide, architectures.jsonl), its two nested groups apart
    "attention_bias": False, "expert_selection_fn": "sigmoid", "first_k_dense_replace": 0, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05, "layer_switch": 4,
    "logit_scale": 1, "max_position_embeddings": 200000, "model_type": "cohere2_moe", "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4, "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj", "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None, "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096, "tf_legacy_loss": False,
    "tie_word_embeddings": True, "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False, "use_qk_norm": False, "vocab_size": 262144}
CUTS = {"num_hidden_layers": 4, "layer_types": PERIOD, "num_experts": 16, "vocab_size": 32768}


def test_the_configuration_is_the_catalog_row_with_the_four_cuts_and_nothing_else():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    config_rules(entry, HELD, BENCH)
    assert entry["reduced"] == list(CUTS) == [r["key"] for r in HELD["reduced"]]
    published = dict(CATALOG, layer_types=PERIOD * 8)
    assert HELD["reduced"] == [{"key": k, "published": published[k], "used": v} for k, v in CUTS.items()]
    assert HELD["source"] == entry["source"] == (
        "https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json")
    assert {k: CFG[k] for k in CATALOG} == dict(CATALOG, **{k: v for k, v in CUTS.items() if k in CATALOG})
    assert CFG["layer_types"] == PERIOD  # one whole period: every kind in its published ratio
    assert CFG["rope_parameters"] == {"rope_theta": 50000, "rope_type": "default"}  # the nested group, copied whole
    assert CFG["expert_parallel"] == {"size": 8, "rank": 0}
    assert set(CFG) - set(CATALOG) == {"layer_types", "rope_parameters", "expert_parallel"}  # no top-level dtype
    assert HELD["architecture"] == "cohere2_moe" and HELD["reference"] == "benchmarks/reference/cohere2_moe.py"
    assert not set(CUTS) & set(ARCH.WIDTH_KEYS)  # no width is cut
    for width in ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                  "num_experts_per_tok", "num_shared_experts", "sliding_window", "expert_parallel"):
        assert width in ARCH.WIDTH_KEYS, width
    for said in ("64 v5e chips", "8 pipeline stages of 4 layers", "expert parallelism", "rank 0 of stage 0",
                 "experts 0-15", "eighth of the tied vocabulary"):
        assert said in HELD["deployment"], said
    for said in ("shared_expert_combination_strategy", "intermediate_size", "sliding_window", "rope",
                 "first_k_dense_replace", "router", "norm", "logit_scale", "expert_parallel", "vocab_size",
                 "num_hidden_layers", "vision", "weights", "max_position_embeddings"):
        assert len(HELD["assumed"][said]) > 40, said
    assert "not taken" in HELD["assumed"]["shared_expert_combination_strategy"]  # the other reading is named
    for key in ("logit_rel_tol", "route_shortfall_tol"):
        read = HELD["check"]["readings"][key]
        assert read["sound_max"] < HELD["check"][key] < read["control_min"]


def test_the_cell_is_issue_57_s():
    cell = harness.load_workload(CELL)
    assert cell["config"] == CONFIG and cell["kind"] == "serve" and cell["chips"] == 1
    (listed,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert listed == {"name": CELL, "config": CONFIG, "traffic": "serve.long-prompt-wave8", "chips": 1,
                      "why": cell["why"]}
    assert "ring of 257 pages" in cell["why"] and "band of 4,096" in cell["why"] and len(cell["why"]) <= 200
    assert cell["traffic"] == {"kind": "closed_waves", "wave": 8,
                               "prompt_len": {"dist": "uniform", "min": 8192, "max": 16384}, "output_tokens": 128}
    engine = cell["engine"]
    assert {k: engine[k] for k in ("dtype", "kv_cache_dtype", "max_seqs", "decode_chain", "kv_block_size",
                                   "chunk_bucket", "max_ragged_batch_size", "kv_pool_bytes", "max_seq_len",
                                   "flight_recorder", "hbm_check")} == {
        "dtype": "bf16", "kv_cache_dtype": "bf16", "max_seqs": 8, "decode_chain": 8, "kv_block_size": 16,
        "chunk_bucket": 16384, "max_ragged_batch_size": 16384, "kv_pool_bytes": 2 ** 30, "max_seq_len": 16512,
        "flight_recorder": True, "hbm_check": "off"}
    assert cell["warm"] == {"prefill": [[1, 16384]], "chain_rows": [8], "chain_prompt_len": 8192}
    # every prompt is past the window in every sliding layer, where correct is decided too
    assert min(cell["traffic"]["prompt_len"]["min"], engine["chunk_bucket"] // 2) >= 2 * CFG["sliding_window"]
    # the pool in two classes fits what the traffic can hold; in one class it would not
    contexts = [16384 + 128] * 8
    page = 16 * ARCH.cache_bytes_per_token_layer(CFG)
    assert page == 65536 and ARCH.two_class_pages(CFG, contexts, 16) * page <= engine["kv_pool_bytes"]
    assert ARCH.one_class_pages(CFG, contexts, 16) * page == 4 * 8 * 1032 * 65536 > 2 * engine["kv_pool_bytes"]
    (e2e,) = [m for m in BENCH["end_to_end"] if m["name"] == "serve_out_tokens_per_s"]
    assert CELL in e2e["workloads"]
    on_cell = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", ())}
    assert on_cell >= set(NEW) | set(SHARED) and UNLISTED not in on_cell
    assert {m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", CELL)} == {"serve_out_tokens_per_s", "setup_s"}
    for m in BENCH["per_layer"]:  # the .batch names of the other architectures' readers stay the cells' that had them
        if m["name"].startswith(("moe_", "mla_", "gdn_", "eva_", "mhc_", "ssm_", "dsa_")) and m["name"].endswith(".batch"):
            assert CELL not in m["workloads"], m["name"]


def test_the_architecture_file_counts_the_program_s_parameters():
    from deepspeed_tpu.checkpoint.hf import config_from_hf

    # ISSUE 57's arithmetic
    assert ARCH.attention_params(CFG) == 4096 * 128 * (2 * 128 + 2 * 8) == 142_606_336
    assert ARCH.shared_params(CFG) == 4 * 3 * 4096 * 4096 == 201_326_592
    assert ARCH.expert_params(CFG) == 3 * 4096 * 4096 == 50_331_648
    layer = 142_606_336 + 201_326_592 + 4096 * 128 + 16 * 50_331_648 + 4096
    assert layer == 1_149_767_680
    total = 4 * layer + 32768 * 4096 + 4096
    assert ARCH.total_params(CFG) == ARCH.parameter_count(CFG) == config_from_hf(CFG).num_params() == total == 4_733_292_544
    assert (ARCH.layers(CFG), ARCH.heads(CFG), ARCH.kv_heads(CFG), ARCH.head_dim(CFG)) == (4, 128, 8, 128)
    assert (ARCH.sliding_layers(CFG), ARCH.full_layers(CFG), ARCH.window(CFG)) == (3, 1, 4096)
    assert (ARCH.routed_layers(CFG), ARCH.routed_experts(CFG), ARCH.held_experts(CFG), ARCH.experts_per_token(CFG)) == (
        4, 128, 16, 8)
    routing = program.routing(ARCH, HELD)
    assert (routing.layers, routing.experts, routing.k) == (4, 128, 8)  # picks in the PUBLISHED numbering
    # a token's products here: 8 / 8 = one expert visit a layer on average
    assert ARCH.matmul_params(CFG) == 4 * (142_606_336 + 201_326_592 + 4096 * 128 + 50_331_648) + 4096 * 32768
    # the uncut row: the published 218 B, of which a token meets 25 B
    whole = dict(CFG, num_hidden_layers=32, layer_types=PERIOD * 8, num_experts=128, vocab_size=262144)
    del whole["expert_parallel"]
    assert 217e9 < ARCH.total_params(whole) == config_from_hf(whole).num_params() < 219e9
    assert 24e9 < ARCH.matmul_params(whole) < 26e9


def test_the_prefill_costs_by_hand():
    per_pair = 4 * 128 * 128  # q . k and p . v, 2 x 128 FLOPs each, over 128 query heads
    per_token = (2 * 128 + 2 * 8) * 128 * 2  # q and o a query head, k and v a key-value head, bf16
    # under the window every query attends every key up to its own: 1 + 2 + 3 + 4
    assert ARCH.swa_prefill_cost(CFG, [4]) == ARCH.full_prefill_cost(CFG, [4]) == (10 * per_pair, 4 * per_token)
    # past it: the first 4,096 queries the triangle, the rest 4,096 each; THE BAND'S WORK, not the square's
    band = 4096 * 4097 // 2 + (16384 - 4096) * 4096
    assert ARCH.swa_prefill_cost(CFG, [16384]) == (band * per_pair, 16384 * per_token)
    assert ARCH.full_prefill_cost(CFG, [16384])[0] == (16384 * 16385 // 2) * per_pair
    # ISSUE 57's reckoning: three sliding layers 11.5 TFLOP under the band, 26.4 without it; the full layer 8.8
    assert 11.4e12 < 3 * ARCH.swa_prefill_cost(CFG, [16384])[0] < 11.6e12
    assert 26.3e12 < 3 * ARCH.full_prefill_cost(CFG, [16384])[0] < 26.5e12
    assert 8.7e12 < ARCH.full_prefill_cost(CFG, [16384])[0] < 8.9e12
    # prompts add; a prompt is compute-bound on the v5e
    assert ARCH.swa_prefill_cost(CFG, [8192, 16384])[0] == (
        ARCH.swa_prefill_cost(CFG, [8192])[0] + ARCH.swa_prefill_cost(CFG, [16384])[0])
    flops, bytes_ = ARCH.swa_prefill_cost(CFG, [16384])
    assert flops / 197e12 > bytes_ / 819e9


def test_the_decode_cost_and_the_pages_by_class_by_hand():
    # a row reads min(context, 4,096) tokens of 4,096 B a sliding layer; memory-bound
    assert ARCH.cache_bytes_per_token_layer(CFG) == 2 * 8 * 128 * 2 == 4096
    assert ARCH.swa_decode_cost(CFG, [100]) == (4.0 * 128 * 128 * 100, 100 * 4096.0)
    assert ARCH.swa_decode_cost(CFG, [12000, 16000, 3000])[1] == (4096 + 4096 + 3000) * 4096.0
    flops, bytes_ = ARCH.swa_decode_cost(CFG, [12000] * 8)
    assert flops / 197e12 < bytes_ / 819e9
    # pages: a ring of 257 a sliding layer whatever the context, the full layer's as many as the context has
    assert ARCH.two_class_pages(CFG, [16512], 16) == 1032 + 3 * 257
    assert ARCH.one_class_pages(CFG, [16512], 16) == 4 * 1032
    assert ARCH.two_class_pages(CFG, [100], 16) == ARCH.one_class_pages(CFG, [100], 16) == 4 * 7
    # ISSUE 57's 0.95 GB where one class would hold 2.16 GB; the share the reader reports at these shapes: ~44
    assert 0.94e9 < ARCH.two_class_pages(CFG, [16512] * 8, 16) * 65536 < 0.96e9
    assert 2.15e9 < ARCH.one_class_pages(CFG, [16512] * 8, 16) * 65536 < 2.17e9
    assert 43 < 100 * ARCH.two_class_pages(CFG, [16512] * 8, 16) / ARCH.one_class_pages(CFG, [16512] * 8, 16) < 45


def test_the_share_s_routed_decode_cost_by_hand():
    # a (step, layer): the router's 128 columns and the four shared experts once; all 16 held experts read
    flops, bytes_ = ARCH.routed_decode_cost(CFG, 16.0, 8.0, 1.0)
    assert bytes_ == (16 * 50_331_648 + 201_326_592 + 4096 * 128) * 2
    # a token: router and shared experts, and 8 / 8 = one visit to a held expert on average
    assert flops == 2.0 * 8 * (50_331_648 + 201_326_592 + 4096 * 128)
    assert bytes_ / 819e9 > flops / 197e12  # memory-bound on the v5e


# ---- the readers on a synthetic trace ------------------------------------------------------------

STEP = "jit(step)/pool_scan/while/body/layer/attn/"
CHAIN = "jit(chain)/while/body/pool_scan/while/body/layer/attn/"


def instruction(program_name, name, op_name, seconds):
    return scopes.Instruction(program_name, name, "fusion", f"%{name} = bf16[8] fusion()", op_name, seconds, 1)


INSTRUCTIONS = (
    instruction("step", "swa_flash_fwd.1", STEP + "swa/swa_flash_fwd/pallas_call", 0.30),
    instruction("step", "fusion.2", STEP + "swa/kv_write/scatter", 0.02),
    instruction("step", "flash_fwd.3", STEP + "attn_full/flash_fwd/pallas_call", 0.20),
    instruction("step", "fusion.4", STEP + "attn_full/kv_write/scatter", 0.01),
    instruction("step", "fusion.5", STEP + "wq/dot_general", 0.30),                  # a projection: under neither
    instruction("step", "fusion.6", STEP + "noswa/add", 1.0),                        # a component, not a substring
    instruction("chain", "swa_paged_attn.7", CHAIN + "swa/swa_paged_attn/pallas_call", 0.06),
    instruction("chain", "paged_attn.8", CHAIN + "attn_full/paged_attn/pallas_call", 0.05),
    instruction("train_step", "fusion.1", "jit(train_step)/layers/attn/swa/dot_general", 9.0),  # no serving program
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


# the window is [10, 13]. Prefill A (one row of 12,000 tokens) whole inside it, its run 10.12-10.82 with 0.24 s of
# swa_flash_fwd and 0.16 s of flash_fwd; prefill B cut by the window's end; a prefill of a program that says nothing
# of what it fed (the parent's) is not paired; chain 5 whole inside it, chain 6 cut by the window's end
PAGES_A = dict(ring_pages=3 * 257, global_pages=750, one_class_pages=3000)
PAGES_5 = dict(ring_pages=8 * 3 * 257, global_pages=8 * 800, one_class_pages=8 * 3200)
HOST = [
    event("bench:window", 10.0, 3.0),
    event("dstpu:serve:dispatch", 10.10, 0.01, kind="prefill", rows=1, live=1, tokens=12000, fed="0:12000", **PAGES_A),
    event("dstpu:serve:fetch", 10.11, 0.72, kind="prefill"),
    event("dstpu:serve:dispatch", 11.10, 0.01, kind="prefill", rows=1, live=1, tokens=9000),   # no ``fed``, no pages
    event("dstpu:serve:fetch", 11.11, 0.50, kind="prefill"),
    event("dstpu:serve:dispatch", 12.70, 0.01, kind="prefill", rows=1, live=1, tokens=16000, fed="0:16000"),
    event("dstpu:serve:fetch", 12.71, 0.40, kind="prefill"),                                    # cut by the window's end
    event("dstpu:serve:dispatch", 11.70, 0.005, kind="chain", chain=5, rows=8, live=8, k=8, ring_tokens=8 * 8 * 4096,
          **PAGES_5),
    event("dstpu:serve:fetch", 11.71, 0.09, kind="chain", chain=5),
    event("dstpu:serve:dispatch", 12.95, 0.005, kind="chain", chain=6, rows=8, live=8, k=8, ring_tokens=8 * 8 * 4096),
    event("dstpu:serve:fetch", 12.96, 0.09, kind="chain", chain=6),                             # cut likewise
]
MODULES = [event("jit_step(3)", 10.12, 0.70), event("jit_step(3)", 11.12, 0.45), event("jit_step(3)", 12.72, 0.50),
           event("jit_chain(7)", 11.71, 0.08), event("jit_chain(7)", 12.96, 0.08)]
OPS = [op("swa_flash_fwd.1", 10.13, 0.24), op("flash_fwd.3", 10.40, 0.16), op("fusion.5", 10.60, 0.20),
       op("swa_flash_fwd.1", 11.13, 0.20), op("flash_fwd.3", 11.35, 0.10),      # the prefill that says nothing
       op("swa_flash_fwd.1", 12.73, 0.25),                                       # prefill B's: not paired
       op("swa_paged_attn.7", 11.72, 0.012), op("paged_attn.8", 11.74, 0.01),
       op("swa_paged_attn.7", 12.97, 0.012)]                                     # chain 6's: not paired


@pytest.fixture
def synthetic(monkeypatch):
    path = "synthetic-swa.xplane.pb"
    monkeypatch.setattr(spans, "trace_file", lambda run: path)
    monkeypatch.setattr(spans, "profile", lambda p: profile_of(HOST, MODULES, OPS))
    monkeypatch.setattr(spans, "report_idle", lambda p: None)
    monkeypatch.setattr(scopes, "instructions", lambda p: INSTRUCTIONS)
    spans.read_spans.cache_clear()
    yield {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    spans.read_spans.cache_clear()


class Trace:
    busy_s, n_devices = 2.0, 1


def test_the_time_shares_are_what_lies_under_each_scope_in_the_two_serving_programs(synthetic):
    assert harness.load_reader("swa_time_share.batch")(synthetic, Trace()) == pytest.approx(100 * (0.30 + 0.02 + 0.06) / 2.0)
    assert harness.load_reader("attn_full_time_share.batch")(synthetic, Trace()) == pytest.approx(
        100 * (0.20 + 0.01 + 0.05) / 2.0)


def test_the_prefill_rooflines_pair_a_prefill_with_its_own_run_and_count_the_band_s_work(synthetic):
    calls = swa.paired_prefills(synthetic)
    assert [(c["rows"], c["swa_flash_fwd"], c["flash_fwd"]) for c in calls] == [
        ([(0, 12000)], pytest.approx(0.24), pytest.approx(0.16))]
    assert swa.fed_rows("0:8192 17:1") == [(0, 8192), (17, 1)]
    band = 4096 * 4097 // 2 + (12000 - 4096) * 4096
    least = 3 * band * 4 * 128 * 128 / 197e12  # three sliding layers, compute-bound
    assert harness.load_reader("swa_prefill_roofline.batch")(synthetic, Trace()) == pytest.approx(100 * least / 0.24)
    least = (12000 * 12001 // 2) * 4 * 128 * 128 / 197e12  # the one full layer
    assert harness.load_reader("full_prefill_roofline.batch")(synthetic, Trace()) == pytest.approx(100 * least / 0.16)
    # neither can pass 100 while the kernel runs no faster than the chip's peak: the band is counted, not the square
    assert harness.load_reader("swa_prefill_roofline.batch")(synthetic, Trace()) < 100


def test_the_decode_roofline_reads_whole_chains_alone_and_is_not_listed(synthetic):
    calls = swa.paired_chains(synthetic)
    assert [(c["ring_tokens"], c["swa_paged_attn"]) for c in calls] == [(8 * 8 * 4096.0, pytest.approx(0.012))]
    least = 3 * 8 * 8 * 4096 * 4096 / 819e9  # three sliding layers' rings, memory-bound
    assert swa.decode_roofline(synthetic) == pytest.approx(100 * least / 0.012)
    assert UNLISTED not in {m["name"] for m in BENCH["per_layer"]}
    assert not os.path.exists(os.path.join(harness.BENCH_DIR, "metrics", "swa_decode_roofline.py"))


def test_the_decode_step_is_read_off_every_chain_of_the_run_and_needs_no_traced_chain(synthetic):
    """The benchmark's own spans around ``engine.decode_chain`` over the whole
    run: fifteen chains of 8 steps at 8 rows and a last one of 7, one of them
    slow; prefill calls and a chain that emitted nothing are no part of it."""
    chain = {"kind": "decode_chain", "rows": 8, "traced": False, "context_tokens": 0.0}
    calls = [dict(chain, t0=10.0 + i, t1=10.0 + i + 0.096, row_steps=64) for i in range(15)]
    calls += [dict(chain, t0=30.0, t1=30.0 + 0.0847, row_steps=56), dict(chain, t0=31.0, t1=31.9, row_steps=64),
              dict(chain, t0=32.0, t1=32.5, row_steps=0),
              {"kind": "prefill", "rows": 1, "tokens": 12000, "traced": False, "t0": 1.0, "t1": 1.63}]
    read = harness.load_reader("swa_decode_step_ms.batch")
    assert read(dict(synthetic, calls=calls), Trace()) == pytest.approx(12.0)  # the median of 15 x 12.0, 12.1, 112.5
    assert read(dict(synthetic, calls=calls[-1:]), Trace()) is None and read(synthetic, Trace()) is None
    # a configuration whose architecture file knows no sliding kind: nothing, whatever its chains
    assert read(dict(synthetic, calls=calls, architecture=types.SimpleNamespace()), Trace()) is None


def test_the_pages_share_is_two_classes_over_one_summed_over_the_calls_that_say_them(synthetic):
    held = (3 * 257 + 750) + (8 * 3 * 257 + 8 * 800)
    assert harness.load_reader("swa_pages_share.batch")(synthetic, Trace()) == pytest.approx(
        100 * held / (3000 + 8 * 3200))
    assert len(swa.pages_held(synthetic)) == 2
    for name in NEW:
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics", name.rpartition(".")[0] + ".py"))


@pytest.mark.parametrize("name", NEW + [UNLISTED])
def test_a_program_without_the_names_reads_nothing(name, tmp_path, monkeypatch):
    read = (lambda run, trace: swa.decode_roofline(run)) if name == UNLISTED else harness.load_reader(name)
    """The recorded v5e trace is of PR 25's program: no ``swa`` or ``attn_full`` scope, no ``swa_*`` kernel, no
    ``fed``, ``ring_tokens`` or pages on a dispatch. As the parent of this PR reads the new metrics."""
    path = unpack_span_trace(tmp_path)
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: path)
    scopes.report.cache_clear(), spans.report_idle.cache_clear()
    run = {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    trace = xplane.reduce_trace(path)
    assert read(run, trace) is None
    assert swa.paired_prefills(run) == [] and swa.paired_chains(run) == [] and swa.pages_held(run) == []


def test_the_entries_of_this_pr():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, unit, better, source, layer in [
            ("swa_time_share.batch", "%", "higher", "device_trace", "model"),
            ("attn_full_time_share.batch", "%", "higher", "device_trace", "model"),
            ("swa_prefill_roofline.batch", "%", "higher", "device_trace", "kernels"),
            ("full_prefill_roofline.batch", "%", "higher", "device_trace", "kernels"),
            ("swa_pages_share.batch", "%", "lower", "program_counter", "serving loop"),
            ("swa_decode_step_ms.batch", "ms", "lower", "host_clock", "model")]:
        new = by_name[name]
        assert new == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer,
                       "moves": "serve_out_tokens_per_s", "workloads": new["workloads"]}
        assert new["workloads"][0] == CELL  # a list compared by its prefix: a later cell may follow
    for name in SHARED:
        assert CELL in by_name[name]["workloads"] and by_name[name]["moves"] == "serve_out_tokens_per_s", name


def test_the_benchmark_only_grew():
    """Against the parent's ``BENCHMARK.json`` as git has it, where git is there: every entry that was there is
    there, in place, changed by nothing but cells appended to a list of cells."""
    import subprocess

    root = os.path.dirname(harness.BENCH_DIR)
    shown = subprocess.run(["git", "-C", root, "show", "03d344db31b1c75d9cb438304da3eb27ee07aad3:BENCHMARK.json"],
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

    path = os.path.join(harness.BENCH_DIR, "reference", "cohere2_moe.py")
    tree = ast.parse(open(path).read())
    imported = {(n.module if isinstance(n, ast.ImportFrom) else a.name) for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom)) for a in n.names}
    assert imported <= {"__future__", "jax", "jax.numpy"}, imported
