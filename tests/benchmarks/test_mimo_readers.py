"""PR 61's files: the ``mimo_v2`` configuration (MiMo-V2.5: five sliding layers
of 8 kv heads on a ring of 9 pages with a sink to one global layer of 4 kv
heads, keys of 192 beside values of 128, a dense layer before the routed ones;
one chip's share of a sixteen-way expert-parallel stage), its cell, its
architecture file's counts, the two-width costs of ``lib/two_width.py`` by
hand-worked numbers (the key's 192 and the value's 128 counted apart, GQA's
keys and values read once a key-value head, the band's keys, the sink's
scalars), and the five new readers (``swa_ring_decode_roofline``,
``full_decode_roofline``, ``attn_decode_time_share``, ``kv_ring_bytes_share``,
``ring_page_turns``) on a synthetic trace whose numbers can be checked by hand
and on the recorded v5e trace of a program that has none of their names
(nothing found, nothing raised). The configuration's and the cell's facts are
held by MEMBERSHIP and by PREFIX, never by position or count: the next appended
cell, and the next cell appended to a list this one is on, breaks nothing here."""

import json
import os
import types

import pytest

from benchmarks.lib import harness, program, scopes, spans, two_width, xplane
from tests.benchmarks.conftest import config_rules, unpack_span_trace

BENCH = harness.load_benchmark()
CONFIG, CELL = "mimo-v2.5", "mimo-v2.5.serve.long-output-wave128"
NEW = ["swa_ring_decode_roofline.batch", "full_decode_roofline.batch", "attn_decode_time_share.batch",
       "kv_ring_bytes_share.batch", "ring_page_turns.batch"]
SHARED = ["decode_chain_ms.batch", "sched_host_ms.batch", "chain_live_rows.batch", "rows_per_chain.batch",
          "compiles_in_window.batch", "hbm_live_peak_gib.batch", "hbm_reserved_peak_gib.batch", "idle_share.batch",
          "pool_copy_time_share.batch", "stall_s.batch", "gc_pause_ms.batch", "unnamed_time_share.batch",
          "moe_time_share.ep", "moe_experts_roofline.ep", "moe_experts_touched.ep", "moe_held_visits.ep",
          "swa_time_share.batch", "attn_full_time_share.batch", "swa_pages_share.batch"]
HELD = harness.load_config(CONFIG)
CFG = program.published(HELD)
ARCH = harness.load_architecture("mimo_v2")
CUTS = {"num_hidden_layers": 7, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1], "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
        "n_routed_experts": 16, "vocab_size": 19072}
WIDTHS = {"hidden_size": 4096, "intermediate_size": 16384, "moe_intermediate_size": 2048, "num_attention_heads": 64,
          "num_key_value_heads": 4, "head_dim": 192, "v_head_dim": 128, "swa_num_attention_heads": 64,
          "swa_num_key_value_heads": 8, "swa_head_dim": 192, "swa_v_head_dim": 128, "sliding_window": 128,
          "partial_rotary_factor": 0.334, "attention_value_scale": 0.707, "num_experts_per_tok": 8,
          "rope_theta": 10000000, "swa_rope_theta": 10000}


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine: the driver's check holds the same")
    return next(row for row in map(json.loads, open(path)) if row["name"] == "MiMo-V2.5")


def test_the_configuration_is_the_catalog_row_with_the_five_cuts_and_nothing_else():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    config_rules(entry, HELD, BENCH)
    assert entry["reduced"] == list(CUTS) == [r["key"] for r in HELD["reduced"]]
    assert {k: CFG[k] for k in CUTS} == CUTS and {k: CFG[k] for k in WIDTHS} == WIDTHS
    assert CFG["expert_parallel"] == {"size": 16, "rank": 0} and "dtype" not in CFG and "torch_dtype" not in CFG
    assert HELD["source"] == entry["source"] == "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"
    assert HELD["architecture"] == CFG["model_type"] == "mimo_v2" and HELD["reference"] == "benchmarks/reference/mimo_v2.py"
    assert not set(CUTS) & set(ARCH.WIDTH_KEYS) and set(WIDTHS) - {"rope_theta", "swa_rope_theta"} <= set(ARCH.WIDTH_KEYS)
    for said in ("128 v5e chips", "8 pipeline stages of 16", "experts sixteen ways", "rank 0", "experts 0-15 of 256",
                 "eight slices of 19,072 rows", "Nothing stands in"):
        assert said in HELD["deployment"], said
    for said in ("block", "hybrid_layer_pattern", "sliding_window", "sink", "partial_rotary_factor",
                 "attention_value_scale", "attention_projection_layout", "router", "expert_parallel", "vocab_size",
                 "num_hidden_layers", "not_built", "weights", "max_position_embeddings", "page_layout"):
        assert len(HELD["assumed"][said]) > 40, said
    for other_reading in ("hybrid_layer_pattern", "sliding_window"):
        assert "other reading" in HELD["assumed"][other_reading]
    for unbuilt in ("vision tower", "audio encoder", "multi-token-prediction"):
        assert unbuilt in HELD["assumed"]["not_built"]
    for key in ("logit_rel_tol", "route_shortfall_tol"):
        read = HELD["check"]["readings"][key]
        assert read["sound_max"] < HELD["check"][key] < read["control_min"]
    published = catalog_row()["config"]
    assert HELD["reduced"] == [{"key": k, "published": published[k], "used": v} for k, v in CUTS.items()]
    assert {k: CFG[k] for k in published} == dict(published, **CUTS)  # every other number of the row under its key
    assert set(CFG) - set(published) == {"expert_parallel"}


def test_the_cell_is_issue_61_s():
    cell = harness.load_workload(CELL)
    assert cell["config"] == CONFIG and cell["kind"] == "serve" and cell["chips"] == 1
    (listed,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert listed == {"name": CELL, "config": CONFIG, "traffic": "serve.long-output-wave128", "chips": 1,
                      "why": cell["why"]}
    assert "16 of 256 experts" in cell["why"] and "7 of 48 layers" in cell["why"] and len(cell["why"]) <= 200
    assert cell["traffic"] == {"kind": "closed_waves", "wave": 128,
                               "prompt_len": {"dist": "uniform", "min": 1024, "max": 2048}, "output_tokens": 1024}
    engine = cell["engine"]
    assert {k: engine[k] for k in ("dtype", "kv_cache_dtype", "max_seqs", "decode_chain", "kv_block_size",
                                   "chunk_bucket", "flight_recorder", "hbm_check")} == {
        "dtype": "bf16", "kv_cache_dtype": "bf16", "max_seqs": 128, "decode_chain": 8, "kv_block_size": 16,
        "chunk_bucket": 2048, "flight_recorder": True, "hbm_check": "off"}
    assert engine["max_seq_len"] >= 2048 + 1024 and engine["max_ragged_batch_size"] % engine["chunk_bucket"] == 0
    rows, chunk = cell["warm"]["prefill"][0]
    assert (chunk, rows * chunk, cell["warm"]["chain_rows"]) == (2048, engine["max_ragged_batch_size"], [128])
    # the check's prompts (half the bucket to the bucket) are the traffic's own range, all far past the window
    assert engine["chunk_bucket"] // 2 == cell["traffic"]["prompt_len"]["min"] >= 8 * CFG["sliding_window"]
    # the pool in two classes of two geometries holds every seat's whole ring and context; in ONE class of the
    # larger geometry for every layer the same rows would take more than the chip has beside the weights
    bs, seats = engine["kv_block_size"], engine["max_seqs"]
    ring_page, global_page = ARCH.page_bytes(CFG, "sliding", bs), ARCH.page_bytes(CFG, "global", bs)
    assert (ring_page, global_page) == (80 * 1024, 40 * 1024)
    held_global, held_ring = ARCH.two_class_pages(CFG, [engine["max_seq_len"]] * seats, bs)
    assert (held_global, held_ring) == (2 * seats * 194, 5 * seats * 9)
    assert held_global * global_page + held_ring * ring_page == engine["kv_pool_bytes"] == 2_506_096_640
    assert ARCH.one_class_pages(CFG, [engine["max_seq_len"]] * seats, bs) * ring_page > 14e9
    for said in ("max_seqs", "kv_pool_bytes", "max_seq_len", "chunk_bucket", "max_ragged_batch_size", "decode_chain",
                 "compiled_peak"):
        assert len(cell["assumed"][said]) > 40, said
    (e2e,) = [m for m in BENCH["end_to_end"] if m["name"] == "serve_out_tokens_per_s"]
    assert CELL in e2e["workloads"]
    on_cell = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", ())}
    assert on_cell >= set(NEW) | set(SHARED)
    assert {m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", CELL)} == {"serve_out_tokens_per_s", "setup_s"}
    for m in BENCH["per_layer"]:  # the other architectures' own readers stay the cells' that had them
        if m["name"].startswith(("mla_", "gdn_", "eva_", "mhc_", "ssm_", "dsa_", "paged_", "layer_matmul")):
            assert CELL not in m["workloads"], m["name"]


def test_the_architecture_file_counts_the_program_s_parameters_by_kind():
    # ISSUE 61's arithmetic
    assert ARCH.attention_params(CFG, "global") == 4096 * 192 * (64 + 4) + 4096 * 128 * (4 + 64) == 89_128_960
    assert ARCH.attention_params(CFG, "sliding") == 4096 * 192 * (64 + 8) + 4096 * 128 * (8 + 64) + 64 == 94_371_904
    assert ARCH.expert_params(CFG) == 3 * 4096 * 2048 == 25_165_824 and ARCH.dense_mlp_params(CFG) == 201_326_592
    assert (ARCH.layers(CFG), ARCH.sliding_layers(CFG), ARCH.full_layers(CFG), ARCH.dense_layers(CFG)) == (7, 5, 2, 1)
    assert (ARCH.routed_layers(CFG), ARCH.held_experts(CFG), ARCH.routed_experts(CFG), ARCH.experts_per_token(CFG)) == (
        6, 16, 256, 8)
    assert (ARCH.heads(CFG), ARCH.kv_heads(CFG), ARCH.kv_heads(CFG, "sliding"), ARCH.head_dim(CFG), ARCH.value_dim(CFG)) == (
        64, 4, 8, 192, 128)
    routed = 4096 * 256 + 256 + 16 * 25_165_824
    total = (2 * 89_128_960 + 5 * 94_371_904 + 201_326_592 + 6 * routed + 7 * 2 * 4096 + 4096 + 2 * 19072 * 4096)
    assert ARCH.total_params(CFG) == ARCH.parameter_count(CFG) == total == 3_429_955_392
    # a token meets a sixteenth of its eight picks here: half an expert a routed layer
    assert ARCH.matmul_params(CFG) == int(2 * 89_128_960 + 5 * (94_371_904 - 64) + 201_326_592
                                          + 6 * (4096 * 256 + 0.5 * 25_165_824) + 4096 * 19072)
    assert (ARCH.cache_bytes_per_token_layer(CFG, "sliding"), ARCH.cache_bytes_per_token_layer(CFG, "global")) == (5120, 2560)


def test_the_two_width_decode_cost_by_hand():
    # one row, one step, 100 keys seen, 64 heads over 8 kv heads, keys of 192 beside values of 128, a sink
    flops, bytes_ = two_width.decode_cost(100, 1, 1, heads=64, kv_heads=8, key_dim=192, value_dim=128, sink=True)
    assert flops == 100 * 64 * (2 * 192 + 2 * 128) == 4_096_000  # q . k over 192, p . v over 128: NOT 4 x 192
    # a key and its value once a KEY-VALUE head (8, not 64): 100 x 8 x 320 x 2 B; q in and o out; 64 sinks of 4 B
    assert bytes_ == 100 * 8 * (192 + 128) * 2 + 64 * (192 + 128) * 2 + 64 * 4 == 512_000 + 40_960 + 256
    # what a count that charges the value at the key's width, or the keys once a query head, would claim
    assert bytes_ < 100 * 8 * 2 * 192 * 2 + 64 * 2 * 192 * 2 and bytes_ < 100 * 64 * 320 * 2
    no_sink = two_width.decode_cost(100, 1, 1, heads=64, kv_heads=8, key_dim=192, value_dim=128)
    assert no_sink == (flops, bytes_ - 256)
    # the architecture's two kinds: a ring of 128 keys at 5 KiB a token, a global context at 2.5 KiB a token
    flops, bytes_ = ARCH.paged_decode_cost(CFG, "sliding", 128.0, 1.0, 1.0)
    assert (flops, bytes_) == (128 * 64 * 640, 128 * 5120 + 64 * 320 * 2 + 256)
    flops, bytes_ = ARCH.paged_decode_cost(CFG, "global", 3000.0, 1.0, 1.0)
    assert (flops, bytes_) == (3000 * 64 * 640, 3000 * 2560 + 64 * 320 * 2)  # 4 kv heads, no sink
    assert bytes_ / 819e9 > flops / 197e12  # memory-bound on the v5e: groups of 16 do 32 FLOP a byte of page
    # ``lib/swa.py``'s decode reading scales the keys' part alone by a chain's ``ring_tokens``
    assert ARCH.swa_decode_cost(CFG, [1]) == (64 * 640.0, 5120.0) and ARCH.swa_decode_cost(CFG, [500])[1] == 128 * 5120.0


def test_the_two_width_prefill_cost_by_hand():
    assert [two_width.attended(n, 128) for n in (1, 128, 130)] == [1, 128 * 129 // 2, 128 * 129 // 2 + 2 * 128]
    assert two_width.attended(130, None) == 130 * 131 // 2
    # a prompt of 1,500 tokens under the band of 128: 8,256 + 1,372 x 128 pairs, not 1,500 x 1,501 / 2
    pairs = 128 * 129 // 2 + (1500 - 128) * 128
    flops, bytes_ = ARCH.swa_prefill_cost(CFG, [1500])
    assert flops == pairs * 64 * (2 * 192 + 2 * 128) and pairs == 183_872
    assert bytes_ == 1500 * (64 + 8) * (192 + 128) * 2 + 64 * 4  # q and o a query head, k and v a kv head, the sinks
    flops, bytes_ = ARCH.full_prefill_cost(CFG, [1500, 1024])
    assert flops == (1500 * 1501 // 2 + 1024 * 1025 // 2) * 64 * 640
    assert bytes_ == (1500 + 1024) * (64 + 4) * 320 * 2


def test_the_share_s_routed_decode_cost_by_hand():
    # a (step, layer): the router's 256 columns once, NO shared expert; all 16 held experts read
    flops, bytes_ = ARCH.routed_decode_cost(CFG, 16.0, 128.0, 1.0)
    assert bytes_ == (16 * 25_165_824 + 4096 * 256) * 2
    # a token: the router, and 8 / 16 = half a visit to a held expert on average
    assert flops == 2.0 * 128 * (0.5 * 25_165_824 + 4096 * 256)
    assert bytes_ / 819e9 > flops / 197e12  # memory-bound on the v5e


# ---- the readers on a synthetic trace ------------------------------------------------------------

CHAIN = "jit(chain)/while/body/pool_scan/while/body/layer/attn/"


def instruction(program_name, name, op_name, seconds):
    return scopes.Instruction(program_name, name, "fusion", f"%{name} = bf16[8] fusion()", op_name, seconds, 1)


INSTRUCTIONS = (
    instruction("chain", "swa_paged_attn.7", CHAIN + "swa/swa_paged_attn/pallas_call", 0.06),
    instruction("chain", "fusion.9", CHAIN + "swa/kv_write/scatter", 0.01),
    instruction("chain", "paged_attn.8", CHAIN + "attn_full/paged_attn/pallas_call", 0.12),
    instruction("chain", "fusion.5", CHAIN + "wq/dot_general", 0.31),                        # a projection: under neither
    instruction("step", "swa_flash_fwd.1", "jit(step)/pool_scan/while/body/layer/attn/swa/swa_flash_fwd/pallas_call", 0.30),
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


# the window is [10, 13]. Chain 5 (128 rows of 8 steps at contexts near 2,000) whole inside it; chain 6 cut by the
# window's end; chain 4 of a program that says no ``global_tokens`` (the parent's) is not paired
SAID_5 = dict(ring_tokens=128 * 8 * 128, global_tokens=128 * 8 * 2000, row_steps=128 * 8, ring_turns=64,
              ring_pages=128 * 5 * 9, global_pages=128 * 2 * 126, one_class_pages=128 * 7 * 126,
              ring_bytes_held=128 * 5 * 9 * 81920, global_bytes_held=128 * 2 * 126 * 40960)
HOST = [
    event("bench:window", 10.0, 3.0),
    event("dstpu:serve:dispatch", 10.70, 0.005, kind="chain", chain=4, rows=128, live=128, k=8, ring_tokens=131072),
    event("dstpu:serve:fetch", 10.71, 0.13, kind="chain", chain=4),
    event("dstpu:serve:dispatch", 11.70, 0.005, kind="chain", chain=5, rows=128, live=128, k=8, **SAID_5),
    event("dstpu:serve:fetch", 11.71, 0.13, kind="chain", chain=5),
    event("dstpu:serve:dispatch", 12.95, 0.005, kind="chain", chain=6, rows=128, live=128, k=8, **SAID_5),
    event("dstpu:serve:fetch", 12.96, 0.13, kind="chain", chain=6),                             # cut by the window's end
    event("dstpu:serve:dispatch", 10.10, 0.01, kind="prefill", rows=8, live=8, tokens=12000,
          ring_bytes_held=8 * 5 * 9 * 81920, global_bytes_held=8 * 2 * 94 * 40960),
]
MODULES = [event("jit_chain(7)", 10.71, 0.12), event("jit_chain(7)", 11.71, 0.12), event("jit_chain(7)", 12.96, 0.12)]
OPS = [op("swa_paged_attn.7", 10.72, 0.011), op("paged_attn.8", 10.74, 0.03),       # chain 4's: not paired
       op("swa_paged_attn.7", 11.72, 0.012), op("paged_attn.8", 11.74, 0.032), op("fusion.5", 11.78, 0.02),
       op("swa_paged_attn.7", 12.97, 0.012)]                                          # chain 6's: not paired


@pytest.fixture
def synthetic(monkeypatch):
    path = "synthetic-mimo.xplane.pb"
    monkeypatch.setattr(spans, "trace_file", lambda run: path)
    monkeypatch.setattr(spans, "profile", lambda p: profile_of(HOST, MODULES, OPS))
    monkeypatch.setattr(spans, "report_idle", lambda p: None)
    monkeypatch.setattr(scopes, "instructions", lambda p: INSTRUCTIONS)
    spans.read_spans.cache_clear()
    yield {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    spans.read_spans.cache_clear()


class Trace:
    busy_s, n_devices = 2.0, 1


def test_the_decode_rooflines_pair_a_chain_with_its_own_run_and_count_each_kind_s_own_widths(synthetic):
    calls = two_width.paired_chains(synthetic)
    assert [(c["swa_paged_attn"], c["paged_attn"]) for c in calls] == [(pytest.approx(0.012), pytest.approx(0.032))]
    # five sliding layers: 131,072 keys at 5 KiB, 1,024 queries in and out, 8 calls' sinks; memory-bound
    ring = 5 * (131072 * 5120 + 1024 * 64 * 320 * 2 + 8 * 256) / 819e9
    assert harness.load_reader("swa_ring_decode_roofline.batch")(synthetic, Trace()) == pytest.approx(100 * ring / 0.012)
    # two global layers: 2,048,000 keys at 2.5 KiB
    table = 2 * (2048000 * 2560 + 1024 * 64 * 320 * 2) / 819e9
    assert harness.load_reader("full_decode_roofline.batch")(synthetic, Trace()) == pytest.approx(100 * table / 0.032)
    # neither can pass 100 while the kernel reads no faster than the chip's peak
    assert 100 * ring / 0.012 < 100 and 100 * table / 0.032 < 100
    # an architecture file without the two-width cost: nothing, whatever the chains
    assert two_width.decode_roofline(dict(synthetic, architecture=types.SimpleNamespace()), "sliding") is None


def test_the_attention_share_is_what_lies_under_the_two_scopes_in_the_chain_program_over_that_program(synthetic):
    assert harness.load_reader("attn_decode_time_share.batch")(synthetic, Trace()) == pytest.approx(
        100 * (0.06 + 0.01 + 0.12) / (0.06 + 0.01 + 0.12 + 0.31))  # the step program's kernel is no part of either


def test_the_bytes_share_and_the_turns_are_read_off_the_dispatch_spans(synthetic):
    ring = (2 * 128 + 8) * 5 * 9 * 81920  # the two chains in the window that say them and the prefill
    table = 2 * 128 * 2 * 126 * 40960 + 8 * 2 * 94 * 40960
    assert harness.load_reader("kv_ring_bytes_share.batch")(synthetic, Trace()) == pytest.approx(100 * ring / (ring + table))
    assert two_width.ring_turns(synthetic) == [0.5, 0.5]  # 64 pages a sliding layer over 128 live rows, a chain
    assert harness.load_reader("ring_page_turns.batch")(synthetic, Trace()) == 0.5
    for name in NEW:
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics", name.rpartition(".")[0] + ".py"))


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_names_reads_nothing(name, tmp_path, monkeypatch):
    """The recorded v5e trace is of PR 25's program: no ``swa`` or ``attn_full`` scope, no ``swa_paged_attn``, no
    ``global_tokens``, bytes by class or turns on a dispatch. As the parent of this PR reads the new metrics."""
    path = unpack_span_trace(tmp_path)
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: path)
    scopes.report.cache_clear(), spans.report_idle.cache_clear()
    run = {"workload": {"name": CELL}, "config": CFG, "architecture": ARCH, "device_kind": "TPU v5 lite", "calls": []}
    trace = xplane.reduce_trace(path)
    assert harness.load_reader(name)(run, trace) is None
    assert two_width.paired_chains(run) == [] and two_width.bytes_held(run) == [] and two_width.ring_turns(run) == []


def test_the_entries_of_this_pr():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, unit, better, source, layer in [
            ("swa_ring_decode_roofline.batch", "%", "higher", "device_trace", "kernels"),
            ("full_decode_roofline.batch", "%", "higher", "device_trace", "kernels"),
            ("attn_decode_time_share.batch", "%", "lower", "device_trace", "model"),
            ("kv_ring_bytes_share.batch", "%", "lower", "program_counter", "serving loop"),
            ("ring_page_turns.batch", "pages", "higher", "program_counter", "serving loop")]:
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
    shown = subprocess.run(["git", "-C", root, "show", "273de105fecb8e2a39aebec1982a4a1e1ed9f8ff:BENCHMARK.json"],
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

    path = os.path.join(harness.BENCH_DIR, "reference", "mimo_v2.py")
    tree = ast.parse(open(path).read())
    imported = {(n.module if isinstance(n, ast.ImportFrom) else a.name) for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom)) for a in n.names}
    assert imported <= {"__future__", "jax", "jax.numpy"}, imported
