"""The three readers of the EVA serving cell (``eva_time_share``,
``eva_paged_roofline``, ``eva_rows_per_context_token``; ``benchmarks/lib/eva.py``)
on a synthetic trace whose numbers can be checked by hand, on the recorded v5e
trace of a program that has none of their names (nothing found, nothing
raised), the architecture file's three costs by hand, and the real files of
the configuration and the cell they were written for."""

import os
import types

import pytest

from benchmarks.lib import eva, harness, program, scopes, spans, xplane
from tests.benchmarks.conftest import config_rules, unpack_span_trace

BENCH = harness.load_benchmark()
CELL = "evabyte.serve.long-batch"
NEW = [m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
ROW = 2 * 32 * 128 * 2  # a cache row of one layer: K and V, 32 heads of 128, bf16


def instruction(program_name, name, op_name, seconds):
    return scopes.Instruction(program_name, name, "fusion", f"%{name} = bf16[8] fusion()", op_name, seconds, 1)


LAYER = "jit(chain)/while/body/pool_scan/while/body/layer/"
INSTRUCTIONS = (
    instruction("chain", "fusion.1", LAYER + "eva/dot_general", 0.30),  # the projections and RoPE
    instruction("chain", "paged_attn.2", LAYER + "eva/paged_attn", 0.50),  # the kernel, under the scope
    instruction("chain", "fusion.3", LAYER + "eva/kv_write/scatter", 0.02),
    instruction("chain", "fusion.4", LAYER + "eva/cond/branch_1_fun/eva_close/reduce", 0.01),
    instruction("step", "flash_fwd.5", "jit(step)/pool_scan/while/body/layer/eva/eva_prefill/flash_fwd", 0.10),
    instruction("chain", "fusion.6", LAYER + "mlp/dot_general", 0.80),  # not attention
    instruction("chain", "fusion.7", LAYER + "evaluate/add", 1.0),  # a component, not a substring
    instruction("train_step", "fusion.8", "jit(train_step)/layers/eva/dot_general", 9.0),  # no serving program
)


class Trace:
    busy_s, n_devices = 2.0, 1

    def op_seconds(self, pick):
        ops = [types.SimpleNamespace(module="chain", seconds=0.5,
                                     text=f"%paged_attn.2 = bf16[24,1,4096] custom-call(), "
                                          f"custom_call_target=\"{xplane.PALLAS_TARGET}\""),
               types.SimpleNamespace(module="step", seconds=3.0,  # the chunk program's one-token rows
                                     text=f"%paged_attn.9 = bf16[4,1,4096] custom-call(), "
                                          f"custom_call_target=\"{xplane.PALLAS_TARGET}\"")]
        return sum(o.seconds for o in ops if pick(o))


def span(name, **args):
    return spans.Span(name, 0.0, 1.0, args)


SPANS = (
    span("serve:dispatch", kind="chain", chain=3, live=24, rows=24, k=8, attended_rows=270_000, context_tokens=1_350_000,
         row_steps=192, windows_closed=1),
    span("serve:dispatch", kind="chain", chain=4, live=24, rows=24, k=8, attended_rows=280_000, context_tokens=1_120_000,
         row_steps=192, windows_closed=0),
    span("serve:dispatch", kind="prefill", live=4, rows=4, attended_rows=9_000_000, context_tokens=50_000_000,
         row_steps=24_000, windows_closed=10),  # no chain
    span("serve:dispatch", kind="chain", chain=5, live=24, rows=24, k=8),  # a program without the args
)


def run_with():
    return {"workload": {"name": CELL}, "config": program.published(harness.load_config("evabyte")), "calls": [],
            "architecture": harness.load_architecture("evabyte"), "device_kind": "TPU v5 lite"}


@pytest.fixture
def synthetic(monkeypatch):
    monkeypatch.setattr(spans, "trace_file", lambda run: "synthetic")
    monkeypatch.setattr(spans, "of_run", lambda run: SPANS)
    monkeypatch.setattr(scopes, "instructions", lambda path: INSTRUCTIONS)
    return run_with()


def test_the_new_metrics_are_the_three_of_the_cell():
    assert sorted(NEW) == ["eva_paged_roofline.batch", "eva_rows_per_context_token.batch", "eva_time_share.batch"]


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_synthetic_trace(name, synthetic):
    value = harness.load_reader(name)(synthetic, Trace())
    if name == "eva_time_share.batch":
        want = 100 * (0.30 + 0.50 + 0.02 + 0.01 + 0.10) / 2.0  # both serving programs, the kernels under the scope too
    elif name == "eva_rows_per_context_token.batch":
        want = (270_000 / 1_350_000 + 280_000 / 1_120_000) / 2  # the median of the two chains that say
    else:
        attended, rows = 550_000.0, 384.0
        bytes_ = attended * ROW + 2 * rows * 32 * 128 * 2
        flops = 4 * attended * 32 * 128
        want = 100 * 8 * max(bytes_ / 819e9, flops / 197e12) / 0.5  # 8 layers, the chain program's kernel alone
        assert bytes_ / 819e9 > flops / 197e12  # memory-bound
    assert value == pytest.approx(want, rel=1e-9) and value < 100


def test_the_costs_by_hand():
    arch, cfg = harness.load_architecture("evabyte"), program.published(harness.load_config("evabyte"))
    assert arch.row_bytes(cfg) == ROW == 16 * 1024
    # position 6,143 closes the third window: two windows' summaries and 2,048 exact rows
    assert arch.attended_rows(cfg, 6143) == 2 * 128 + 2048 and arch.attended_rows(cfg, 6144) == 3 * 128 + 1
    assert arch.attended_rows(cfg, 32767) == 15 * 128 + 2048 == 3968  # the most at the published positions
    assert arch.eva_decode_cost(cfg, 1000.0, 0.0) == (4 * 1000 * 32 * 128, 1000 * ROW)
    # a prompt of 2 windows and 10: causal halves, and the later windows' queries on the summaries before
    pairs = 2 * (2048 * 2049 // 2) + 10 * 11 // 2 + 128 * 2048 + 256 * 10
    assert pairs == sum(arch.attended_rows(cfg, t) for t in range(2 * 2048 + 10))
    close = arch.eva_close_cost(cfg, 2)
    assert close == (2 * 2048 * 4096 * 6.0, 2 * (2048 + 128) * ROW)
    flops, bytes_ = arch.eva_prefill_cost(cfg, [2 * 2048 + 10])
    assert flops == 4.0 * pairs * 32 * 128 + close[0] and bytes_ == (2 * 2048 + 10) * 4 * 4096 * 2
    # the configuration's own arithmetic (ISSUE 35): a layer 202.4M, 8 layers + embedding + head 1.631B
    assert arch.layer_params(cfg) == 202_391_552 and arch.total_params(cfg) == 1_630_932_992
    assert arch.matmul_params(cfg) == 8 * (4 * 4096 ** 2 + 3 * 4096 * 11008) + 4096 * 320


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_its_names(name, tmp_path_factory, monkeypatch):
    """The recorded v5e trace is of a program with neither the scope nor the
    span args: the metric is left out, nothing raises (the parent, on the
    traced runs the driver makes with this PR's benchmark files)."""
    path = unpack_span_trace(tmp_path_factory.mktemp("eva"))
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: path)
    scopes.report.cache_clear(), spans.report_idle.cache_clear()
    run = run_with()
    run["workload"] = {"name": "pythia-1.4b.serve.batch"}
    assert harness.load_reader(name)(run, xplane.reduce_trace(path)) is None
    assert not eva.chains(run)


def test_the_real_files_hold_the_rules():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "evabyte"]
    held = harness.load_config("evabyte")
    config_rules(entry, held, BENCH)
    assert entry["reduced"] == ["num_hidden_layers"] and held["num_hidden_layers"] == 8
    assert held["reduced"] == [{"key": "num_hidden_layers", "published": 32, "used": 8}]
    published = {"hidden_size": 4096, "intermediate_size": 11008, "num_attention_heads": 32, "num_key_value_heads": 32,
                 "window_size": 2048, "chunk_size": 16, "num_pred_heads": 8, "vocab_size": 320, "rope_theta": 100000,
                 "max_position_embeddings": 32768, "rms_norm_eps": 1e-05, "norm_add_unit_offset": True,
                 "fp32_skip_add": True, "model_type": "evabyte", "attention_class": "eva"}
    assert {k: held[k] for k in published} == published
    read = held["check"]["readings"]["logit_rel_tol"]
    assert read["sound_max"] < held["check"]["logit_rel_tol"] < read["control_min"]
    for said in ("adaptive_phi", "adaptive_mu_k", "num_pred_heads", "visibility", "weights"):
        assert said in held["assumed"]
    src = open(os.path.join(harness.BENCH_DIR, "reference", "evabyte.py")).read()
    assert "deepspeed_tpu" not in src and "import" in src  # imports nothing of the program


def test_the_cell_is_issue_35_s():
    held = harness.load_workload(CELL)
    assert held["kind"] == "serve" and held["chips"] == 1 and held["config"] == "evabyte"
    assert held["traffic"] == {"kind": "closed_waves", "wave": 24, "output_tokens": 1280,
                               "prompt_len": {"dist": "uniform", "min": 4096, "max": 8192}}
    engine = held["engine"]
    assert {k: engine[k] for k in ("dtype", "kv_cache_dtype", "max_seqs", "decode_chain", "kv_block_size", "row_bucket",
                                   "chunk_bucket", "max_ragged_batch_size", "flight_recorder")} == {
        "dtype": "bf16", "kv_cache_dtype": "bf16", "max_seqs": 24, "decode_chain": 8, "kv_block_size": 16,
        "row_bucket": 4, "chunk_bucket": 8192, "max_ragged_batch_size": 32768, "flight_recorder": True}
    assert engine["max_seq_len"] >= 8192 + 1280 and engine["kv_pool_bytes"] == 24 * 160 * 16 * 8 * ROW
    assert [4, 8192] in held["warm"]["prefill"] and 24 in held["warm"]["chain_rows"]
    listed = [m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert "paged_roofline.batch" not in listed and "paged_time_share.batch" not in listed
    glm = [m["name"] for m in BENCH["per_layer"] if "glm-4.7-flash.serve.batch" in m.get("workloads", [])]
    assert set(listed) - set(NEW) == {n for n in glm if not n.startswith(("moe_", "mla_"))} and len(listed) == 9 + 3
    (e2e,) = [m for m in BENCH["end_to_end"] if m["name"] == "serve_out_tokens_per_s"]
    assert e2e["workloads"][-1] == CELL
