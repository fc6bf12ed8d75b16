"""The five readers of the routed, latent-attention serving cell
(``moe_time_share``, ``moe_experts_roofline``, ``mla_paged_time_share``,
``mla_paged_roofline``, ``moe_experts_touched``; ``benchmarks/lib/routed.py``)
on a synthetic trace whose numbers can be checked by hand, on the recorded v5e
trace of a program that has none of their names (nothing found, nothing
raised), and the real files of the configuration they were written for."""

import os
import types

import pytest

from benchmarks.lib import harness, program, routed, scopes, spans, xplane
from tests.benchmarks.conftest import config_rules, routed_rules, unpack_span_trace

BENCH = harness.load_benchmark()
CELL = "glm-4.7-flash.serve.batch"
NEW = [m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]


def instruction(program_name, name, op_name, seconds, text=""):
    return scopes.Instruction(program_name, name, "fusion", text or f"%{name} = bf16[8] fusion()", op_name,
                              seconds, 1)


LAYER = "jit(chain)/while/body/pool_scan/while/body/layer/"
INSTRUCTIONS = (
    instruction("chain", "fusion.1", LAYER + "moe/moe_experts/dot_general", 0.60),
    instruction("chain", "fusion.2", LAYER + "moe/moe_shared/dot_general", 0.05),
    instruction("chain", "fusion.3", LAYER + "moe/moe_router/dot_general", 0.05),
    instruction("chain", "fusion.4", LAYER + "moe/add", 0.02),  # under moe, under none of its parts
    instruction("chain", "fusion.5", LAYER + "mla/dot_general", 0.10),
    instruction("step", "fusion.6", "jit(step)/pool_scan/while/body/layer/moe/moe_experts/gmm", 0.08),
    instruction("train_step", "fusion.7", "jit(train_step)/layers/moe/moe_experts/dot_general", 9.0),  # no serving program
    instruction("chain", "fusion.8", LAYER + "remoe/dot_general", 1.0),  # a component, not a substring
)


class Trace:
    busy_s, n_devices = 2.0, 1

    def __init__(self, kernel_s):
        self.kernel_s = kernel_s

    def op_seconds(self, pick):
        ops = [types.SimpleNamespace(module="chain", seconds=self.kernel_s,
                                     text=f"%mla_paged_attn.7 = bf16[64,32,512] custom-call(), "
                                          f"custom_call_target=\"{xplane.PALLAS_TARGET}\""),
               types.SimpleNamespace(module="step", seconds=5.0,
                                     text=f"%mla_paged_attn.9 = bf16[1024,320,512] custom-call(), "
                                          f"custom_call_target=\"{xplane.PALLAS_TARGET}\""),
               types.SimpleNamespace(module="chain", seconds=7.0,
                                     text=f"%paged_attn.3 = bf16[8] custom-call(), "
                                          f"custom_call_target=\"{xplane.PALLAS_TARGET}\"")]
        return sum(o.seconds for o in ops if pick(o))


def span(name, **args):
    return spans.Span(name, 0.0, 1.0, args)


SPANS = (
    span("serve:dispatch", kind="chain", chain=3, live=64, rows=64),
    span("serve:accept", kind="chain", chain=3, emitted=512, experts_touched=62.0),
    span("serve:dispatch", kind="chain", chain=4, live=64, rows=64),
    span("serve:accept", kind="chain", chain=4, emitted=448, experts_touched=60.0),  # 7 live steps
    span("serve:dispatch", kind="prefill", live=64, rows=64),
    span("serve:accept", kind="prefill", emitted=64),
    span("serve:accept", kind="chain", chain=9, emitted=8, experts_touched=1.0),  # its dispatch not in the window
)


def run_with(calls):
    return {"workload": {"name": CELL}, "config": program.published(harness.load_config("glm-4.7-flash")),
            "calls": calls, "architecture": harness.load_architecture("glm4_moe_lite"),
            "device_kind": "TPU v5 lite"}


@pytest.fixture
def synthetic(monkeypatch):
    monkeypatch.setattr(spans, "trace_file", lambda run: "synthetic")
    monkeypatch.setattr(spans, "of_run", lambda run: SPANS)
    monkeypatch.setattr(scopes, "instructions", lambda path: INSTRUCTIONS)
    return run_with([
        {"kind": "decode_chain", "traced": True, "context_tokens": 64 * 8 * 300.0, "row_steps": 512},
        {"kind": "decode_chain", "traced": False, "context_tokens": 1e9, "row_steps": 512},
        {"kind": "prefill", "traced": True, "rows": 64, "tokens": 10000}])


def test_the_new_metrics_are_the_five_of_the_cell():
    assert sorted(NEW) == ["mla_paged_roofline.batch", "mla_paged_time_share.batch",
                           "moe_experts_roofline.batch", "moe_experts_touched.batch", "moe_time_share.batch"]


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_synthetic_trace(name, synthetic):
    value = harness.load_reader(name)(synthetic, Trace(kernel_s=0.01))
    arch, cfg = synthetic["architecture"], synthetic["config"]
    if name == "moe_time_share.batch":
        want = 100 * (0.60 + 0.05 + 0.05 + 0.02 + 0.08) / 2.0  # both serving programs, whole components
    elif name == "mla_paged_time_share.batch":
        want = 100 * 0.01 / 2.0  # the chain program's kernel alone
    elif name == "moe_experts_touched.batch":
        want = 61.0  # the median of the two chains in the window
    elif name == "mla_paged_roofline.batch":
        context, rows = 64 * 8 * 300.0, 512.0
        bytes_ = context * 1152 + rows * 20 * (576 + 512) * 2
        flops = context * 4 * 20 * 544
        want = 100 * 8 * max(bytes_ / 819e9, flops / 197e12) / 0.01
    else:
        experts = 7 * (62.0 * 8 + 60.0 * 7)
        pairs, tokens = 7 * 15, 7 * (512 + 448)
        always = 3 * 2048 * 1536 + 2048 * 64 + 64
        bytes_ = 2 * (experts * 3 * 2048 * 1536 + pairs * always)
        flops = 2 * tokens * (4 * 3 * 2048 * 1536 + always)
        assert arch.routed_decode_cost(cfg, experts, tokens, pairs) == pytest.approx((flops, bytes_))
        want = 100 * max(bytes_ / 819e9, flops / 197e12) / (0.60 + 0.05 + 0.05)  # the chain's three parts
    assert value == pytest.approx(want, rel=1e-6)
    assert value < 100 or name == "moe_experts_touched.batch" or name == "mla_paged_roofline.batch"


def test_a_token_s_latent_row_is_counted_once_for_all_heads(synthetic):
    arch, cfg = synthetic["architecture"], synthetic["config"]
    flops, bytes_ = arch.latent_decode_cost(cfg, 1000.0, 0.0)
    assert bytes_ == 1000 * 1152 and flops == 1000 * 4 * 20 * 544
    assert arch.expert_params(cfg) * 2 == 18_874_368  # 18.87 MB an expert in bf16


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_its_names(name, tmp_path_factory, monkeypatch):
    """The recorded v5e trace is of a program with neither the scopes, nor the
    kernel, nor ``experts_touched``: the metric is left out, nothing raises."""
    path = unpack_span_trace(tmp_path_factory.mktemp("routed"))
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: path)
    scopes.report.cache_clear(), spans.report_idle.cache_clear()
    run = run_with([{"kind": "decode_chain", "traced": True, "context_tokens": 10.0, "row_steps": 4}])
    assert harness.load_reader(name)(run, xplane.reduce_trace(path)) is None
    assert not routed.chains(run) and routed.mla_seconds(run, xplane.reduce_trace(path)) == 0


def test_the_real_files_hold_the_rules():
    """``config_rules`` and ``routed_rules`` of ``conftest.py`` on the listed
    configuration, its architecture file and its reference."""
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "glm-4.7-flash"]
    held = harness.load_config("glm-4.7-flash")
    config_rules(entry, held, BENCH)
    architecture = harness.load_architecture("glm4_moe_lite")
    reference = harness.load_reference("glm4_moe_lite")
    routed_rules(held, architecture, reference)
    routing = program.routing(architecture, held)
    assert (routing.layers, routing.experts, routing.k) == (7, 64, 4)
    assert entry["reduced"] == ["num_hidden_layers"] and held["num_hidden_layers"] == 8
    assert len(held["check"]["readings"]) == 2 and held["check"]["route_shortfall_tol"] < 0.5
    src = open(os.path.join(harness.BENCH_DIR, "reference", "glm4_moe_lite.py")).read()
    assert "deepspeed_tpu" not in src.replace("the system under test", "")  # imports nothing of the program


def test_the_cell_is_the_pythia_batch_cell_s_traffic():
    ours, theirs = harness.load_workload(CELL), harness.load_workload("pythia-1.4b.serve.batch")
    assert ours["traffic"] == theirs["traffic"] and ours["warm"] == theirs["warm"]
    same = ("dtype", "kv_cache_dtype", "max_seqs", "decode_chain", "kv_block_size", "row_bucket",
            "chunk_bucket", "hbm_check", "flight_recorder")
    assert {k: ours["engine"][k] for k in same} == {k: theirs["engine"][k] for k in same}
    assert ours["engine"]["kv_pool_bytes"] == 1 << 30
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["config"] == "glm-4.7-flash"
    listed = [m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert "paged_roofline.batch" not in listed and "paged_time_share.batch" not in listed
    assert len(listed) == 9 + 5
