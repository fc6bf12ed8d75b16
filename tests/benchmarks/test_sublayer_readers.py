"""The five readers of the sub-layer names (``layer_matmul_time_share``,
``layer_matmul_roofline``, ``scan_stack_time_share``, ``grad_accum_time_share``,
``unnamed_time_share``; ``benchmarks/lib/sublayers.py``) under their eight
listed names: on synthetic instructions whose numbers can be checked by hand, on
the recorded v5e trace of a program that writes none of the serving names
(nothing found, nothing raised), and the arithmetic of both rooflines at the
published widths of the two configurations they were written for."""

import importlib.util
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import harness, peaks, program, scopes, spans, sublayers, xplane
from tests.benchmarks.conftest import unpack_span_trace

BENCH = harness.load_benchmark()
SERVE = "pythia-1.4b.serve.batch"
TRAIN = ["pythia-410m.train.seq2048", "pythia-1.4b.train.zero3-4chip"]
# The glm and EVA cells report ``serve_out_tokens_per_s`` too and the three ``.batch`` readers read them
# (PERF.md, sections 5 and 7), but ``test_routed_readers.py`` and ``test_eva_readers.py`` pin the number of
# metrics those cells list, and this PR may edit no file the benchmark had: the next ``benchmark`` issue lists them.
NEW = {"layer_matmul_time_share.batch": [SERVE], "layer_matmul_roofline.batch": [SERVE],
       "layer_matmul_time_share.train": TRAIN, "layer_matmul_roofline.train": TRAIN,
       "scan_stack_time_share.train": TRAIN, "grad_accum_time_share.train": TRAIN,
       "unnamed_time_share.batch": [SERVE], "unnamed_time_share.train": TRAIN}
LAYOUT = "{1,0:T(8,128)(2,1)}"


def fusion(program_name, name, op_name, seconds, count=1, operands=()):
    text = f"%{name} = bf16[64,2048]{LAYOUT} fusion(" + ", ".join(
        f"{shape}{LAYOUT} %operand.{i}" for i, shape in enumerate(operands)) + "), kind=kOutput, calls=%fused"
    return scopes.Instruction(program_name, name, "fusion", text, op_name, seconds, count)


CHAIN = "jit(chain)/while/body/closed_call/"
LAYER = CHAIN + "pool_scan/while/body/closed_call/layer/"
STEPS = 24 * 8 * 2  # layer-steps of two chains of eight steps
# Pythia-1.4B's own shapes: hidden 2048, 16 heads of 128, intermediate 8192, 24 layers
SERVING = (
    # the scan's stacked parameter sliced inside the fusion: one layer's slice counts
    fusion("chain", "fusion.1", LAYER + "mlp/w_down/dot_general", 0.040, STEPS, ("bf16[64,8192]", "bf16[24,8192,2048]", "s32[]")),
    fusion("chain", "fusion.2", LAYER + "mlp/w_up/dot_general", 0.030, STEPS, ("bf16[64,2048]", "bf16[2048,8192]")),
    fusion("chain", "fusion.3", LAYER + "attn/wq/bse,ehd->bshd/dot_general", 0.010, STEPS, ("bf16[64,1,2048]", "bf16[1,2048,16,128]")),
    fusion("chain", "fusion.4", LAYER + "attn/wk/bse,ehd->bshd/dot_general", 0.010, STEPS, ("bf16[64,1,2048]", "bf16[2048,16,128]")),
    fusion("chain", "fusion.5", LAYER + "attn/wv/bse,ehd->bshd/dot_general", 0.010, STEPS, ("bf16[64,1,2048]", "bf16[2048,16,128]")),
    fusion("chain", "fusion.6", LAYER + "attn/wo/bshd,hde->bse/dot_general", 0.010, STEPS, ("bf16[64,1,16,128]", "bf16[16,128,2048]")),
    fusion("chain", "fusion.7", LAYER + "attn/wq/add", 0.002, STEPS, ("bf16[64,1,16,128]", "bf16[16,128]")),  # a bias add of its own
    fusion("chain", "fusion.8", LAYER + "mlp/mul", 0.004, STEPS, ("bf16[64,8192]",)),  # the activation: under mlp, no weight
    fusion("chain", "fusion.9", LAYER + "moe/moe_shared/w_up/dot_general", 1.0, STEPS, ("bf16[2048,1536]",)),  # a routed layer's
    fusion("chain", "fusion.10", LAYER + "remlp/w_downy/dot_general", 1.0, STEPS, ("bf16[8192,2048]",)),  # a component, not a substring
    fusion("chain", "fusion.11", LAYER + "attn/paged_attn/pallas_call", 0.050, STEPS),
    fusion("chain", "fusion.12", CHAIN + "jit(take_along_axis)/gather", 0.003, 16),  # no name of the program's
    fusion("chain", "copy-done.1", "", 0.001, 16),
    fusion("chain", "dynamic-slice_fusion.2", CHAIN + "pool_scan/while/body/dynamic_slice", 0.005, STEPS, ("bf16[24,2048,16,128]",)),
    fusion("step", "fusion.13", "jit(step)/pool_scan/while/body/closed_call/layer/mlp/w_down/dot_general", 0.006, 24,
           ("bf16[16384,8192]", "bf16[8192,2048]")),
    fusion("train_step", "fusion.14", "jit(train_step)/jvp(CausalLM)/layer_scan/while/body/layers/mlp/w_up/dot_general", 9.0),
)
TRAINED = "jit(train_step)/while/body/closed_call/"
TRAINING = (
    fusion("train_step", "fusion.1", TRAINED + "jvp(CausalLM)/layer_scan/while/body/closed_call/layers/mlp/w_up/dot_general", 0.5),
    fusion("train_step", "fusion.2", TRAINED + "transpose(jvp(CausalLM))/layer_scan/while/body/closed_call/layers/attn/wo/dot_general", 0.7),
    fusion("train_step", "fusion.3", TRAINED + "jvp(CausalLM)/layer_scan/while/body/closed_call/layers/attn_norm/mul", 0.06),
    fusion("train_step", "fusion.4", TRAINED + "jvp(CausalLM)/layer_scan/while/body/dynamic_update_slice", 0.2),
    fusion("train_step", "fusion.5", TRAINED + "transpose(jvp(layer_scan))/while/body/dynamic_slice", 0.1),  # wrapped, as older traces
    fusion("train_step", "fusion.6", TRAINED + "grad_accum/add", 0.05),
    fusion("train_step", "fusion.7", "jit(train_step)/grad_norm/reduce_sum", 0.01),
    fusion("train_step", "fusion.8", "jit(train_step)/optimizer/mul", 0.1),
    fusion("train_step", "fusion.9", TRAINED + "transpose(jvp(CausalLM))/add_any", 0.04),  # no name of the program's
    fusion("train_step", "copy-done.2", "", 0.02),
    fusion("other", "fusion.10", "jit(other)/scan_layers/grad_accumulate/mul", 0.03),  # components, not substrings
)


class Trace:
    busy_s, n_devices = 2.0, 1


def run_of(cell):
    workload = harness.load_workload(cell)
    config = harness.load_config(workload["config"])
    run = {"workload": workload, "config": program.published(config), "device_kind": "TPU v5 lite",
           "architecture": harness.load_architecture(config["architecture"])}
    if workload["kind"] == "train":
        run.update(traced_steps=3, micro_batch=2, micro_batches_per_step=8, seq_len=2048, chips=1)
    return run


@pytest.fixture
def synthetic(monkeypatch):
    def of(cell, instructions):
        monkeypatch.setattr(spans, "trace_file", lambda run: "synthetic")
        monkeypatch.setattr(scopes, "instructions", lambda path: instructions)
        sublayers.report.cache_clear()
        return run_of(cell)
    return of


def test_the_new_metrics_are_listed_for_the_cells_that_report_what_they_move():
    listed = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in NEW}  # by name: later PRs append
    assert listed.keys() == NEW.keys()
    for name, cells in NEW.items():
        assert set(cells) <= set(listed[name]["workloads"])
    for name, m in listed.items():
        assert m["source"] == "device_trace" and m["unit"] == "%"
        assert m["moves"] == ("train_tokens_per_s_chip" if name.endswith(".train") else "serve_out_tokens_per_s")
        assert m["better"] == ("higher" if "roofline" in name else "lower")


WEIGHTS_S = 0.040 + 0.030 + 4 * 0.010 + 0.002  # the chain's seven instructions that a weight names


@pytest.mark.parametrize("name", [n for n in NEW if n.endswith(".batch")])
def test_serving_reader_on_synthetic_instructions(name, synthetic, capsys):
    run = synthetic(SERVE, SERVING)
    value = harness.load_reader(name)(run, Trace())
    if name == "layer_matmul_time_share.batch":
        want = 100 * (WEIGHTS_S + 0.006) / 2.0  # chain and step; not the routed layer's, not the trainer's
    elif name == "unnamed_time_share.batch":
        want = 100 * (0.003 + 0.001) / 2.0  # serving programs only; ``layer`` names what ``remlp`` does not
    else:
        # the chain alone: each weight once a layer-step, the stacked operand as one layer's slice
        a_layer = 2 * (4 * 2048 * 2048 + 2 * 2048 * 8192)
        assert a_layer * 24 == 2 * sublayers.layer_matmul_params(run["architecture"], run["config"])
        # the time side: the products AND the scan's own slices of the stacked weights, whose copies the products read
        want = 100 * (a_layer * STEPS / 819e9) / (WEIGHTS_S + 0.005)
        out = capsys.readouterr().out
        assert "found_of_architecture=1.0" in out  # the bytes found a layer are matmul_params less the head's
        assert "sublayer=w_down program=chain" in out and f"bytes={2.0 * 8192 * 2048}" in out
        assert "scan_slices_s=0.005" in out and f"products_alone_pct={100 * (a_layer * STEPS / 819e9) / WEIGHTS_S}"[:30] in out
    assert value == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("gone", ["fusion.2", "fusion.6"])
def test_the_serving_roofline_is_left_out_where_the_named_weights_are_not_the_architecture_s(gone, synthetic, capsys):
    """A weight whose product lost its name (fused under another's, renamed):
    its bytes are not found, the share would read high or low in silence."""
    run = synthetic(SERVE, tuple(i for i in SERVING if i.name != gone))
    assert harness.load_reader("layer_matmul_roofline.batch")(run, Trace()) is None
    out = capsys.readouterr().out
    assert "layer_matmul_roofline=left_out" in out and "found_of_architecture=0." in out


@pytest.mark.parametrize("name", [n for n in NEW if n.endswith(".train")])
def test_training_reader_on_synthetic_instructions(name, synthetic):
    run = synthetic(TRAIN[0], TRAINING)
    value = harness.load_reader(name)(run, Trace())
    if name == "layer_matmul_time_share.train":
        want = 100 * (0.5 + 0.7) / 2.0  # forward and transposed
    elif name == "scan_stack_time_share.train":
        want = 100 * (0.2 + 0.1) / 2.0  # under layer_scan and in no layer; wrappers taken off
    elif name == "grad_accum_time_share.train":
        want = 100 * (0.05 + 0.01) / 2.0
    elif name == "unnamed_time_share.train":
        want = 100 * (0.04 + 0.02 + 0.03) / 2.0  # every program of a training cell's trace
    else:
        params = 24 * (4 * 1024 * 1024 + 2 * 1024 * 4096)
        assert params == sublayers.layer_matmul_params(run["architecture"], run["config"])
        want = 100 * (6 * params * 3 * 16 * 2048 / 197e12) / (0.5 + 0.7)
    assert value == pytest.approx(want, rel=1e-9)


def test_the_tables_say_which_name_each_instruction_got(synthetic, capsys):
    run = synthetic(SERVE, SERVING)
    harness.load_reader("unnamed_time_share.batch")(run, Trace())
    harness.load_reader("layer_matmul_time_share.batch")(run, Trace())
    out = capsys.readouterr().out
    assert out.count("named_op=") == 10 and out.count("sublayer=mlp/w_down ") == 1  # printed once a trace
    assert "named_op=fusion.1 program=chain path=mlp/w_down" in out
    assert "operands=bf16[64,8192],bf16[24,8192,2048],s32[] " in out
    assert "named_op=fusion.11 program=chain path=attn/paged_attn" in out
    assert "named_op=fusion.13 program=step path=mlp/w_down" in out and "named_op=fusion.10 program=chain path=pool_scan/layer" in out
    assert "sublayer=(no_name) device_s=0.004" in out and "sublayer=layer/mlp " in out and "fusion.14" not in out


@pytest.mark.parametrize("cell,layer_bytes,step_bytes", [
    ("pythia-1.4b.serve.batch", 100_663_296, 2_415_919_104),    # 2.42 GB a decode step
    ("evabyte.serve.long-batch", 404_750_336, 3_238_002_688)])  # 3.24 GB
def test_a_decode_step_s_weights_at_the_published_widths(cell, layer_bytes, step_bytes):
    run = run_of(cell)
    arch, cfg = run["architecture"], run["config"]
    params = sublayers.layer_matmul_params(arch, cfg)
    assert 2 * params == step_bytes and 2 * params == layer_bytes * arch.layers(cfg)
    assert params + cfg["hidden_size"] * cfg["vocab_size"] == arch.matmul_params(cfg)
    # at the chip's bandwidth a step's weights take 2.95 ms (Pythia) and 3.95 ms (EVA)
    assert step_bytes / peaks.device_peaks("TPU v5 lite").hbm_bytes_per_s == pytest.approx(
        {"pythia-1.4b.serve.batch": 2.950e-3, "evabyte.serve.long-batch": 3.954e-3}[cell], rel=1e-3)


@pytest.mark.parametrize("text,stacked,want", [
    ("%f = bf16[64,2048]{1,0} fusion(bf16[64,8192]{1,0:T(8,128)(2,1)} %a, bf16[24,8192,2048]{2,1,0:T(8,128)(2,1)S(1)} %w)", 24, 2 * 8192 * 2048),
    ("%f = bf16[64,2048]{1,0} fusion(bf16[64,8192]{1,0} %a, bf16[24,8192,2048]{2,1,0} %w)", 0, 24 * 2 * 8192 * 2048),
    # two products in one instruction read two weights; the activation and the biases are neither
    ("%f = bf16[64,2048]{1,0} fusion(bf16[64,2048]{1,0} %x, bf16[24,2048,8192]{2,1,0} %up, bf16[24,8192]{1,0} %b, "
     "bf16[24,8192,2048]{2,1,0} %down)", 24, 2 * 2 * 8192 * 2048),
    ("%f = f32[24,1,4096]{2,1,0} fusion(f32[24,1,4096]{2,1,0} %x, bf16[11008,4096]{1,0} %w, pred[] %p)", 8, 2 * 11008 * 4096),
    ("%f = (f32[8]{0}, f32[8]{0}) fusion(s8[16,16] %q)", 0, 256),
    ("%copy-done.1 = f32[2,256]{1,0} copy-done((f32[2,256]{1,0}, u32[]{:S(2)}) %copy-start.1)", 0, 0),
    ("fusion.3", 0, 0),
])
def test_the_weights_an_instruction_reads_from_its_own_text(text, stacked, want):
    assert sublayers.weight_bytes(text, stacked) == want


@pytest.mark.parametrize("op_name,path,weight", [
    ("jit(chain)/while/body/closed_call/pool_scan/while/body/closed_call/layer/attn/mla/wkv_b/nchd,rhd->nchr/dot_general",
     ("pool_scan", "layer", "attn", "mla", "wkv_b"), "wkv_b"),
    ("jit(train_step)/while/body/transpose(jvp(CausalLM))/layer_scan/while/body/closed_call/layers/mlp/w_down/dot_general",
     ("layer_scan", "layers", "mlp", "w_down"), "w_down"),
    ("jit(train_step)/jvp(lm_head_ce)/transpose(jvp(w_up))/mul:", ("lm_head_ce", "w_up"), "w_up"),
    ("jit(step)/pool_scan/while/body/closed_call/layer/moe/moe_experts/w_up/gmm", ("pool_scan", "layer", "moe", "moe_experts", "w_up"), None),
    ("jit(chain)/while/body/closed_call/jit(floor_divide)/div", (), None),
    ("", (), None),
])
def test_an_op_name_s_path_and_weight(op_name, path, weight):
    assert sublayers.path(op_name) == path and sublayers.weight_of(op_name) == weight
    assert sublayers.label(op_name) == ("/".join(path[-2:]) or "(no name)")


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_the_recorded_trace_of_a_program_without_the_names(name, tmp_path_factory, monkeypatch):
    """The recorded v5e trace is of PR 25's programs: no serving name, no
    ``layer_scan``, no ``grad_accum``; flax's own ``layers/attn/wq`` are there,
    as they always were. Nothing raises; a serving metric and the two new
    train scopes' are left out."""
    path = unpack_span_trace(tmp_path_factory.mktemp("sublayers"))
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: path)
    sublayers.report.cache_clear()
    run = run_of(NEW[name][0])
    value = harness.load_reader(name)(run, xplane.reduce_trace(path))
    if name.endswith(".batch") or name.split(".")[0] in ("scan_stack_time_share", "grad_accum_time_share"):
        assert value is None
    else:
        assert value is None or value >= 0


@pytest.mark.parametrize("cell,added", [("glm-4.7-flash.serve.batch", 3), ("evabyte.serve.long-batch", 3),
                                        (SERVE, 0), (TRAIN[0], 0)])
def test_the_tool_that_reads_the_cells_not_listed_yet(cell, added, monkeypatch):
    """``tools/traced_cell.py`` runs ``run.main`` with the three serving readers
    in every serving cell's list (``PERF.md`` section 5's glm and EVA readings)
    and changes no other cell's; both of its wraps restored here afterwards."""
    spec = importlib.util.spec_from_file_location("traced_cell", os.path.join(os.path.dirname(harness.BENCH_DIR), "tools", "traced_cell.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    plain = harness.cell_metrics
    monkeypatch.setattr(harness, "cell_metrics", plain)
    monkeypatch.setattr(scopes, "_hlo_stats", scopes._hlo_stats)
    seen = {}

    def main(argv):
        seen["per_layer"] = harness.cell_metrics(BENCH, "per_layer", cell)
        seen["end_to_end"] = harness.cell_metrics(BENCH, "end_to_end", cell)
        return 0

    monkeypatch.setattr(bench_run, "main", main)
    assert tool.main(["--workload", cell]) == 0
    listed = plain(BENCH, "per_layer", cell)
    assert seen["per_layer"][:len(listed)] == listed and seen["end_to_end"] == plain(BENCH, "end_to_end", cell)
    assert sorted(m["name"] for m in seen["per_layer"][len(listed):]) == sorted(tool.SERVING)[:added]
