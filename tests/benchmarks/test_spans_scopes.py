"""``benchmarks/lib/spans.py``, ``scopes.py`` and the six readers built on
them, against the trace ``record_span_trace.py`` took on one v5e chip (again
in PR 27, on the one page-major pool of PR 26: no ``page_view``, and a pool of
``[layers * blocks, block, kvH * hd]`` = ``[128, 16, 256]``): two steps of a
tiny trainer, then one ``generate`` of a tiny v2 engine (a prefill of 6
prompts and two decode chains of 4), all under ``bench:window``. What the
file holds, as looked at by hand, is in ``data/v5e_1chip_spans.txt``."""

import os

import pytest

from benchmarks.lib import harness, kernels, scopes, spans, xplane
from tests.benchmarks.conftest import unpack_span_trace

DATA = os.path.join(os.path.dirname(__file__), "data")
BENCH = harness.load_benchmark()
NEW = [m["name"] for m in BENCH["per_layer"] if m["name"].split(".")[0] in {
    "pool_copy_time_share", "sched_host_ms", "chain_live_rows",
    "optimizer_time_share", "lm_head_ce_time_share", "host_data_ms"}]


@pytest.fixture(scope="module")
def sample(tmp_path_factory):
    return unpack_span_trace(tmp_path_factory.mktemp("spans"))


@pytest.fixture
def run_of(monkeypatch):
    """A run whose traced file is the one given, as ``run.py`` leaves it for the readers."""
    def make(path, kv_pool_shape=(128, 16, 256)):  # the recorded engine's pool, from the .txt
        monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: path)
        scopes.report.cache_clear(), spans.report_idle.cache_clear()  # each prints once a trace
        config = harness.load_config("pythia-410m")
        config.update(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
                      num_attention_heads=2, vocab_size=512)
        return {"workload": {"name": "tiny"}, "config": config, "kv_pool_shape": kv_pool_shape,
                "architecture": harness.load_architecture(config["architecture"])}
    return make


def test_spans_with_their_args_clipped_to_the_window(sample):
    got = spans.read_spans(sample)
    assert [s.name for s in got[:4]] == ["train_batch", "data", "step", "post_step"]
    assert [s.args["step"] for s in spans.named(got, "train_batch")] == [2, 3]
    generate = spans.named(got, "serve:generate")[0]
    assert generate.args == {"requests": 6, "max_new_tokens": 9}
    admit = spans.named(got, "serve:admit")[0]
    assert (admit.args["requests"], admit.args["tokens"], admit.args["rids"]) == (6, 144, "0 1 2 3 4 5")
    chains = spans.named(got, "serve:dispatch", kind="chain")
    assert [(s.args["chain"], s.args["rows"], s.args["live"], s.args["k"]) for s in chains] == [
        (2, 8, 6, 4), (3, 8, 6, 4)]
    assert [s.args["emitted"] for s in spans.named(got, "serve:accept")] == [6, 24, 24]
    serving = [s for s in got if s.name.startswith("serve:")]
    assert all(generate.start_s <= s.start_s and s.end_s <= generate.end_s for s in serving)
    assert (serving[1].name, serving[-1].name) == ("serve:setup", "serve:finish")
    assert all(a.start_s <= b.start_s for a, b in zip(got, got[1:]))
    window = xplane.reduce_trace(sample).window_s
    assert 0 < got[-1].end_s - got[0].start_s <= window


def test_idle_gaps_go_to_the_innermost_span_over_them(sample):
    table = spans.idle_by_span(sample)
    # the chip waits while the host dispatches and fetches; between the two
    # train_batch calls the recorder itself makes the next batch
    assert set(table) == {"data", "step", "serve:dispatch", "serve:fetch",
                          "outside, after train_batch before train_batch"}
    assert max(table, key=table.get) == "serve:dispatch"
    reduced = xplane.reduce_trace(sample)
    idle = reduced.window_s - reduced.busy_s
    assert 0.9 * idle < sum(table.values()) <= idle   # the rest: gaps under 20 us
    assert spans.share_inside(table) == pytest.approx(0.971, abs=0.001)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(chain)/while/body/closed_call/pool_scan/while/body/closed_call/layer/paged_attn/pallas_call",
     "paged_attn"),
    # a scope the program no longer opens (``page_view`` went with PR 26) is no scope of ours
    ("jit(chain)/while/body/closed_call/pool_scan/while/body/closed_call/layer/page_view/reshape",
     "layer"),
    ("jit(chain)/while/body/closed_call/pool_scan/while/body/dynamic_update_slice", "pool_scan"),
    ("jit(step)/pool_scan/while/body/closed_call/layer/kv_write/scatter:", "kv_write"),
    ("jit(train_step)/while/body/closed_call/transpose(jvp(CausalLM))/while/body/closed_call/"
     "layers/attn/flash_bwd_dq/pallas_call", "flash_bwd_dq"),
    ("jit(train_step)/while/body/closed_call/jvp(CausalLM)/lm_head_ce/jit(take_along_axis)/gather",
     "lm_head_ce"),
    ("jit(train_step)/transpose(jvp(lm_head_ce))/dot_general", "lm_head_ce"),
    ("jit(train_step)/optimizer/mul;add", "optimizer"),
    ("jit(train_step)/while/body/closed_call/jvp(CausalLM)/final_norm/mul", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
])
def test_innermost_scope(op_name, scope):
    assert scopes.innermost_scope(op_name) == scope


def test_device_seconds_by_scope(sample):
    by_scope = scopes.scope_seconds(sample)
    assert set(by_scope) == {"embed", "layers", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                             "lm_head_ce", "optimizer", "pool_scan", "layer", "kv_write",
                             "paged_attn", "lm_head", scopes.UNSCOPED}
    assert by_scope["paged_attn"] == pytest.approx(143.8e-6, rel=0.01)
    assert by_scope["layers"] == pytest.approx(159.5e-6, rel=0.01)
    # hlo_stats' self times add up to the device's busy time (whole trace against
    # the window's clip: within a few percent)
    assert sum(by_scope.values()) == pytest.approx(xplane.reduce_trace(sample).busy_s, rel=0.05)
    assert 0.25 < by_scope[scopes.UNSCOPED] / sum(by_scope.values()) < 0.30
    assert not [f for f in os.listdir(os.path.dirname(sample)) if "op_stats" in f]  # xprof's cache


def test_kernels_are_instructions_of_their_own_names(sample):
    pallas = {(i.program, i.name.split(".")[0]) for i in scopes.instructions(sample)
              if xplane.PALLAS_TARGET in i.text}
    assert pallas == {("train_step", "flash_fwd"), ("train_step", "flash_bwd_dq"),
                      ("train_step", "flash_bwd_dkv"), ("chain", "paged_attn"),
                      ("step", "paged_attn")}


@pytest.mark.parametrize("name", NEW)
def test_new_reader_on_the_recorded_trace(name, sample, run_of, capsys):
    assert len(NEW) == 6
    run, trace = run_of(sample), xplane.reduce_trace(sample)
    value = harness.load_reader(name)(run, trace)
    want = {
        "pool_copy_time_share.batch": 7.76,      # 72.4 us under kv_write + nothing of the pool's shape
        "sched_host_ms.batch": 0.1468,           # of two chains: 0.137 and 0.156 ms
        "chain_live_rows.batch": 6.0,
        "optimizer_time_share.train": 4.58,
        "lm_head_ce_time_share.train": 3.35,
        "host_data_ms.train": 0.9325,           # of two steps: 0.992 and 0.873 ms
    }[name]
    assert value == pytest.approx(want, rel=0.01)
    said = capsys.readouterr().out
    if name.startswith(("sched_host_ms", "host_data_ms")):
        assert "idle_in_span=serve:dispatch" in said and "share_inside_a_dstpu_span=" in said
    elif not name.startswith("chain_live_rows"):
        assert "scope=(no_scope)" in said and "scope=pool_scan" in said


def test_kernels_are_picked_by_name_and_pool_copies_by_the_pool_s_shape(sample, run_of, capsys):
    """The readers take no shape from a configuration: a kernel is the
    instruction of its name, the pool's shape is the engine's own."""
    run, trace = run_of(sample), xplane.reduce_trace(sample)
    by_name = {(i.program, i.name.split(".")[0]): i.seconds for i in scopes.instructions(sample)
               if xplane.PALLAS_TARGET in i.text}
    # the window's clip of the trace against hlo_stats' whole trace: the same events
    assert kernels.paged_seconds(run, trace) == pytest.approx(by_name["chain", "paged_attn"], rel=1e-3)
    assert kernels.flash_seconds(run, trace) == pytest.approx(
        sum(by_name["train_step", k] for k in kernels.FLASH_KERNELS), rel=1e-3)
    assert harness.load_reader("paged_time_share.batch")(run, trace) == pytest.approx(14.20, rel=0.01)
    assert harness.load_reader("flash_time_share.train")(run, trace) == pytest.approx(8.40, rel=0.01)
    # no copy of the whole pool is left since PR 26; given the shape of the two
    # stacked projections the chain re-lays (copy.44, copy.45: 1.57 + 1.58 us),
    # the by-shape part finds them, so it would find a copy of the pool
    capsys.readouterr()
    pool_copy = harness.load_reader("pool_copy_time_share.batch")
    assert pool_copy(run, trace) == pytest.approx(7.76, rel=0.01)
    assert "pool_copy_by_shape_s=0.0 pool_shape=[128,16,256]" in capsys.readouterr().out
    as_weights = run_of(sample, kv_pool_shape=(2, 256, 2, 128))
    assert pool_copy(as_weights, trace) == pytest.approx(8.10, rel=0.01)
    assert pool_copy(run_of(sample, kv_pool_shape=None), trace) is None  # a run without a pool


@pytest.mark.parametrize("name", NEW)
def test_new_reader_finds_nothing_in_a_program_without_spans_and_scopes(name, run_of):
    """The first sample was recorded before the program annotated anything, as
    the parent commit of PR 25 runs: the metric is left out, nothing raises."""
    old = os.path.join(DATA, "v5e_1chip_sample.xplane.pb")
    assert harness.load_reader(name)(run_of(old), xplane.reduce_trace(old)) is None
    assert not [f for f in os.listdir(DATA) if "op_stats" in f]  # xprof's cache went elsewhere


def test_no_trace_file_no_metric(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "BENCH_DIR", str(tmp_path / "benchmarks"))
    assert spans.trace_file({"workload": {"name": "tiny"}}) is None
