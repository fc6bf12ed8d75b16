"""PR 53's three readers, ``stall_s.batch`` and ``gc_pause_ms.batch`` /
``.train``, on synthetic spans whose numbers can be checked by hand (a value,
0.0, nothing), on the recorded v5e trace of a program that has none of their
names (nothing found, nothing raised), and the entries appended to
``BENCHMARK.json``, held by MEMBERSHIP and by PREFIX, never by position or
count: a later cell, and a later cell appended to a list these metrics are on,
breaks nothing here. ``tools/stall_controls.py`` is imported and its arguments
checked."""

import importlib.util
import json
import os
import types

import pytest

from benchmarks.lib import harness, scopes, spans, xplane
from tests.benchmarks.conftest import unpack_span_trace

BENCH = harness.load_benchmark()
REPO = os.path.dirname(harness.BENCH_DIR)
GLM = "glm-4.7-flash.serve.batch"
SERVE = ["pythia-1.4b.serve.batch", "evabyte.serve.long-batch", "xing4.0-29b-a4b.serve.long-prompt-batch",
         "granite-4.0-h-micro.serve.long-output-batch", "qwen3-next-80b-a3b.serve.long-output-wave128",
         "granite-4.0-h-small.serve.long-output-wave64"]
TRAIN = ["pythia-410m.train.seq2048", "pythia-1.4b.train.zero3-4chip"]
NEW = {"stall_s.batch": ("s", "serving loop", "serve_out_tokens_per_s", SERVE),
       "gc_pause_ms.batch": ("ms", "serving loop", "serve_out_tokens_per_s", SERVE),
       "gc_pause_ms.train": ("ms", "engine", "train_tokens_per_s_chip", TRAIN)}


def event(name, start_s, lasts_s, **stats):
    return types.SimpleNamespace(name=name, start_ns=start_s * 1e9, duration_ns=lasts_s * 1e9, stats=stats.items())


def profile_of(*lines):
    return types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(name=f"thread {i}", events=evs) for i, evs in enumerate(lines)])])


# the window is [10, 13]
WINDOW = event("bench:window", 10.0, 3.0)
CLEAN = [WINDOW,
         event("dstpu:serve:fetch", 10.40, 0.10, kind="chain", chain=5, cadence_ms=101.5),
         event("dstpu:serve:fetch", 10.55, 0.10, kind="chain", chain=6, cadence_ms=102.0)]
STALLED = CLEAN + [
    event("dstpu:serve:fetch", 10.70, 2.10, kind="chain", chain=7, cadence_ms=2103.0),
    event("dstpu:serve:fetch", 12.81, 0.10, kind="chain", chain=8, cadence_ms=101.0),
    event("dstpu:serve:stall", 12.911, 0.0001, chain=7, kind="chain", rows=64, seconds=2.103, excess_s=2.0,
          in_fetch_s=2.1, cpu_s=0.003, gc_s=0.0, next_wait_s=0.1, cause="device_late"),
    event("dstpu:serve:stall", 12.95, 0.0001, chain=8, kind="chain", rows=64, seconds=0.5, excess_s=0.4,
          in_fetch_s=0.0, cpu_s=0.4, gc_s=0.39, next_wait_s=0.0, cause="collector"),
    event("dstpu:serve:stall", 13.50, 0.0001, chain=9, kind="chain", rows=64, seconds=9.0, excess_s=8.9,
          cause="unknown")]  # after the window
PARENT = [WINDOW, event("dstpu:serve:fetch", 10.40, 0.10, kind="chain", chain=5),
          event("dstpu:train_batch", 10.0, 1.0, step=3)]
# collections: one whole, one cut by the window's end, one before it; one on another thread
PAUSES = [WINDOW, event("dstpu:train_batch", 10.0, 1.0, step=3),
          event("dstpu:gc", 10.20, 0.004, generation=0, collected=12),
          event("dstpu:gc", 12.99, 0.030, generation=2, collected=0),
          event("dstpu:gc", 9.00, 0.500, generation=2, collected=7)]
OTHER_THREAD = [event("dstpu:gc", 11.00, 0.001, generation=1, collected=3)]


@pytest.fixture
def reading(monkeypatch):
    def of(*lines):
        monkeypatch.setattr(spans, "trace_file", lambda run: "synthetic-stalls.xplane.pb")
        monkeypatch.setattr(spans, "profile", lambda p: profile_of(*lines))
        spans.read_spans.cache_clear(), spans.report_idle.cache_clear()
        return {"workload": {"name": SERVE[0]}, "calls": []}

    yield of
    spans.read_spans.cache_clear(), spans.report_idle.cache_clear()


def test_stall_s_is_the_window_s_excess_and_zero_where_the_loop_judged_and_none_stalled(reading, capsys):
    read = harness.load_reader("stall_s.batch")
    assert read(reading(STALLED), None) == pytest.approx(2.4)  # chains 7 and 8; chain 9's span lies after the window
    said = capsys.readouterr().out
    assert "stall_in_window=device_late chain=7" in said and "stall_in_window=collector chain=8" in said
    assert read(reading(CLEAN), None) == 0.0
    assert read(reading(PARENT), None) is None  # fetches without cadence_ms: a program that keeps no log
    assert read(reading([WINDOW]), None) is None


def test_gc_pause_ms_is_the_window_s_gc_spans_on_every_thread(reading, monkeypatch):
    read = harness.load_reader("gc_pause_ms.train")
    assert read is not harness.load_reader("stall_s.batch")
    assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics", "gc_pause_ms.py"))  # one reader, two suffixes
    assert not os.path.exists(os.path.join(harness.BENCH_DIR, "metrics", "gc_pause_ms.batch.py"))
    # 4 ms whole, 10 of the 30 ms the window's end cuts, 1 ms on another thread; the one before the window not
    assert read(reading(PAUSES, OTHER_THREAD), None) == pytest.approx(15.0)
    assert harness.load_reader("gc_pause_ms.batch")(reading(PAUSES), None) == pytest.approx(14.0)
    # a window without a collection: 0.0 of a program with the hook (this one), nothing of one without
    import benchmarks.lib.harness as h

    module = h._load_module(os.path.join(harness.BENCH_DIR, "metrics", "gc_pause_ms.py"), "gc_pause_ms_under_test")
    assert module.hooked() is True and module.read(reading(PARENT), None) == 0.0
    monkeypatch.setattr(module, "hooked", lambda: False)
    assert module.read(reading(PARENT), None) is None
    assert module.read(reading(PAUSES), None) == pytest.approx(14.0)  # spans are spans, whatever the marker


def test_the_idle_table_puts_a_gap_under_a_collection_down_to_gc(monkeypatch):
    """``benchmarks/lib/spans.py`` is unchanged: the innermost span over a
    gap's midpoint. A collection inside ``serve:accept`` takes the gap."""
    host = [WINDOW, event("dstpu:serve:accept", 10.50, 0.40, kind="chain", chain=5),
            event("dstpu:gc", 10.60, 0.20, generation=2, collected=0)]
    ops = [types.SimpleNamespace(name="%fusion.1 = bf16[8] fusion()", start_ns=s * 1e9, duration_ns=d * 1e9, stats=())
           for s, d in ((10.0, 0.62), (10.78, 2.22))]
    profile = profile_of(host)
    profile.planes.append(types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name=xplane.OPS_LINE, events=ops)]))
    monkeypatch.setattr(spans, "profile", lambda p: profile)
    spans.read_spans.cache_clear()
    try:
        assert spans.idle_by_span("synthetic-gc.xplane.pb") == {"gc": pytest.approx(0.16)}
    finally:
        spans.read_spans.cache_clear()


@pytest.mark.parametrize("name", ["stall_s.batch", "gc_pause_ms.batch", "gc_pause_ms.train"])
def test_a_program_without_the_spans_reads_nothing(name, tmp_path, monkeypatch):
    """The recorded v5e trace is of PR 25's program: no ``serve:stall``, no
    ``gc`` span, no ``cadence_ms``. As the parent reads the new metrics (the
    collector's marker is the running program's, so it is taken out here)."""
    path = unpack_span_trace(tmp_path)
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: path)
    scopes.report.cache_clear(), spans.report_idle.cache_clear(), spans.read_spans.cache_clear()
    from deepspeed_tpu.telemetry import tracer

    monkeypatch.delattr(tracer, "gc_seconds")
    run = {"workload": {"name": SERVE[0]}, "calls": []}
    assert harness.load_reader(name)(run, xplane.reduce_trace(path)) is None


@pytest.mark.parametrize("name", list(NEW))
def test_the_entries_of_this_pr(name):
    unit, layer, moves, cells = NEW[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": "program_span", "layer": layer,
                     "moves": moves, "workloads": entry["workloads"]}
    assert entry["workloads"][:len(cells)] == cells  # a later cell may be appended behind them
    # the glm cell waits: tests/benchmarks/test_routed_readers.py pins the count of metrics it is listed on
    assert GLM not in entry["workloads"]
    (e2e,) = [m for m in BENCH["end_to_end"] if m["name"] == moves]
    assert set(entry["workloads"]) <= set(e2e["workloads"])  # every cell reports the metric it should move
    assert layer in {m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW}  # a layer PERF.md has
    for cell in cells:
        assert name in {m["name"] for m in harness.cell_metrics(BENCH, "per_layer", cell)}


def test_the_benchmark_only_grew():
    """Against the parent's ``BENCHMARK.json`` as git has it, where git is
    there: every entry that was there is there, in place, changed by nothing
    but cells appended to a list of cells; what came is per-layer metrics."""
    import subprocess

    shown = subprocess.run(["git", "-C", REPO, "show", "5ec4e59d064c6fbe7238104b32cb1a51321675ac:BENCHMARK.json"],
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
    assert [m["name"] for m in BENCH["per_layer"][len(before["per_layer"]):]][:3] == list(NEW)


def test_the_controls_tool_checks_its_arguments():
    from deepspeed_tpu.diagnostics.anomaly import CAUSES

    spec = importlib.util.spec_from_file_location("stall_controls", os.path.join(REPO, "tools", "stall_controls.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert set(tool.EXPECTED) == {"sleep", "stop", "device", "collect"} and set(tool.EXPECTED.values()) <= set(CAUSES)
    cell = ["--workload", SERVE[0], "--seed", "1", "--trace", "0"]
    for wrong in (["--control", "nap", "--seconds", "5"], ["--control", "sleep"],  # no such control; no --seconds
                  ["--control", "sleep", "--seconds", "5", "--at-share", "1.5"],
                  ["--control", "sleep", "--seconds", "5", "--stall-seconds", "0"]):
        with pytest.raises(SystemExit) as refused:
            tool.main(wrong + cell)
        assert refused.value.code == 2
    plant = tool.Plant("sleep", 1.0, 0.5)
    assert (plant.control, plant.at_s, plant.stall_s, plant.planted_at) == ("sleep", 1.0, 0.5, None)
