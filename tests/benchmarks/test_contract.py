"""BENCHMARK.json against the files it names and the builder's contract."""

import json
import os
import re

import pytest

from benchmarks.lib import harness
from tests.benchmarks.conftest import NAME, ROUTED, add_routed_toy, config_rules

REPO = os.path.dirname(harness.BENCH_DIR)
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/run.py"]
    assert BENCH["paths"] == ["benchmarks", "tests/benchmarks"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 4)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
                         + BENCH["per_layer"], ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_rules_hold_for_any_architecture(config):
    config_rules(config, harness.load_config(config["name"]), BENCH)


@pytest.mark.parametrize("name,head_dim", [("pythia-410m", 64), ("pythia-1.4b", 128)])
def test_pythia_is_published_uncut(name, head_dim):
    """Pythia's own facts, which were the rule while the benchmark had one architecture."""
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    held = harness.load_config(name)
    assert held["reduced"] == entry["reduced"] == []
    assert held["architecture"] == held["model_type"] == "gpt_neox" and held["num_hidden_layers"] == 24
    assert held["hidden_size"] // held["num_attention_heads"] == head_dim
    assert harness.load_architecture("gpt_neox").WIDTH_KEYS == (
        "hidden_size", "intermediate_size", "num_attention_heads", "rotary_pct")
    assert held["check"]["loss_rel_tol"] == 2e-4  # PR 24's; the serving one where it serves
    assert held["check"].get("logit_rel_tol") == (0.010 if name == "pythia-1.4b" else None)


DEPTH_CUT = {"key": "num_hidden_layers", "published": 24, "used": 12}


@pytest.mark.parametrize("why,entry_reduced,file_reduced,used", [
    ("a depth cut said in both places passes", ["num_hidden_layers"], [DEPTH_CUT], 12),
    ("a width is never cut", ["hidden_size"], [{"key": "hidden_size", "published": 2048, "used": 1024}], 1024),
    ("the entry does not list the file's cut", [], [DEPTH_CUT], 12),
    ("the file does not say the published value", ["num_hidden_layers"],
     [{"key": "num_hidden_layers", "used": 12}], 12),
    ("the file runs another value than it says", ["num_hidden_layers"], [DEPTH_CUT], 16),
])
def test_config_rules_on_a_cut_configuration(why, entry_reduced, file_reduced, used):
    entry = dict(next(c for c in BENCH["configs"] if c["name"] == "pythia-1.4b"), reduced=entry_reduced)
    held = dict(harness.load_config("pythia-1.4b"), reduced=file_reduced)
    held[file_reduced[0]["key"]] = used
    if why.endswith("passes"):
        config_rules(entry, held, BENCH)
    else:
        with pytest.raises(AssertionError):
            config_rules(entry, held, BENCH)


def _without(key):
    def edit(check):
        del check[key]
    return edit


def _with(key, value):
    def edit(check):
        check[key] = value
    return edit


@pytest.mark.parametrize("why,renamed,edit,error,says", [
    ("the routed members, both tolerances between their readings and a reference that takes picks pass",
     None, None, None, None),
    ("the architecture file hands no picks out of put", "put_with_picks", None, AttributeError,
     "lacks put_with_picks: a routed model is checked at the program's own expert picks"),
    ("nor out of the fused prefill and the chain", "generate_with_picks", None, AttributeError,
     "lacks generate_with_picks: a routed model is checked at the program's own expert picks"),
    ("the configuration states no tolerance for the audit", None, _without("route_shortfall_tol"), KeyError,
     "states no check.route_shortfall_tol"),
    ("an audit of five sigmas guards nothing", None, _with("route_shortfall_tol", 5.0), AssertionError, None),
    ("a tolerance for the chain's tokens apart is not part of the protocol: it is held as a share",
     None, _with("token_gap_tol", 2.0), AssertionError, None),
    ("the readings behind the tolerances are not given", None, _without("readings"), AssertionError,
     "check.readings.logit_rel_tol has to give sound_max"),
    ("a tolerance the control would pass", None, _with("logit_rel_tol", 0.04), AssertionError,
     "check.logit_rel_tol 0.04 does not lie between its readings"),
    ("a tolerance a sound run would fail", None, _with("route_shortfall_tol", 0.02), AssertionError,
     "check.route_shortfall_tol 0.02 does not lie between its readings"),
])
def test_a_listed_routed_configuration_that_serves_hands_out_its_picks(bench_copy, why, renamed, edit, error,
                                                                       says):
    """The rule for every configuration ``BENCHMARK.json`` lists whose
    architecture file says ``routed_layers(cfg) > 0`` and which has a serving
    cell; the toy ``mixtral`` of ``test_data_driven.py`` says nothing and is not held to it."""
    source = open(os.path.join(ROUTED, "architecture.py")).read()
    if renamed:
        source = source.replace(f"def {renamed}", f"def not_{renamed}")
    bench = add_routed_toy(bench_copy, source)
    held = harness.load_config("routed-toy", bench_copy)
    if edit:
        edit(held["check"])
    if error is None:
        config_rules(bench["configs"][-1], held, bench, bench_copy)
    else:
        with pytest.raises(error, match=says):
            config_rules(bench["configs"][-1], held, bench, bench_copy)


def test_a_configuration_without_a_tolerance_inherits_none():
    from benchmarks.lib import program

    held = harness.load_config("pythia-410m")  # trains only: no serving tolerance was measured
    assert program.tolerance(held, "loss_rel_tol") == 2e-4
    with pytest.raises(KeyError, match="states no check.logit_rel_tol"):
        program.tolerance(held, "logit_rel_tol")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_each_cell_has_its_files_and_its_metrics(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    held = harness.load_workload(cell["name"])
    assert (held["name"], held["config"], held["chips"], held["why"]) == (
        cell["name"], cell["config"], cell["chips"], cell["why"])
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert os.path.isfile(os.path.join(harness.BENCH_DIR, "runners", held["kind"] + ".py"))
    reported = harness.cell_metrics(BENCH, "end_to_end", cell["name"])
    assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
    assert harness.cell_metrics(BENCH, "per_layer", cell["name"])


def test_end_to_end_metrics():
    assert E2E["setup_s"]["bound"] <= 0.1 and "workloads" not in E2E["setup_s"]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert set(cells_of(m)) <= set(CELLS)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_its_reader_and_its_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert callable(harness.load_reader(metric["name"]))
    assert cells_of(metric) and set(cells_of(metric)) <= set(CELLS)
    for cell in cells_of(metric):  # the metric it moves is reported wherever it is
        assert cell in cells_of(E2E[metric["moves"]])
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%" and metric["better"] == "higher"


# what waits under benchmarks/ for the chat cell (PERF.md, section 7)
WAITING_READERS = {"prefill_ms", "queue_wait_p95_ms"}
WAITING_CELLS = {"pythia-1.4b.serve.chat"}


def test_every_reader_is_listed_or_waits_for_its_cell():
    stems = {f[:-3] for f in os.listdir(os.path.join(harness.BENCH_DIR, "metrics"))
             if f.endswith(".py")}
    used = {m["name"] for m in BENCH["per_layer"]} | {m["name"].rpartition(".")[0]
                                                     for m in BENCH["per_layer"]}
    assert stems - used == WAITING_READERS


def test_every_workload_file_is_a_cell_or_says_why_not():
    files = {f[:-5] for f in os.listdir(os.path.join(harness.BENCH_DIR, "workloads"))}
    assert files - set(CELLS) == WAITING_CELLS
    for name in WAITING_CELLS:
        assert "NOT a cell" in harness.load_workload(name)["status"]
    with pytest.raises(KeyError, match="not a cell"):
        harness.cell_metrics(BENCH, "end_to_end", "pythia-1.4b.serve.chat")
