"""Tiny cells for the CPU: the published Pythia files with every size cut, so
that each runner goes end to end in seconds. Nothing here describes a chip."""

import functools
import gzip
import json
import os
import re
import shutil

import pytest

from benchmarks.lib import harness, peaks, program

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# what an architecture file gives the runners and the readers
ARCHITECTURE_FILE = ("WIDTH_KEYS", "reference_weights", "matmul_params", "total_params", "layers",
                     "heads", "kv_heads", "head_dim")
TINY_MODEL = dict(hidden_size=64, intermediate_size=256, num_hidden_layers=2,
                  num_attention_heads=4, vocab_size=512, max_position_embeddings=256)
# what a ``check`` block holds beside its tolerances; and the one tolerance that is no share
CHECK_NOTES = ("why", "readings")
TOLERANCE_BELOW = {"route_shortfall_tol": 0.5}
# the tiny configuration's own tolerances, for the CPU: Pythia's on the chip,
# which its bf16 error at two layers and hidden 64 stays well inside
TINY_CHECK = {"logit_rel_tol": 0.010, "loss_rel_tol": 2e-4,
              "why": "tests/benchmarks: a tiny gpt_neox on the CPU"}


# --- a ROUTED architecture, served by a stand-in for the program ----------
#
# The program cannot run a sigmoid router with a correction bias, renormalised
# and scaled weights and a shared expert yet (PERF.md, section 7), so the
# benchmark's half of the routed check is proved against the stand-in engine
# under ``data/routed_architecture/``, through an architecture file and a plain
# reference that follow the contract and are copied in as new files.
ROUTED = os.path.join(os.path.dirname(__file__), "data", "routed_architecture")
ROUTED_TOY = {
    "name": "routed-toy", "source": "tests/benchmarks/data/routed_architecture",
    "architecture": "routed_toy", "reference": "benchmarks/reference/routed_toy.py",
    "deployment": "a CPU test", "reduced": [], "assumed": {"weights": "random, from --seed"},
    "check": {
        "logit_rel_tol": 0.0148, "route_shortfall_tol": 0.1,
        "readings": {
            "logit_rel_tol": {"sound_max": 0.01229, "control_min": 0.0349},
            "route_shortfall_tol": {"sound_max": 0.031, "control_min": 1.73}},
        "why": "bf16 stand-in against fp32 on the CPU, 16 seeds. logit_rel_tol: at the engine's own picks "
               "0.01082-0.01229 (the plain comparison of the same runs 0.026-0.213), the control an e4m3 "
               "cache, 0.0349-0.0369: 1.2 x the largest. route_shortfall_tol: honest 0.011-0.031 sigma, the "
               "controls ranks 2..k+1 1.73-2.27 and the correction bias ignored 1.81-2.12. Serves only: no "
               "loss_rel_tol"},
    "model_type": "routed_toy", "vocab_size": 512, "hidden_size": 256, "intermediate_size": 512,
    "moe_intermediate_size": 64, "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "n_routed_experts": 32, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "norm_topk_prob": True, "topk_method": "noaux_tc",
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "max_position_embeddings": 256,
}


@functools.lru_cache(maxsize=None)
def _stand_in_module():
    # loaded once: every load would bring a jitted step of its own, compiled anew
    return harness._load_module(os.path.join(ROUTED, "engine.py"), "routed_stand_in_engine")


def routed_stand_in(seed, faults=(), config=ROUTED_TOY):
    """The stand-in engine with weights from ``seed``, as a runner would build the program."""
    import jax

    engine_py = _stand_in_module()
    engine = engine_py.StandInEngine(program.published(config), None, faults=faults)
    engine.params = engine_py.make_params(jax.random.PRNGKey(seed & 0x7FFFFFFF), engine.spec)
    return engine


@pytest.fixture
def cpu_counts_as_chip(monkeypatch):
    """Steer the device check (and the peaks table) so a runner takes the CPU."""
    import jax

    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(peaks.DEVICE_PEAKS, kind, peaks.DEVICE_PEAKS["TPU v5 lite"])
    monkeypatch.setattr(harness, "require_devices", lambda chips: jax.devices()[:chips])


@pytest.fixture
def tiny_config():
    cfg = harness.load_config("pythia-410m")
    cfg.update(TINY_MODEL, check=TINY_CHECK)
    return cfg


def run_cell(workload, config, seed=2**31 + 5, seconds=1.5, bench_dir=harness.BENCH_DIR):
    """One run of a cell's runner, its files found by name under ``bench_dir``
    as ``run.py`` finds them, with what ``run.py`` adds for the readers."""
    import time

    runner = harness.load_runner(workload["kind"], bench_dir)
    architecture = harness.load_architecture(config["architecture"], bench_dir)
    devices = harness.require_devices(workload["chips"])
    run = runner.run(workload=workload, config=config,
                     reference=harness.load_reference(config["architecture"], bench_dir),
                     architecture=architecture, seed=seed, seconds=seconds, devices=devices,
                     trace_dir=None, compiles=harness.CompileCounter(),
                     t_process_start=time.perf_counter())
    run.update(workload=workload, config=config, architecture=architecture,
               device_kind=devices[0].device_kind)
    return run


def tiny_train_workload(chips=1, mesh=None):
    wl = harness.load_workload("pythia-410m.train.seq2048")
    wl["chips"] = chips
    wl["traffic"].update(sequences=4, seq_len=64)
    wl["engine"].update(train_micro_batch_size_per_gpu=2 if chips == 1 else 1,
                        gradient_accumulation_steps=2 if chips == 1 else 1,
                        mesh=mesh or {"dp": 1})
    return wl


def tiny_serve_workload(kind):
    wl = harness.load_workload("pythia-1.4b.serve.batch")
    wl["engine"].update(kv_pool_bytes=None, num_kv_blocks=256, max_seqs=8,
                        chunk_bucket=32, row_bucket=4)
    if kind == "batch":
        wl["traffic"].update(wave=8, prompt_len={"dist": "uniform", "min": 8, "max": 32},
                             output_tokens=12)
        wl["warm"] = {"prefill": [], "chain_rows": [8], "chain_prompt_len": 32}
    else:
        wl["traffic"] = {"kind": "open_loop", "rate_per_s": 10,
                         "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                                        "min": 4, "max": 64},
                         "output_tokens": 12}
        wl["warm"] = {"prefill": [[4, 64], [8, 32], [8, 64]], "chain_rows": [4, 8],
                      "chain_prompt_len": 32}
    return wl


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of BENCHMARK.json and benchmarks/ that a test may add to."""
    dst = tmp_path / "benchmarks"
    shutil.copytree(harness.BENCH_DIR, dst, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(os.path.dirname(harness.BENCH_DIR), "BENCHMARK.json"), tmp_path)
    return str(dst)


def unpack_span_trace(directory) -> str:
    """The v5e trace ``record_span_trace.py`` took, unpacked into ``directory``."""
    path = os.path.join(str(directory), "v5e_1chip_spans.xplane.pb")
    packed = os.path.join(os.path.dirname(__file__), "data", "v5e_1chip_spans.xplane.pb.gz")
    with gzip.open(packed, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def add_to_benchmark(bench_copy, cell, config, like, metric=None, source="test"):
    """Entries added to the copy's BENCHMARK.json, none there changed but the
    lists of cells that the metrics of the cell ``like`` are reported in."""
    path = os.path.join(os.path.dirname(bench_copy), "BENCHMARK.json")
    bench = harness.load_json(path)
    bench["configs"].append({"name": config, "source": source, "reduced": [], "why": "test",
                             "file": f"benchmarks/configs/{config}.json"})
    bench["workloads"].append({"name": cell, "config": config, "chips": 1, "why": "test",
                               "traffic": cell.partition(".")[2]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(cell)
    if metric:
        bench["per_layer"].append({"name": metric, "unit": "count", "better": "higher",
                                   "source": "program_counter", "layer": "engine",
                                   "moves": "train_tokens_per_s_chip", "workloads": [cell]})
    write_json(path, bench)
    return bench


def add_routed_toy(bench_copy, architecture_source=None):
    """The routed toy added to a copy of ``benchmarks/`` by new files alone:
    architecture file, reference, configuration, cell, appended entries."""
    architecture_source = architecture_source or open(os.path.join(ROUTED, "architecture.py")).read()
    with open(os.path.join(bench_copy, "architectures", "routed_toy.py"), "w") as f:
        f.write(architecture_source)
    shutil.copy(os.path.join(ROUTED, "reference.py"), os.path.join(bench_copy, "reference", "routed_toy.py"))
    write_json(os.path.join(bench_copy, "configs", "routed-toy.json"), ROUTED_TOY)
    workload = dict(tiny_serve_workload("batch"), name="routed-toy.serve.batch", config="routed-toy")
    write_json(os.path.join(bench_copy, "workloads", "routed-toy.serve.batch.json"), workload)
    return add_to_benchmark(bench_copy, "routed-toy.serve.batch", "routed-toy", "pythia-1.4b.serve.batch",
                            source=ROUTED_TOY["source"])


def config_rules(entry, held, bench, bench_dir=harness.BENCH_DIR):
    """What holds for a configuration of ANY architecture: its entry in
    ``BENCHMARK.json`` against its file and the files that file names."""
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmarks/configs/{entry['name']}.json"
    assert held["name"] == entry["name"] and held["source"] == entry["source"]
    # the file says of every cut: key, published value, value used; the entry lists the keys
    assert [set(r) for r in held["reduced"]] == [{"key", "published", "used"}] * len(entry["reduced"])
    assert [r["key"] for r in held["reduced"]] == entry["reduced"]
    architecture = harness.load_architecture(held["architecture"], bench_dir)
    assert all(hasattr(architecture, name) for name in ARCHITECTURE_FILE)
    for cut in held["reduced"]:
        assert NAME.match(cut["key"]) and cut["key"] not in architecture.WIDTH_KEYS  # a width is never cut
        assert held[cut["key"]] == cut["used"] != cut["published"]
    assert all(k in held for k in architecture.WIDTH_KEYS)
    assert held["reference"] == f"benchmarks/reference/{held['architecture']}.py"
    assert os.path.isfile(os.path.join(bench_dir, "reference", held["architecture"] + ".py"))
    assert isinstance(held["check"]["why"], str) and len(held["check"]) >= 2
    # a tolerance is a small share; a router's shortfall, in sigmas of its scores, stays under half a one
    assert all(0 < v < TOLERANCE_BELOW.get(k, 0.1) for k, v in held["check"].items() if k not in CHECK_NOTES)
    cells = [w["name"] for w in bench["workloads"] if w["config"] == entry["name"]]
    assert cells
    if any(harness.load_workload(c, bench_dir)["kind"] == "serve" for c in cells):
        routed_rules(held, architecture, harness.load_reference(held["architecture"], bench_dir))


def routed_rules(held, architecture, reference):
    """A routed configuration that serves is checked at the program's own
    expert picks (PERF.md, sections 2 and 7): its architecture file hands them
    out of ``put`` and of the chain, its reference takes them and audits them,
    its file states both tolerances, each between the two readings it was set
    from. An architecture file that says nothing of routing is held to none of it."""
    import inspect

    routing = program.routing(architecture, held)  # says what to write where a member is missing
    if routing is None:
        return
    assert "picks" in inspect.signature(reference.forward).parameters, (
        f"benchmarks/reference/{held['architecture']}.py: forward(weights, cfg, tokens, picks=None) has to "
        "send every position to the experts in picks [B, S, routed_layers, k]")
    assert callable(getattr(reference, "route_shortfall", None)), (
        f"benchmarks/reference/{held['architecture']}.py lacks route_shortfall(weights, cfg, tokens, picks) "
        "-> float32 [B, S, routed_layers]")
    for key in ("logit_rel_tol", "route_shortfall_tol"):
        tol = program.tolerance(held, key)
        read = held["check"].get("readings", {}).get(key)
        assert read and set(read) == {"sound_max", "control_min"}, (
            f"configuration {held['name']!r}: check.readings.{key} has to give sound_max, the largest that "
            "sound runs of the program read over a dozen seeds, and control_min, the smallest that the "
            "control (the nearest lower precision; for the audit a router that could not have made the "
            "picks) reads, both on the chip at the cell's own size (PERF.md, section 7)")
        assert read["sound_max"] < tol < read["control_min"], (
            f"configuration {held['name']!r}: check.{key} {tol} does not lie between its readings {read}")
