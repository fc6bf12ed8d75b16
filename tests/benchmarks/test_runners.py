"""Each runner end to end at a tiny size on the CPU, the device check steered
here; the last line against the contract's keys; refusal without a TPU."""

import json
import os
import subprocess
import sys

import jax
import pytest

from benchmarks.lib import harness
from tests.benchmarks.conftest import run_cell, tiny_serve_workload, tiny_train_workload

REPO = os.path.dirname(harness.BENCH_DIR)
BENCH = harness.load_benchmark()
UNITS = {m["name"]: m["unit"] for m in BENCH["end_to_end"]}
UNITS.update(serve_ttft_p95_ms="ms", serve_tpot_p95_ms="ms")  # wait for the chat cell


def check_last_line(run, wanted):
    metrics = {n: {"value": run["end_to_end"][n], "unit": UNITS[n]} for n in wanted}
    line = harness.last_line(run["correct"], run["attempted"], run["failed"], metrics,
                             harness.device_report(jax.devices()[:1], run["memory"]),
                             compared=run["compared"])
    out = json.loads(line)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert set(out["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0 and " " not in m["unit"]
    return out


@pytest.mark.parametrize("in_flight", [1, 8])
def test_train_runner(cpu_counts_as_chip, tiny_config, in_flight):
    workload = tiny_train_workload()
    workload["traffic"]["steps_in_flight"] = in_flight
    run = run_cell(workload, tiny_config)
    wanted = [m["name"] for m in harness.cell_metrics(BENCH, "end_to_end", workload["name"])]
    out = check_last_line(run, wanted)
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s_chip"}
    assert run["compiles_in_window"] == 0 and len(run["step_s"]) == run["attempted"]
    tokens = run["attempted"] * 4 * 64
    # step_s runs from one step's end to the next one's, so it adds up to the window
    assert run["end_to_end"]["train_tokens_per_s_chip"] == pytest.approx(tokens / sum(run["step_s"]))


def test_train_runner_zero3_over_four_devices(cpu_counts_as_chip, tiny_config):
    workload = tiny_train_workload(chips=4, mesh={"fsdp": 4})
    workload["engine"]["zero_optimization"] = {"stage": 3}
    run = run_cell(workload, tiny_config)
    assert run["correct"] and run["chips"] == 4 and run["micro_batches_per_step"] == 1


@pytest.mark.parametrize("kind,wanted", [
    ("batch", {"setup_s", "serve_out_tokens_per_s"}),
    ("chat", {"setup_s", "serve_ttft_p95_ms", "serve_tpot_p95_ms"}),
])
def test_serve_runner(cpu_counts_as_chip, tiny_config, kind, wanted):
    workload = tiny_serve_workload(kind)
    run = run_cell(workload, tiny_config)
    out = check_last_line(run, wanted)
    assert run["compiles_in_window"] == 0
    assert all(r["ok"] and r["tokens"] == 12 and r["ttft_s"] > 0 for r in run["requests"])
    if kind == "batch":
        assert run["attempted"] % 8 == 0  # whole waves, the one in flight completed


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell,tiny", [
    ("pythia-410m.train.seq2048", tiny_train_workload),
    ("pythia-1.4b.serve.batch", lambda: tiny_serve_workload("batch")),
])
def test_run_py_prints_the_cell_s_metrics_last(cpu_counts_as_chip, tiny_config, monkeypatch,
                                               capsys, cell, tiny, trace):
    """``run.py`` itself, from the arguments to the last line: which metrics a
    cell reports, and their units, are BENCHMARK.json's. The CPU gives the
    profiler no device plane, so the traced run reads the recorded v5e trace."""
    from benchmarks import run as run_py
    from benchmarks.lib import xplane

    workload = tiny()
    monkeypatch.setattr(harness, "load_workload", lambda name: workload)
    monkeypatch.setattr(harness, "load_config", lambda name: tiny_config)
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: os.path.join(
        REPO, "tests", "benchmarks", "data", "v5e_1chip_sample.xplane.pb"))
    assert run_py.main(["--workload", cell, "--seed", str(2**31 + 5), "--seconds", "1",
                        "--trace", str(trace)]) == 0
    printed = capsys.readouterr()
    out = json.loads(printed.out.splitlines()[-1])
    # every number compared beside its limit: last in the line, and the end of standard error
    assert list(out)[-1] == "compared" and all(found <= limit for found, limit in out["compared"].values())
    assert printed.err.splitlines()[-len(out["compared"]):] == [
        f"compared {name}={found} limit={limit}" for name, (found, limit) in out["compared"].items()]
    group = "per_layer" if trace else "end_to_end"
    listed = {m["name"]: m["unit"] for m in harness.cell_metrics(BENCH, group, cell)}
    assert out["correct"] is True and out["metrics"]
    for name, m in out["metrics"].items():
        assert m["unit"] == listed[name]
    if trace:
        assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > out["device"]["busy_s"]
        assert len(out["breakdown"]["device_ops"]) <= 10 and out["breakdown"]["idle_gaps"]
    else:
        assert set(out["metrics"]) == set(listed)


# what PR 27's ``check`` printed for the tiny gpt_neox engine on the CPU, read from a copy of that
# commit under this suite's XLA flags (``tests/conftest.py``: backend optimization level 1, which moves
# the sixth digit; two runs alike), before PR 29 taught ``check`` a routed architecture's picks
PARENT_DENSE_CHECK = {
    2**31 + 5: "check_logit_rel_err=[0.006228420417755842, 0.006386684253811836, 0.006014551036059856] "
               "tol=0.01 generated_token_gap=0.0 gap_tol=0.09 ok=True",
    41: "check_logit_rel_err=[0.005732002668082714, 0.0060546803288161755, 0.005712674930691719] "
        "tol=0.01 generated_token_gap=0.016517234966158867 gap_tol=0.09 ok=True",
}


@pytest.mark.parametrize("seed", PARENT_DENSE_CHECK)
def test_the_dense_check_prints_the_parents_numbers_to_the_last_digit(tiny_config, capsys, seed):
    """An architecture file that says nothing of routing is checked by the
    code that always checked it."""
    import jax.numpy as jnp

    from benchmarks.lib import program
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.topology.mesh import build_mesh

    serve = harness.load_runner("serve")
    model_cfg = program.model_config(tiny_config, jnp.bfloat16)
    mesh = build_mesh(devices=jax.devices()[:1], axis_sizes={"tp": 1, "dp": 1})
    engine = InferenceEngineV2(model_cfg, serve.make_weights(model_cfg, seed),
                               dict(tiny_serve_workload("batch")["engine"]), mesh=mesh)
    architecture = harness.load_architecture("gpt_neox")
    assert program.routing(architecture, tiny_config) is None
    ok, compared = serve.check(engine, harness.load_reference("gpt_neox"), architecture, tiny_config, seed)
    said = [line for line in capsys.readouterr().out.splitlines() if line.startswith("check_")]
    assert said == [PARENT_DENSE_CHECK[seed]]
    assert ok and list(compared) == ["logit_rel_err", "token_gap"]  # no audit of a router it has not


def test_run_py_refuses_what_is_not_a_cell(capsys):
    from benchmarks import run as run_py

    assert run_py.main(["--workload", "pythia-1.4b.serve.chat", "--seconds", "1"]) == 5
    assert "not a cell of BENCHMARK.json" in capsys.readouterr().err


def test_a_wrong_model_is_not_correct(cpu_counts_as_chip, tiny_config, monkeypatch):
    """The reference given another activation than the program runs: the
    comparison that decides ``correct`` has to notice."""
    reference = harness.load_reference("gpt_neox")
    real = reference.layer
    monkeypatch.setattr(reference, "layer",
                        lambda x, w, cfg: real(x, w, dict(cfg, hidden_act="relu")))
    monkeypatch.setattr(harness, "load_reference", lambda arch, bench_dir=None: reference)
    assert run_cell(tiny_train_workload(), tiny_config)["correct"] is False


def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "pythia-410m.train.seq2048",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode not in (0, None)
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_run_py_refuses_a_checkout_without_the_program(tmp_path):
    """BENCHMARK.json and the files under ``paths`` alone are not a benchmark."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(REPO, "tests", "benchmarks"), tmp_path / "tests" / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "pythia-410m.train.seq2048",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=300)
    assert proc.returncode not in (0, None) and "deepspeed_tpu/, is not in this checkout" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_require_devices_counts_chips(monkeypatch):
    class Dev:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    assert len(harness.require_devices(1)) == 1
    with pytest.raises(harness.NoDevice):
        harness.require_devices(4)
