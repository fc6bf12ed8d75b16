"""Telemetry subsystem tests (tracer spans, comm accounting, exporters,
MonitorMaster integration, disabled no-op contract).

Runs in the default tier (tier-1's ``-m 'not slow'`` sweep collects it): the
telemetry substrate is what every future perf PR measures with, so its
contract stays under the cheap sweep.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.utils.compat import shard_map

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu import telemetry
from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
from deepspeed_tpu.telemetry import get_tracer
from deepspeed_tpu.telemetry.tracer import Tracer


@pytest.fixture(autouse=True)
def _clean_global_tracer():
    """The tracer is process-global (like comms_logger): leave it disabled
    and empty for the rest of the suite."""
    tr = get_tracer()
    tr.configure(enabled=False)
    tr.trace_path = None
    tr.jsonl_path = None
    tr.prometheus_path = None
    tr.reset()
    yield
    tr.configure(enabled=False)
    tr.trace_path = None
    tr.jsonl_path = None
    tr.prometheus_path = None
    tr.reset()


def _tiny_engine(config_extra=None):
    cfg = TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=2, max_seq_len=32,
    )
    eng, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=16),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 10_000,
            **(config_extra or {}),
        },
    )
    return eng


def _batch(eng, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, 64, (eng.train_batch_size, 16), dtype=np.int32)}


# --------------------------------------------------------------- tracer core
def test_span_nesting_and_timing():
    tr = Tracer(enabled=True)
    with tr.span("outer", step=3):
        time.sleep(0.01)
        with tr.span("inner"):
            time.sleep(0.005)
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # inner closes first
    inner, outer = evs
    assert inner["kind"] == outer["kind"] == "span"
    assert outer["dur"] >= 0.01 and inner["dur"] >= 0.005
    # same-thread nesting is timestamp containment (how Perfetto nests them)
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert inner["tid"] == outer["tid"] == threading.get_ident()
    assert outer["args"] == {"step": 3}
    # every span also feeds the span/<name> histogram (registry = same truth)
    assert tr.phase_summary()["outer"]["count"] == 1


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    s = tr.span("anything", big_arg="ignored")
    # the bare trace annotation (inactive without a jax.profiler session):
    # a context manager that takes late args, and nothing of the tracer's own
    assert type(s) is jax.profiler.TraceAnnotation
    with s as entered:
        entered.set_metadata(late_arg=1)
    assert not tr.recording()
    tr.count("comm/bytes", 1024)
    tr.instant("marker")
    assert tr.events() == []
    assert tr.registry.counters() == {}
    assert tr.step_scalars() == {}


def test_bounded_event_buffer():
    tr = Tracer(enabled=True, max_events=5)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events()) == 5
    assert tr.dropped_events == 5


# ----------------------------------------------------------- comm accounting
def test_comm_bytes_accounting_known_payload():
    """Facade collectives record exact (bytes, world, dtype) at trace time:
    a [2, 64] fp32 local shard over a 4-way axis is 512 bytes, world 4."""
    tr = get_tracer()
    tr.configure(enabled=True)
    dist.comms_logger.configure(enabled=True)
    dist.comms_logger.reset()

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("dp",))
    x = jnp.ones((8, 64), jnp.float32)  # local shard per rank: [2, 64]

    f = shard_map(lambda v: dist.all_reduce(v, "dp"),
                  mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    np.asarray(jax.jit(f)(x))

    counters = tr.registry.counters()
    assert counters["comm/bytes"] == 2 * 64 * 4  # one trace-time record
    assert counters["comm/bytes/all_reduce_sum"] == 512
    assert counters["comm/count"] == 1
    ev = next(e for e in tr.events() if e.get("cat") == "comm")
    assert ev["name"] == "comm:all_reduce_sum"
    assert ev["args"]["bytes"] == 512
    assert ev["args"]["world"] == 4
    assert ev["args"]["dtype"] == "float32"
    assert ev["args"]["axis"] == "dp"
    # the pre-existing comms logger keeps seeing the same traffic
    rows = dist.comms_logger.summary()
    assert any(r["op"] == "all_reduce_sum" and r["total_bytes"] == 512 for r in rows)
    dist.comms_logger.configure(enabled=False)


# ----------------------------------------------------------------- exporters
def test_chrome_trace_schema_valid(tmp_path):
    tr = get_tracer()
    tr.configure(enabled=True)
    with tr.span("phase_a", cat="span", step=1):
        with tr.span("comm:all_reduce_sum", cat="comm", bytes=2048, world=4,
                     dtype="float32", op="all_reduce_sum"):
            pass
    tr.instant("overflow", reason="test")
    tr.sample_counter("mem/device_bytes_in_use", 12345.0)

    path = telemetry.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and len(evs) >= 4
    for e in evs:
        assert "ph" in e and "name" in e
        if e["ph"] == "X":
            assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
            assert e["dur"] >= 0 and "pid" in e and "tid" in e
    comm = next(e for e in evs if e.get("cat") == "comm")
    assert comm["ph"] == "X" and comm["args"]["bytes"] == 2048
    counter = next(e for e in evs if e["ph"] == "C")
    assert counter["args"]["value"] == 12345.0
    assert doc["otherData"]["dropped_events"] == 0


def test_jsonl_export_one_event_per_line(tmp_path):
    tr = get_tracer()
    tr.configure(enabled=True)
    with tr.span("a"):
        pass
    tr.instant("b", k=1)
    path = telemetry.export_jsonl(str(tmp_path / "events.jsonl"))
    lines = [json.loads(l) for l in open(path) if l.strip()]
    # stream opens with the fleet meta line (identity + origin anchor)
    assert lines[0]["kind"] == "process_meta"
    assert "run_id" in lines[0]["identity"] and "origin_unix" in lines[0]
    evs = [l for l in lines
           if l.get("kind") in ("span", "instant", "flow", "counter")]
    assert {l["name"] for l in evs} == {"a", "b"}
    assert all("pid" in l and "ts" in l for l in evs)


# ------------------------------------------------------- engine + monitoring
def test_engine_spans_and_monitor_csv(tmp_path):
    """telemetry config block -> engine spans -> per-step scalars flow into
    the existing MonitorMaster CSV backend for free."""
    eng = _tiny_engine({
        "telemetry": {"enabled": True},
        "csv_monitor": {"enabled": True, "output_path": str(tmp_path), "job_name": "t"},
    })
    tr = get_tracer()
    assert tr.enabled  # the config block configured the global tracer
    for i in range(2):
        eng.train_batch(_batch(eng, seed=i))
    eng.flush_monitor()

    names = {e["name"] for e in tr.events()}
    assert {"train_batch", "data", "step"} <= names

    csv_dir = os.path.join(str(tmp_path), "t")
    files = os.listdir(csv_dir)
    assert any(f.startswith("Train_loss") for f in files)
    telem_files = [f for f in files if f.startswith("Telemetry_")]
    assert telem_files, files  # registry scalars reached the CSV backend
    # memory watermark gauge is part of the per-step summary
    assert any("mem" in f for f in telem_files), telem_files
    # spans keep flowing through the fwd/bwd/step parity API too
    eng.forward(_batch(eng))
    eng.backward()
    eng.step()
    names = {e["name"] for e in tr.events()}
    assert {"fwd", "bwd"} <= names


def test_engine_disabled_telemetry_records_nothing():
    eng = _tiny_engine()  # no telemetry block, tracer disabled by fixture
    eng.train_batch(_batch(eng))
    assert get_tracer().events() == []
    assert get_tracer().registry.counters() == {}


def test_checkpoint_and_dataloader_spans(tmp_path):
    eng = _tiny_engine({"telemetry": {"enabled": True}})
    eng.train_batch(_batch(eng))
    eng.save_checkpoint(str(tmp_path / "ckpt"))
    eng.load_checkpoint(str(tmp_path / "ckpt"))
    loader = eng.deepspeed_io({"input_ids": np.zeros((32, 16), np.int32)})
    next(iter(loader))
    names = {e["name"] for e in get_tracer().events()}
    assert "checkpoint:save" in names
    assert "checkpoint:load" in names
    assert "data:materialize" in names
