"""Diagnostics subsystem tests (health policies, recompile detector,
step-time anomaly, flight recorder, disabled no-op contract).

Default tier: like telemetry, the diagnostics contract is what every future
reliability claim leans on, so it stays under the cheap sweep. Engine-level
tests use the SimpleMLP fixture on the 8-device CPU mesh; NaN injection goes
through the batch (a NaN input poisons the whole backward), matching how a
bad shard poisons a real run.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.diagnostics import (
    FlightRecorder,
    RecompileDetector,
    StepTimeAnomalyDetector,
    TrainingHealthError,
)
from deepspeed_tpu.telemetry import get_tracer
from tests.unit.simple_model import random_batch, simple_model_spec


@pytest.fixture(autouse=True)
def _clean_global_tracer():
    tr = get_tracer()
    tr.configure(enabled=False)
    tr.trace_path = None
    tr.jsonl_path = None
    tr.reset()
    yield
    tr.configure(enabled=False)
    tr.trace_path = None
    tr.jsonl_path = None
    tr.reset()


def _engine(diag=None, extra=None):
    eng, *_ = deepspeed_tpu.initialize(
        model=simple_model_spec(),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 10_000,
            **({"diagnostics": diag} if diag else {}),
            **(extra or {}),
        },
    )
    return eng


def _poisoned(batch):
    bad = {k: np.array(v, copy=True) for k, v in batch.items()}
    bad["x"][0, 0] = np.nan
    return bad


def _params(eng):
    return jax.device_get(eng.state.params)


def _same(a, b):
    return all(np.array_equal(x, y) for x, y in
               zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


# ------------------------------------------------------------ health policies
def test_nan_injection_skip_step_policy():
    """skip_step: the poisoned step applies NO update (params, opt state,
    step counter all frozen — the fp16 overflow-skip select, extended to
    bf16/fp32 runs the loss scaler never watches)."""
    eng = _engine({"enabled": True, "health": {"nonfinite_policy": "skip_step"}})
    batch = random_batch(eng.train_batch_size)
    eng.train_batch(batch)
    assert eng.global_steps == 1
    before = _params(eng)

    m = eng.train_batch(_poisoned(batch))
    assert bool(m["health/skip"])
    assert bool(m["health/nonfinite_any"])
    assert int(m["health/nonfinite_total"]) > 0
    # per-leaf-group attribution names the layer group(s) that went nonfinite
    groups = [k for k in m if k.startswith("health/nonfinite/")]
    assert groups and any(int(m[k]) > 0 for k in groups)
    assert eng.global_steps == 1  # skipped step does not count
    assert _same(before, _params(eng))

    m2 = eng.train_batch(batch)  # clean step applies again
    assert not bool(m2["health/skip"])
    assert eng.global_steps == 2
    assert not _same(before, _params(eng))


def test_nan_injection_log_policy_applies_update():
    """log: the verdict is recorded but the update still applies (and the
    step counter advances) — observation only."""
    eng = _engine({"enabled": True, "health": {"nonfinite_policy": "log"}})
    batch = random_batch(eng.train_batch_size)
    eng.train_batch(batch)
    m = eng.train_batch(_poisoned(batch))
    assert bool(m["health/nonfinite_any"])
    assert not bool(m["health/skip"])
    assert eng.global_steps == 2


def test_nan_injection_abort_policy_raises_and_dumps(tmp_path):
    eng = _engine({
        "enabled": True,
        "health": {"nonfinite_policy": "abort"},
        "flight_recorder": {"dump_dir": str(tmp_path),
                            "install_signal_handlers": False,
                            "dump_on_exception": False},
    })
    batch = random_batch(eng.train_batch_size)
    eng.train_batch(batch)
    with pytest.raises(TrainingHealthError) as ei:
        eng.train_batch(_poisoned(batch))
    assert ei.value.verdicts.get("health/nonfinite_any")
    assert ei.value.dump_path and os.path.exists(ei.value.dump_path)
    # abort also skipped the poisoned update
    assert eng.global_steps == 1


def test_grad_spike_zscore_detection():
    """A 1000x-scaled batch after a stable warmup trips the grad-norm
    z-score; with policy log the verdict lands in metrics."""
    eng = _engine({"enabled": True, "health": {
        "grad_spike_policy": "log", "warmup_steps": 4, "grad_spike_zscore": 4.0,
        "ema_beta": 0.9}})
    batch = random_batch(eng.train_batch_size)
    for i in range(8):  # stable baseline past warmup
        m = eng.train_batch(random_batch(eng.train_batch_size, seed=i))
        assert not bool(m["health/grad_spike"])
    spike = {k: np.array(v, copy=True) for k, v in batch.items()}
    spike["x"] *= 1000.0
    m = eng.train_batch(spike)
    assert bool(m["health/grad_spike"])
    assert float(m["health/grad_zscore"]) > 4.0


def test_health_ema_not_poisoned_by_skipped_step():
    """The EMA baseline must ignore skipped steps: after a NaN step the
    count stays put and later clean steps are not judged against NaN."""
    eng = _engine({"enabled": True, "health": {"nonfinite_policy": "skip_step"}})
    batch = random_batch(eng.train_batch_size)
    eng.train_batch(batch)
    c1 = int(eng.state.health.count)
    eng.train_batch(_poisoned(batch))
    assert int(eng.state.health.count) == c1
    assert np.isfinite(float(eng.state.health.gnorm_ema))
    m = eng.train_batch(batch)
    assert not bool(m["health/skip"])


# ------------------------------------------------------- disabled-path no-op
def test_disabled_diagnostics_is_noop():
    eng = _engine()  # no diagnostics block
    assert eng.diagnostics is None
    assert eng.state.health is None
    m = eng.train_batch(random_batch(eng.train_batch_size))
    assert not any(k.startswith("health/") for k in m)
    # and nothing leaked into the (disabled) tracer
    assert get_tracer().events() == []


def test_disabled_health_block_keeps_state_none():
    eng = _engine({"enabled": True, "health": {"enabled": False},
                   "flight_recorder": {"install_signal_handlers": False,
                                       "dump_on_exception": False}})
    assert eng.diagnostics is not None and eng._health is None
    assert eng.state.health is None
    m = eng.train_batch(random_batch(eng.train_batch_size))
    assert not any(k.startswith("health/") for k in m)


# ----------------------------------------------------------------- recompile
def test_recompile_detector_warns_once_naming_argument():
    det = RecompileDetector("unit", arg_names=("x",))
    f = det.wrap(jax.jit(lambda x: x * 2))
    f(jnp.ones((4, 8)))  # initial compile: expected, no warning
    f(jnp.ones((4, 8)))  # cache hit
    assert det.compiles == 1 and det.recompiles == 0

    f(jnp.ones((4, 16)))  # forced shape change -> exactly one recompile event
    assert det.recompiles == 1
    recs = [e for e in det.events if e["kind"] == "recompile"]
    assert len(recs) == 1
    assert any("x" in d and "(4, 8)" in d and "(4, 16)" in d for d in recs[0]["diff"])

    f(jnp.ones((4, 16)))  # stable again: no new events
    assert det.recompiles == 1


def test_recompile_storm_escalates():
    det = RecompileDetector("storm", storm_threshold=3, storm_window_s=60.0)
    f = det.wrap(jax.jit(lambda x: x + 1))
    for n in range(2, 7):  # every call a new shape
        f(jnp.ones((n,)))
    assert det.recompiles >= 3
    assert any(e["kind"] == "storm" for e in det.events)


def test_engine_forced_recompile_fires_detector():
    """An unpadded sequence length (the classic silent-recompile trigger)
    recompiles the fused step; the engine's detector names the changed leaf
    exactly once."""
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    cfg = TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=2, max_seq_len=64,
    )
    eng, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=16),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 10_000,
            "diagnostics": {"enabled": True, "health": {"enabled": False},
                            "flight_recorder": {"install_signal_handlers": False,
                                                "dump_on_exception": False}},
        },
    )

    def tok_batch(seq, seed=0):
        rng = np.random.default_rng(seed)
        return {"input_ids": rng.integers(
            0, 64, (eng.train_batch_size, seq), dtype=np.int32)}

    eng.train_batch(tok_batch(16))
    eng.train_batch(tok_batch(16, seed=1))
    det = eng.diagnostics.detector("train_step")
    assert det is not None and det.recompiles == 0

    eng.train_batch(tok_batch(24, seed=2))
    assert det.recompiles == 1
    recs = [e for e in det.events if e["kind"] == "recompile"]
    assert len(recs) == 1
    assert any("input_ids" in d and "16" in d and "24" in d
               for d in recs[0]["diff"]), recs[0]["diff"]


def test_inference_bucketing_no_recompile_within_bucket():
    """The v1 engine's seq_bucket claim, now checked: prompts inside one
    bucket never recompile; a new bucket is an expected first compile."""
    from deepspeed_tpu.models import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=2, max_seq_len=128,
    )
    import flax.linen as nn  # noqa: F401  (CausalLM import path warmup)
    from deepspeed_tpu.models import CausalLM

    module = CausalLM(cfg)
    params = module.init({"params": jax.random.PRNGKey(0)},
                         {"input_ids": jnp.zeros((1, 8), jnp.int32)},
                         train=False)["params"]
    eng = deepspeed_tpu.init_inference(
        cfg, params=params, config={"dtype": "fp32", "seq_bucket": 32})
    assert eng._gen_detector is not None
    eng.generate(np.ones((1, 10), np.int32), max_new_tokens=4)
    eng.generate(np.ones((1, 20), np.int32), max_new_tokens=4)  # same bucket
    eng.generate(np.ones((1, 17), np.int32), max_new_tokens=4)  # same bucket
    det = eng._gen_detector
    assert det.compiles == 1 and det.recompiles == 0
    eng.generate(np.ones((1, 40), np.int32), max_new_tokens=4)  # new bucket
    assert det.compiles == 2 and det.recompiles == 0


# ------------------------------------------------------------------- anomaly
def test_step_time_straggler_and_regression_flags():
    tr = get_tracer()
    det = StepTimeAnomalyDetector(window=32, straggler_mads=6.0,
                                  regression_factor=1.3, min_samples=8,
                                  name="t", tracer=tr)
    for _ in range(16):
        flags = det.observe(0.100)
        assert not flags["straggler"] and not flags["regression"]
    flags = det.observe(1.0)  # 10x median: straggler, not yet a regression
    assert flags["straggler"]
    assert det.stragglers == 1
    for _ in range(12):  # sustained 1.5x shift
        flags = det.observe(0.150)
    assert flags["regression"]
    gauges = tr.registry.gauges()
    assert gauges["anomaly/t_median_ms"] > 0
    assert gauges["anomaly/t_regression"] == 1.0


def test_the_rule_is_written_once_and_the_detector_uses_it(monkeypatch):
    """``beyond`` is the median + MAD test: past the median by more than
    ``mads`` MADs, a MAD of at least 1% of the median. The detector's verdict
    is its verdict."""
    from deepspeed_tpu.diagnostics import anomaly

    assert anomaly.beyond([0.10, 0.11, 0.09, 0.10, 0.12], 0.17, 6.0) == (True, 0.10, pytest.approx(0.01))
    assert anomaly.beyond([0.10, 0.11, 0.09, 0.10, 0.12], 0.159, 6.0)[0] is False
    slow, med, mad = anomaly.beyond([0.1] * 8, 0.1061, 6.0)  # identical timings: the floor, 1% of the median
    assert (slow, med, mad) == (True, 0.1, pytest.approx(0.001))
    assert anomaly.beyond([0.1] * 8, 0.1059, 6.0)[0] is False
    asked = []
    monkeypatch.setattr(anomaly, "beyond", lambda prior, value, mads: asked.append((len(prior), value, mads))
                        or (True, 0.1, 0.001))
    det = StepTimeAnomalyDetector(min_samples=8, straggler_mads=5.0, name="u", tracer=get_tracer())
    for _ in range(9):
        flags = det.observe(0.1)
    assert flags["straggler"] and asked == [(8, 0.1, 5.0)]


# ------------------------------------------------------------- a slow call
class _Host:
    """The injected clock: what ``host_stamp`` would read, moved by hand."""

    def __init__(self):
        self.wall = self.cpu = self.gc_s = 0.0

    def stamp(self):
        from deepspeed_tpu.diagnostics.anomaly import Stamp

        return Stamp(self.wall, self.cpu, self.gc_s)

    def work(self, s):       # the thread runs
        self.wall, self.cpu = self.wall + s, self.cpu + s

    def wait(self, s):       # it waits for the device, or does not run and does not know why: no CPU either way
        self.wall += s

    away = wait

    def collect(self, s):
        self.wall, self.cpu, self.gc_s = self.wall + s, self.cpu + s, self.gc_s + s


class _Loop:
    """``decode_chain`` as the serving loop drives it with a chain ahead: call
    N dispatches chain N+1, then fetches chain N; between two calls the host
    accepts and schedules."""

    def __init__(self):
        from deepspeed_tpu.diagnostics.anomaly import CallLog

        self.host = _Host()
        self.log = CallLog(stamp=self.host.stamp)
        self.stalls = []
        self.flight = self._dispatch(0)

    def _dispatch(self, chain):
        rec = self.log.open("chain", chain, 64, 8)
        rec.dispatch_open = self.host.stamp()
        self.host.work(0.001)
        rec.dispatch_close = self.host.stamp()
        self.log.host_span("serve:dispatch", 0.001)
        return rec

    def call(self, in_fetch=None, ahead=True):
        """One ``decode_chain``; ``in_fetch()`` is what happens while the host
        is inside ``serve:fetch`` (by default it waits 0.1 s for the chain)."""
        cur = self.flight
        self.flight = self._dispatch(cur.chain + 1) if ahead else None
        cur.fetch_open = self.host.stamp()
        (in_fetch or (lambda: self.host.wait(0.1)))()
        cur.fetch_close = self.host.stamp()
        stall = self.log.fetched(cur)
        if stall is not None:
            self.stalls.append(stall)
        self.host.work(0.002)
        self.log.host_span("serve:accept", 0.002)
        return cur

    def steady(self, n=12):
        for _ in range(n):
            self.call()
        assert not self.stalls and self.log.settle() is None
        return self


def test_a_steady_loop_stalls_nowhere_and_a_small_excess_is_no_stall():
    loop = _Loop().steady(80)
    assert len(loop.log.calls) == 81 and loop.log.calls[5].cadence_s == pytest.approx(0.103)
    loop.call(lambda: loop.host.wait(0.1 + 0.24))  # past 6 MADs, under a quarter of a second
    loop.steady(3)
    assert not loop.log.stalls


def test_a_class_is_judged_from_its_fourth_call_on():
    loop = _Loop()
    for _ in range(3):
        loop.call()
    loop.call(lambda: loop.host.wait(3.0))  # the class has three cadences: judged
    loop.call()
    assert [s.chain for s in loop.stalls] == [3]
    early = _Loop()
    early.call(), early.call()
    early.call(lambda: early.host.wait(3.0))  # two cadences: not yet
    early.call()
    assert not early.stalls


def test_a_late_device_makes_the_chain_queued_behind_it_wait_its_usual_time():
    loop = _Loop().steady()
    slow = loop.call(lambda: loop.host.wait(2.1))
    assert not loop.stalls  # decided when the NEXT call is fetched
    loop.call()
    (stall,) = loop.stalls
    assert (stall.cause, stall.chain, stall.kind, stall.rows, stall.k) == ("device_late", slow.chain, "chain", 64, 8)
    assert stall.seconds == pytest.approx(2.103) and stall.excess_s == pytest.approx(2.0)
    assert stall.in_fetch_s == pytest.approx(2.1) and stall.usual_fetch_s == pytest.approx(0.1)
    assert stall.cpu_s == pytest.approx(0.003) and stall.fetch_cpu_s == 0.0 and stall.gc_s == 0.0
    assert stall.next_wait_s == pytest.approx(0.1) and stall.usual_next_wait_s == pytest.approx(0.1)
    assert slow.cadence_s == stall.seconds and list(loop.log.stalls) == [stall]


def test_a_host_that_froze_in_the_fetch_finds_the_next_chain_ready_at_once():
    loop = _Loop().steady()
    slow = loop.call(lambda: loop.host.away(2.1))  # stopped from outside while it waited: no switch is counted
    loop.call(lambda: loop.host.wait(0.0004))      # the device went on while the host did not
    (stall,) = loop.stalls
    assert (stall.cause, stall.chain) == ("host_not_running", slow.chain)
    assert stall.in_fetch_s == pytest.approx(2.1) and stall.next_wait_s == pytest.approx(0.0004)


def test_a_host_that_slept_between_two_calls_finds_its_own_chain_ready_at_once():
    loop = _Loop().steady()
    loop.host.away(2.0)  # between two calls, the chain ahead in flight
    slow = loop.call(lambda: loop.host.wait(0.0004))
    loop.call()
    (stall,) = loop.stalls
    assert (stall.cause, stall.chain) == ("host_not_running", slow.chain)
    assert stall.excess_s == pytest.approx(1.9, abs=0.01) and stall.in_fetch_s == pytest.approx(0.0004)
    assert stall.next_wait_s == stall.in_fetch_s and stall.cpu_s == pytest.approx(0.003)


def test_a_serial_call_has_no_witness_and_reads_unknown():
    """Nothing in flight, so no chain to witness: a host that lost its core
    inside a serial call's dispatch, or a device that was late under its
    fetch, leaves the same stamps as the other would."""
    for where in ("dispatch", "fetch"):
        loop = _Loop().steady()
        loop.call(ahead=False)
        loop.flight = loop.log.open("chain", 99, 64, 8)
        loop.flight.dispatch_open = loop.host.stamp()
        loop.host.away(1.5 if where == "dispatch" else 0.001)
        loop.flight.dispatch_close = loop.host.stamp()
        loop.call((lambda: loop.host.wait(1.6)) if where == "fetch" else None, ahead=False)
        stall = loop.log.settle()
        assert (stall.cause, stall.chain, stall.next_wait_s) == ("unknown", 99, -1.0), where
        assert stall.cpu_s == pytest.approx(0.0) and stall.excess_s == pytest.approx(1.5, abs=0.01)


def test_the_collector_s_seconds_name_it():
    loop = _Loop().steady()
    loop.host.collect(1.0)
    slow = loop.call(lambda: loop.host.wait(0.0004))
    loop.call()
    (stall,) = loop.stalls
    assert (stall.cause, stall.chain, stall.gc_s) == ("collector", slow.chain, pytest.approx(1.0))
    assert stall.cpu_s == pytest.approx(1.003)


def test_a_busy_host_is_named_with_the_span_it_was_busy_in():
    loop = _Loop().steady()
    loop.host.work(1.2)
    loop.log.host_span("serve:schedule", 1.2)  # a compilation, a long loop: the program's own host code
    slow = loop.call(lambda: loop.host.wait(0.0004))
    loop.call()
    (stall,) = loop.stalls
    assert (stall.cause, stall.span, stall.chain) == ("host_busy", "serve:schedule", slow.chain)
    assert "span=serve:schedule cause=host_busy" in stall.line()


def test_what_the_evidence_does_not_decide_reads_unknown_with_its_numbers():
    # the last call of a generate: nothing follows to witness it (settled at serve:finish)
    loop = _Loop().steady()
    loop.call(lambda: loop.host.wait(2.1), ahead=False)
    stall = loop.log.settle()
    assert (stall.cause, stall.next_wait_s, stall.in_fetch_s) == ("unknown", -1.0, pytest.approx(2.1))
    assert loop.log.settle() is None
    # the next chain waited neither its usual time nor none
    loop = _Loop().steady()
    loop.call(lambda: loop.host.wait(2.1))
    loop.call(lambda: loop.host.wait(0.04))
    assert [s.cause for s in loop.stalls] == ["unknown"]
    # the excess lay in the fetch, and the thread was busy there
    loop = _Loop().steady()
    loop.call(lambda: loop.host.work(2.1))
    loop.call()
    assert [s.cause for s in loop.stalls] == ["unknown"] and loop.stalls[0].fetch_cpu_s == pytest.approx(2.1)
    line = loop.stalls[0].line()
    assert line.startswith("[serving] stall: chain=12 kind=chain rows=64 k=8 seconds=2.10")
    for name in ("excess_s", "in_fetch_s", "usual_fetch_s", "cpu_s", "fetch_cpu_s", "gc_s",
                 "next_wait_s", "usual_next_wait_s", "span=-", "cause=unknown"):
        assert name in line
    assert set(loop.stalls[0].span_args()) == {"chain", "kind", "rows", "seconds", "excess_s", "in_fetch_s", "cpu_s",
                                               "gc_s", "next_wait_s", "cause"}


def test_the_host_s_own_stamp_reads_this_thread():
    import gc

    from deepspeed_tpu.diagnostics.anomaly import CAUSES, host_stamp
    from deepspeed_tpu.telemetry import tracer as tracer_mod

    a = host_stamp()
    sum(i * i for i in range(200_000))
    gc.collect()
    b = host_stamp()
    assert b.wall > a.wall and b.cpu > a.cpu and b.gc_s > a.gc_s and b.gc_s == tracer_mod.gc_seconds()
    assert a._fields == ("wall", "cpu", "gc_s")  # two clock reads and an attribute: no system call beyond them
    assert b.cpu - a.cpu <= (b.wall - a.wall) * 1.5 and CAUSES[-1] == "unknown" and len(CAUSES) == 5


# ----------------------------------------------------------- flight recorder
def test_flight_recorder_ring_and_dump_schema(tmp_path):
    """≥8 step records with health verdicts survive in the dump; the ring
    stays bounded; the JSONL round-trips."""
    eng = _engine({
        "enabled": True,
        "health": {"nonfinite_policy": "skip_step"},
        "flight_recorder": {"capacity": 12, "dump_dir": str(tmp_path),
                            "install_signal_handlers": False,
                            "dump_on_exception": False},
    })
    batch = random_batch(eng.train_batch_size)
    for i in range(15):
        eng.train_batch(random_batch(eng.train_batch_size, seed=i))
    eng.train_batch(_poisoned(batch))
    assert len(eng.diagnostics.flight_recorder) == 12  # bounded

    path = eng.diagnostics.dump(reason="unit_test")
    lines = [json.loads(l) for l in open(path) if l.strip()]
    header = lines[0]
    assert header["kind"] == "header"
    assert header["reason"] == "unit_test"
    assert header["n_records"] == 12
    assert header["context"]["zero_stage"] == 1

    recs = [l for l in lines if l["kind"] == "step_record"]
    assert len(recs) >= 8
    for r in recs:
        assert {"step", "t_unix", "metrics", "health"} <= set(r)
        assert "skip" in r["health"] and "nonfinite_any" in r["health"]
        assert "loss" in r["metrics"] and "grad_norm" in r["metrics"]
    # the poisoned step's verdict is in the dump
    assert recs[-1]["health"]["skip"] is True
    assert recs[-1]["health"]["nonfinite_any"] is True
    # steps are contiguous and ordered (the ring kept the LAST capacity steps)
    steps = [r["step"] for r in recs]
    assert steps == sorted(steps) and steps[-1] == 16

    # schema round-trip: re-serialize == re-parse identical
    assert [json.loads(json.dumps(l)) for l in lines] == lines


def test_flight_recorder_dump_all_via_hook_helpers(tmp_path):
    """dump_all (what the excepthook/signal handlers call) reaches every
    live recorder without an engine reference."""
    from deepspeed_tpu.diagnostics import dump_all

    rec = FlightRecorder(capacity=4, dump_dir=str(tmp_path))
    rec.set_context(run="t")
    for i in range(6):
        rec.record(i, {"loss": float(i)})
    paths = dump_all(reason="signal:SIGUSR1")
    assert any(str(tmp_path) in p for p in paths)
    mine = [p for p in paths if str(tmp_path) in p][0]
    lines = [json.loads(l) for l in open(mine) if l.strip()]
    assert lines[0]["reason"] == "signal:SIGUSR1"
    assert lines[0]["n_records"] == 4  # bounded ring kept the last 4
    assert [l["step"] for l in lines[1:5]] == [2, 3, 4, 5]


def test_flops_profiler_mfu_reaches_registry_and_monitor_scalars():
    """The flops profiler publishes achieved-TFLOPS/MFU into the shared
    registry, so MFU rides the same step_scalars stream (monitor CSV/trace)
    as step time and comm bytes."""
    tr = get_tracer()
    tr.configure(enabled=True)
    eng = _engine(extra={"telemetry": {"enabled": True}})
    eng.flops_profiler.start_profile()
    eng.train_batch(random_batch(eng.train_batch_size))
    assert eng.flops_profiler.result is not None
    gauges = tr.registry.gauges()
    assert "flops/mfu" in gauges and "flops/achieved_tflops" in gauges
    assert gauges["flops/flops_per_step"] > 0
    scalars = tr.step_scalars()
    assert "Telemetry/flops/mfu" in scalars
    assert scalars["Telemetry/flops/flops_per_step"] > 0


def test_explicit_dump_includes_recent_spans(tmp_path):
    """With telemetry on, the dump carries the recent span tail so the
    post-mortem has the timeline, not just the scalars."""
    eng = _engine(
        {"enabled": True,
         "flight_recorder": {"dump_dir": str(tmp_path),
                             "install_signal_handlers": False,
                             "dump_on_exception": False}},
        extra={"telemetry": {"enabled": True}})
    eng.train_batch(random_batch(eng.train_batch_size))
    path = eng.diagnostics.dump()
    lines = [json.loads(l) for l in open(path) if l.strip()]
    span_names = {l["name"] for l in lines if l.get("kind") == "span"}
    assert {"train_batch", "step"} <= span_names
    # Perfetto trace written next to the JSONL
    assert os.path.exists(os.path.splitext(path)[0] + "_trace.json")
