"""Numerics observatory (telemetry/numerics.py) — ISSUE 17 acceptance.

Pinned here:
  - an injected single-replica bit flip (``FaultInjector.flip_param_bit``)
    fires the divergence sentinel within ONE sampled step, names the
    offending param group, and latches the event in the carried state
  - the ``abort`` policy raises ``TrainingHealthError`` from the host hook
  - disabled mode is jaxpr-identical: the engine update program with the
    numerics block absent, explicitly disabled, and enabled-without-sentinel
    all trace to the same jaxpr (probes are standalone dispatches)
  - the whole-tree xor digest checksum is bit-stable across mesh shapes
    (the fleet heartbeat's cross-process comparator contract)
  - serving probes (KV dequant / WOQ matmul / spec-accept trend alarm)
  - the ``numerics`` perf-ledger suite is headline-gated by the PR-16 gate
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.diagnostics.faultinject import FaultInjector
from deepspeed_tpu.diagnostics.manager import TrainingHealthError
from deepspeed_tpu.telemetry import get_tracer
from deepspeed_tpu.telemetry import numerics
from tests.unit.simple_model import random_batch, simple_model_spec


@pytest.fixture(autouse=True)
def _reset():
    numerics.configure(enabled=False)
    tr = get_tracer()
    tr.configure(enabled=False)
    tr.reset()
    yield
    numerics.configure(enabled=False)
    get_tracer().configure(enabled=False)
    get_tracer().reset()


@pytest.fixture
def dslog():
    lg = logging.getLogger("deepspeed_tpu")
    prev = lg.propagate
    lg.propagate = True
    yield lg
    lg.propagate = prev


def _engine(num=None, extra=None):
    eng, *_ = deepspeed_tpu.initialize(
        model=simple_model_spec(),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 10_000,
            **({"numerics": num} if num else {}),
            **(extra or {}),
        },
    )
    return eng


def _step(eng, seed=0):
    return eng.train_batch(batch=random_batch(eng.train_batch_size, seed=seed))


# ----------------------------------------------------------- sentinel: engine
def test_bit_flip_fires_sentinel_within_one_sampled_step(dslog, caplog):
    eng = _engine({"enabled": True, "sample_every": 1,
                   "sentinel_sample_every": 1})
    assert eng.state.numerics is not None
    for s in range(2):
        _step(eng, seed=s)
    obs = numerics.get_observatory()
    assert obs.divergence_events_seen == 0
    FaultInjector().flip_param_bit(eng)
    with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
        m = _step(eng, seed=9)
    assert obs.divergence_events_seen == 1
    fetched = jax.device_get(
        {k: v for k, v in m.items() if k.startswith("numerics/")})
    assert int(fetched["numerics/diverged"]) == 1
    assert int(fetched["numerics/divergence_events"]) == 1
    # the offending top-level group is named; the untouched ones are clean
    flagged = {k: int(v) for k, v in fetched.items()
               if k.startswith("numerics/diverged/")}
    assert sum(flagged.values()) == 1
    assert any("NUMERICS DIVERGENCE" in r.message for r in caplog.records)


def test_clean_run_raises_zero_alarms():
    eng = _engine({"enabled": True, "sample_every": 1,
                   "sentinel_sample_every": 1})
    for s in range(4):
        m = _step(eng, seed=s)
    obs = numerics.get_observatory()
    assert obs.divergence_events_seen == 0
    assert int(jax.device_get(m["numerics/divergence_events"])) == 0
    assert int(jax.device_get(m["numerics/checked"])) == 4


def test_abort_policy_raises_training_health_error():
    eng = _engine({"enabled": True, "sample_every": 1,
                   "sentinel_sample_every": 1,
                   "divergence_policy": "abort"})
    _step(eng, seed=0)
    FaultInjector().flip_param_bit(eng)
    with pytest.raises(TrainingHealthError) as ei:
        _step(eng, seed=1)
    assert "numerics divergence" in str(ei.value)
    assert ei.value.verdicts["numerics/divergence_events"] >= 1


def test_identical_corruption_on_all_replicas_is_invisible():
    """The sentinel detects REPLICA DISAGREEMENT, not bad values: a fault
    every replica applies identically keeps the digests equal — that
    failure class belongs to the health probes. Pinned at sentinel level
    (a mid-training engine-state device_put swap is not cache-hermetic on
    the forced-CPU harness)."""
    mesh = _mesh((4, 2))
    params = jax.device_put({"w": np.ones((8, 8), np.float32) * 1.5},
                            {"w": NamedSharding(mesh, P())})
    _st, m = _digest_params(mesh, {"w": P()}, params)
    assert int(m["numerics/diverged"]) == 0


# ------------------------------------------------------------ program identity
def test_disabled_mode_is_jaxpr_identical():
    """THE structural acceptance: the traced update program with the
    numerics block absent, explicitly disabled, and enabled WITHOUT the
    sentinel is one and the same jaxpr — wire probes are standalone
    dispatches, never ops inside the step."""

    def update_jaxpr(eng):
        state = eng.state
        grads = jax.tree_util.tree_map(jnp.zeros_like, state.params)

        def fn(s, g):
            return eng._update_math(s, g, s.rng, grads_are_unscaled=True)

        return str(jax.make_jaxpr(fn)(state, grads))

    j_absent = update_jaxpr(_engine())
    j_off = update_jaxpr(_engine({"enabled": False}))
    j_probes_only = update_jaxpr(
        _engine({"enabled": True, "sentinel": False, "sample_every": 4}))
    assert j_absent == j_off == j_probes_only
    # and the sentinel DOES change the program when armed (the cond + digest)
    j_sentinel = update_jaxpr(
        _engine({"enabled": True, "sentinel_sample_every": 4}))
    assert j_sentinel != j_absent


def test_disabled_engine_keeps_state_numerics_none():
    eng = _engine()
    assert eng.state.numerics is None
    assert eng._numerics is None
    assert eng._numerics_sentinel is None


# --------------------------------------------------------------- digest math
def _mesh(shape):
    return Mesh(np.array(jax.devices()[:8]).reshape(*shape), ("dp", "fsdp"))


def _digest_params(mesh, specs, params, sample_every=1):
    sent = numerics.DivergenceSentinel(mesh, specs, sample_every=sample_every)
    st = jax.device_put(sent.init_state(), NamedSharding(mesh, P()))

    @jax.jit
    def step(st, p):
        return sent.probe(st, p, jnp.zeros((), jnp.int32))

    new_st, metrics = step(st, params)
    return new_st, jax.device_get(metrics)


def test_digest_checksum_bit_stable_across_mesh_shapes():
    """The fleet comparator contract: the whole-tree xor checksum is the
    SAME number on a 4x2 and a 2x4 mesh over the same params (sum-of-squares
    folds would not be — xor is order-independent and exact)."""
    host = {"blk": {"w": np.arange(64, dtype=np.float32).reshape(8, 8) / 7.0},
            "head": {"b": np.linspace(-1, 1, 16, dtype=np.float32)}}
    specs = {"blk": {"w": P("fsdp", None)}, "head": {"b": P()}}
    cks = []
    for shape in ((4, 2), (2, 4)):
        mesh = _mesh(shape)
        sharding = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        params = jax.device_put(host, sharding)
        st, m = _digest_params(mesh, specs, params)
        assert int(m["numerics/diverged"]) == 0
        cks.append(int(np.uint32(jax.device_get(st.checksum))))
    assert cks[0] == cks[1]


def test_sentinel_detects_single_replica_flip_on_sharded_leaf():
    """A leaf sharded over fsdp but replicated over dp: flipping one dp
    replica's copy of one shard must still trip the comparator."""
    mesh = _mesh((4, 2))
    specs = {"w": P("fsdp", None)}
    sharding = {"w": NamedSharding(mesh, P("fsdp", None))}
    params = jax.device_put(
        {"w": np.ones((8, 8), np.float32)}, sharding)
    leaf = params["w"]
    shards = [np.array(np.asarray(s.data), copy=True)
              for s in leaf.addressable_shards]
    shards[0].view(np.uint32).flat[0] ^= np.uint32(1 << 18)
    bufs = [jax.device_put(s, sh.device)
            for s, sh in zip(shards, leaf.addressable_shards)]
    bad = jax.make_array_from_single_device_arrays(
        leaf.shape, leaf.sharding, bufs)
    _st, m = _digest_params(mesh, specs, {"w": bad})
    assert int(m["numerics/diverged"]) == 1


def test_sentinel_cond_skips_unsampled_steps():
    mesh = _mesh((4, 2))
    params = jax.device_put({"w": np.ones((8,), np.float32)},
                            {"w": NamedSharding(mesh, P())})
    sent = numerics.DivergenceSentinel(mesh, {"w": P()}, sample_every=4)
    st = jax.device_put(sent.init_state(), NamedSharding(mesh, P()))

    @jax.jit
    def step(st, p, i):
        return sent.probe(st, p, i)

    for i in range(8):
        st, _m = step(st, params, jnp.int32(i))
    assert int(jax.device_get(st.checked)) == 2  # steps 0 and 4


# -------------------------------------------------------------- serving plane
def test_kv_dequant_probe_within_pinned_bounds():
    obs = numerics.configure(enabled=True)
    rel8 = obs.kv_dequant_probe("int8", head_dim=128)
    relf8 = obs.kv_dequant_probe("fp8", head_dim=128)
    # int8 absmax/127 blockwise ~1-2%, fp8 E4M3 (3 mantissa bits) ~5-6%
    assert 0.0 < rel8 < 2e-2
    assert 0.0 < relf8 < 6e-2
    assert obs.kv_dequant_probe(None) == 0.0


def test_woq_matmul_probe_reports_small_error():
    obs = numerics.configure(enabled=True)
    rel = obs.woq_matmul_probe("int8")
    assert 0.0 < rel < 0.05
    g = get_tracer().registry.gauges()
    assert any(k.startswith("numerics/woq_matmul_rel_err") for k in g)


def test_spec_accept_trend_alarm_fires_on_collapse(dslog, caplog):
    obs = numerics.configure(enabled=True, spec_accept_window=16,
                             spec_accept_mads=6.0, spec_accept_min_n=8)
    rng = np.random.default_rng(0)
    with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
        for _ in range(12):
            assert not obs.note_spec_accept(0.8 + rng.normal() * 0.01)
        assert obs.note_spec_accept(0.1)  # collapse
    assert obs.spec_accept_alarm.alarms == 1
    assert any("acceptance rate" in r.message for r in caplog.records)


def test_trend_alarm_needs_quorum():
    alarm = numerics.TrendAlarm(window=8, mads=6.0, min_n=4)
    assert not alarm.observe(0.9)
    assert not alarm.observe(0.0)  # only 1 prior observation: no verdict
    assert alarm.alarms == 0


# ------------------------------------------------------------------ EF gauges
def test_ef_residual_norm_gauges():
    obs = numerics.configure(enabled=True)
    err = {"layer": {"w": jnp.full((4, 4), 0.5, jnp.float32)}}
    out = obs.note_ef_residuals(err)
    assert out and abs(out["layer"] - 2.0) < 1e-5
    g = get_tracer().registry.gauges()
    assert any(k.startswith("numerics/ef_residual_norm") for k in g)
