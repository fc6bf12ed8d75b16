"""ZeRO++ wiring tests: the qwZ/qgZ knobs change the compiled step.

Reference: ``runtime/comm/coalesced_collectives.py:31`` (qgZ),
``zero/partition_parameters.py:1200`` (qwZ). Here both route through
``parallel/zeropp.sharded_weight_gather`` inside the train step; tests pin
(a) trajectory within quantization tolerance of the exact run, (b) comm
telemetry showing int8 (not fp32/bf16) bytes on the wire, (c) an honest
error for the unimplemented hpZ knob.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm.comm import comms_logger
from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
from tests.unit.parallel.partial_manual import partial_manual_xfail


def _cfg(stage=2, **zero_extra):
    return {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage, **zero_extra},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
    }


def _model():
    cfg = TransformerConfig(
        vocab_size=64, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=2, max_seq_len=32,
    )
    return causal_lm_spec(cfg, example_seq_len=16)


def _run(engine, n=3, seed=0):
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(n):
        batch = {"input_ids": rng.integers(0, 64, (engine.train_batch_size, 16), dtype=np.int32)}
        losses.append(float(engine.train_batch(batch)["loss"]))
    return losses


@pytest.mark.parametrize("stage,knobs", [
    (2, {"zero_quantized_gradients": True}),
    (3, {"zero_quantized_gradients": True, "zero_quantized_weights": True}),
    (3, {"zero_quantized_weights": True}),
])
def test_zpp_trajectory_close_to_exact(stage, knobs):
    exact, *_ = deepspeed_tpu.initialize(model=_model(), config=_cfg(stage=stage))
    zpp, *_ = deepspeed_tpu.initialize(model=_model(), config=_cfg(stage=stage, **knobs))
    l0 = _run(exact, 3)
    l1 = _run(zpp, 3)
    # int8 block quantization of comm: same trend, small error
    np.testing.assert_allclose(l0, l1, rtol=0.05)
    assert abs(l0[-1] - l1[-1]) < 0.25


def test_zpp_comm_bytes_reduced():
    """Telemetry must show the gradient reduction riding int8, not fp32."""
    comms_logger.configure(enabled=True)
    comms_logger.reset()
    try:
        zpp, *_ = deepspeed_tpu.initialize(
            model=_model(), config=_cfg(stage=2, zero_quantized_gradients=True)
        )
        _run(zpp, 1)
        rows = comms_logger.summary()
    finally:
        comms_logger.configure(enabled=False)
        comms_logger.reset()
    a2a = [r for r in rows if r["op"] == "all_to_all"]
    assert a2a, f"no all_to_all telemetry recorded: {[r['op'] for r in rows]}"
    # int8 payload: bytes == numel (1 byte/elem); fp32 would be 4x. Each
    # sharded leaf contributes numel int8 values + fp32 scales (1/2048th).
    total_a2a = sum(r["total_bytes"] for r in a2a)
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(zpp.state.params)
    )
    assert total_a2a < 2 * n_params, (total_a2a, n_params)


def test_hpz_with_quantized_collectives_raises():
    """hpZ itself is implemented (tests/unit/runtime/test_hpz.py); the
    unimplemented COMPOSITION with qwZ/qgZ must still fail loudly."""
    with pytest.raises(NotImplementedError, match="hpZ"):
        deepspeed_tpu.initialize(
            model=_model(),
            config=_cfg(stage=3, zero_hpz_partition_size=2, zero_quantized_weights=True),
        )


def test_zpp_parity_path_uses_quantized_comm():
    """forward/backward/step must ride the same quantized collectives."""
    zpp, *_ = deepspeed_tpu.initialize(
        model=_model(), config=_cfg(stage=2, zero_quantized_gradients=True)
    )
    rng = np.random.default_rng(1)
    batch = {"input_ids": rng.integers(0, 64, (zpp.train_batch_size, 16), dtype=np.int32)}
    comms_logger.configure(enabled=True)
    comms_logger.reset()
    try:
        zpp.backward(batch=batch)
        zpp.step()
        rows = comms_logger.summary()
    finally:
        comms_logger.configure(enabled=False)
        comms_logger.reset()
    assert any(r["op"] == "all_to_all" for r in rows), [r["op"] for r in rows]


def test_zpp_rejects_offload_combination():
    with pytest.raises(NotImplementedError):
        deepspeed_tpu.initialize(
            model=_model(),
            config=_cfg(stage=2, zero_quantized_gradients=True,
                        offload_optimizer={"device": "cpu"}),
        )


def test_nvme_requires_path():
    with pytest.raises(ValueError):
        deepspeed_tpu.initialize(
            model=_model(),
            config=_cfg(stage=2, offload_optimizer={"device": "nvme"}),
        )


def test_qg_requires_stage2():
    with pytest.raises(ValueError):
        deepspeed_tpu.initialize(
            model=_model(), config=_cfg(stage=1, zero_quantized_gradients=True)
        )


# ------------------------------------------------------------ LoCo (round 5)

def test_loco_error_feedback_beats_plain_qgz(devices):
    """The EF property (reference all_to_all_loco_quant_reduce): repeatedly
    reducing the SAME gradient, the loco running sum tracks the exact sum with
    bounded error, while plain qgZ accumulates its quantization bias linearly."""
    from deepspeed_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.parallel.zeropp import (
        _int8_reduce_scatter_dim,
        _int8_reduce_scatter_dim_loco,
    )
    from deepspeed_tpu.topology.mesh import build_mesh

    mesh = build_mesh(axis_sizes={"dp": 8})
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((8, 512)), jnp.float32)  # replicated grad
    T = 8

    def plain(gl):
        # lax.scan (not a Python loop): the body compiles ONCE — the unrolled
        # form was the single slowest test in the default tier (69 s cold)
        def body(out, _):
            return out + _int8_reduce_scatter_dim(gl, 0, ("dp",), 64), ()

        out0 = jnp.zeros((gl.shape[0] // 8, gl.shape[1]), jnp.float32)
        return jax.lax.scan(body, out0, None, length=T)[0]

    def loco(gl):
        def body(carry, _):
            out, err = carry
            s, err = _int8_reduce_scatter_dim_loco(gl, err, 0, ("dp",), 1.0, 64)
            return (out + s, err), ()

        out0 = jnp.zeros((gl.shape[0] // 8, gl.shape[1]), jnp.float32)
        return jax.lax.scan(body, (out0, jnp.zeros_like(gl)), None, length=T)[0][0]

    spec = P()  # grad replicated over dp; outputs scattered on dim 0
    run = lambda f: shard_map(  # noqa: E731
        f, mesh=mesh, in_specs=(spec,), out_specs=P("dp"), check_vma=False)(g)
    exact = T * g  # mean over 8 identical replicas == g; rank r gets row r
    err_plain = float(jnp.abs(run(plain) - exact).max())
    err_loco = float(jnp.abs(run(loco) - exact).max())
    assert err_loco < 0.5 * err_plain, (err_loco, err_plain)


def test_loco_trajectory_close_to_exact():
    """Engine-level: qgZ+LoCo trains within quantization tolerance of exact,
    and the residual state actually lives in the step (nonzero after a step)."""
    exact, *_ = deepspeed_tpu.initialize(model=_model(), config=_cfg(stage=2))
    loco, *_ = deepspeed_tpu.initialize(
        model=_model(),
        config=_cfg(stage=2, zero_quantized_gradients=True,
                    loco_param={"err_beta": 0.8, "reset_T": 64}))
    l0 = _run(exact, 3)
    l1 = _run(loco, 3)
    np.testing.assert_allclose(l0, l1, rtol=0.05)
    assert abs(l0[-1] - l1[-1]) < 0.25
    assert loco.state.comm_error is not None
    max_err = max(float(jnp.abs(e).max())
                  for e in jax.tree_util.tree_leaves(loco.state.comm_error))
    assert max_err > 0, "LoCo residuals never updated — EF not wired"


def test_loco_requires_qg():
    with pytest.raises(ValueError, match="loco"):
        deepspeed_tpu.initialize(
            model=_model(),
            config=_cfg(stage=2, loco_param={"err_beta": 0.8}))


@partial_manual_xfail
def test_zpp_composes_with_ulysses_sp(devices):
    """Ulysses sharding constraints inside the ZeRO++ manual micro fn must
    name only non-manual axes (round-5 dryrun D caught the violation)."""
    model = TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, max_seq_len=32)
    eng, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(model, example_seq_len=32),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2, "zero_quantized_gradients": True},
                "mesh": {"dp": 4, "sp": 2}, "steps_per_print": 1000})
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 64, (eng.train_batch_size, 32), dtype=np.int32)}
    loss = float(eng.train_batch(batch)["loss"])
    assert np.isfinite(loss)


# ------------------------------------------- the differentiable gather, against NumPy

def _gather_and_grad(dim, qw, qg, world=8):
    """(what went in, the forward's full weight a rank, a rank's shard gradient) of ``sharded_weight_gather`` over
    ``dp``: the loss of rank r is ``sum(full * C_r)``, so the full weight's gradient there is ``C_r``."""
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.parallel.zeropp import sharded_weight_gather
    from deepspeed_tpu.utils.compat import shard_map

    mesh = Mesh(np.array(jax.devices()[:world]), ("dp",))
    rng = np.random.default_rng(7)
    shard_shape, full_shape = ((2, 6), (2 * world, 6)) if dim == 0 else ((3, 4), (3, 4 * world))
    shards = jnp.asarray(rng.standard_normal((world, *shard_shape)), jnp.float32)
    coeffs = jnp.asarray(rng.standard_normal((world, *full_shape)), jnp.float32)

    def per_rank(shard, coeff):
        gather = lambda s: sharded_weight_gather(s, dim, ("dp",), (), qw, qg, 16)  # noqa: E731
        full, back = jax.vjp(gather, shard[0])
        return full[None], back(coeff[0])[0][None]

    full, grad = jax.jit(shard_map(per_rank, mesh=mesh, in_specs=(P("dp"), P("dp")), out_specs=(P("dp"), P("dp")),
                                   check_vma=False))(shards, coeffs)
    return np.asarray(shards), np.asarray(coeffs), np.asarray(full), np.asarray(grad)


@pytest.mark.parametrize("dim", [0, 1])
@pytest.mark.parametrize("qw", [False, True], ids=["exact", "qwZ"])
def test_weight_gather_forward_is_numpy_s_concatenate(devices, qw, dim):
    """Every rank holds the shards side by side along the leaf's own dimension: to the bit on the exact wire, within
    half an int8 step of a 16-element block's largest value on the quantized one."""
    shards, _, full, _ = _gather_and_grad(dim, qw, False)
    want = np.concatenate(list(shards), dim)
    tol = np.abs(shards).max() / 127 / 2 + 1e-6 if qw else 0.0
    for rank in range(len(shards)):
        assert np.abs(full[rank] - want).max() <= tol


@pytest.mark.parametrize("dim", [0, 1])
@pytest.mark.parametrize("qg", [False, True], ids=["exact", "qgZ"])
def test_weight_gather_backward_is_a_rank_s_slice_of_the_mean_gradient(devices, qg, dim):
    _, coeffs, _, grad = _gather_and_grad(dim, False, qg)
    mean = coeffs.mean(0)
    tol = np.abs(coeffs).max() / 127 / 2 + 1e-6 if qg else 1e-6
    for rank, want in enumerate(np.split(mean, len(coeffs), dim)):
        assert np.abs(grad[rank] - want).max() <= tol
