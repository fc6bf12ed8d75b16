"""ZeRO-Offload / ZeRO-Infinity wiring tests.

The reference integrates optimizer offload into the step
(``runtime/zero/stage3.py:2082`` + ``swap_tensor/partitioned_optimizer_swapper.py:29``);
here the engine reads ``zero_optimization.offload_optimizer`` and splits the
step into a device grad program + a host-committed compiled update. These
tests pin (a) state placement off the mesh, (b) trajectory match vs the fused
non-offload step, (c) the NVMe round-trip keeping state on disk between steps.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import TransformerConfig, causal_lm_spec


def _cfg(extra_zero=None, stage=1):
    zero = {"stage": stage, **(extra_zero or {})}
    return {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3, "weight_decay": 0.01}},
        "zero_optimization": zero,
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
    }


def _model():
    cfg = TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=2, max_seq_len=32,
    )
    return causal_lm_spec(cfg, example_seq_len=16)


def _run_steps(engine, n=3, seed=0):
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(n):
        batch = {"input_ids": rng.integers(0, 64, (engine.train_batch_size, 16), dtype=np.int32)}
        m = engine.train_batch(batch)
        losses.append(float(m["loss"]))
    return losses


def test_twin_flow_partial_offload_structure():
    """Twin-Flow (reference ZeRO-Offload++ ``offload_optimizer.ratio``):
    with ratio<1, part of the master state must stay ON the mesh (device
    partition updates in a fused accelerator program) while the host
    partition lives on the CPU backend — and a step runs."""
    eng, *_ = deepspeed_tpu.initialize(
        model=_model(),
        config=_cfg({"offload_optimizer": {"device": "cpu", "ratio": 0.5}}),
    )
    assert eng.offload_mode == "host-jit" and eng._twin_ratio == 0.5
    leaves = jax.tree_util.tree_leaves(eng.state.params)
    kinds = [type(leaf.sharding).__name__ for leaf in leaves]
    assert "SingleDeviceSharding" in kinds and "NamedSharding" in kinds, kinds
    # host partition holds ~ratio of the master bytes (greedy split)
    host_b = sum(l.size for l in leaves if type(l.sharding).__name__ == "SingleDeviceSharding")
    total_b = sum(l.size for l in leaves)
    assert 0.2 < host_b / total_b < 0.8, host_b / total_b
    losses = _run_steps(eng, 2)
    assert all(np.isfinite(losses))
    # the fragment API sees THROUGH the masked partition states: a moment is
    # retrievable for params in both partitions (embed is first in flatten
    # order => host; the final norm lands in the device partition)
    from deepspeed_tpu.utils.tensor_fragment import safe_get_full_optimizer_state

    mu_host = safe_get_full_optimizer_state(eng, "embed/embedding", "exp_avg")
    mu_dev = safe_get_full_optimizer_state(eng, "final_norm/scale", "exp_avg")
    assert mu_host is not None and float(np.abs(mu_host).max()) > 0
    assert mu_dev is not None and float(np.abs(mu_dev).max()) > 0


def test_twin_flow_trajectory_matches_fused():
    """ratio=0.5 partial offload reproduces the fused non-offload trajectory
    (same split semantics: one global grad norm, one loss-scale/step
    bookkeeping)."""
    twin, *_ = deepspeed_tpu.initialize(
        model=_model(),
        config=_cfg({"offload_optimizer": {"device": "cpu", "ratio": 0.5}}),
    )
    base, *_ = deepspeed_tpu.initialize(model=_model(), config=_cfg())
    l0 = _run_steps(base, 3)
    l1 = _run_steps(twin, 3)
    np.testing.assert_allclose(l0, l1, rtol=2e-4)
    # and the masters stay consistent: fp32 state_dict matches closely
    sd_t = twin.module_state_dict()
    sd_b = base.module_state_dict()
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5),
        sd_t, sd_b)


def test_twin_flow_fp16_dynamic_scale_matches_fused():
    """fp16 dynamic loss scaling under Twin-Flow: the shared bookkeeping
    (one finite flag, one loss-scale state) must reproduce the fused fp16
    trajectory including any scale adjustments."""
    fp16 = {"fp16": {"enabled": True, "initial_scale_power": 8, "loss_scale_window": 2}}

    twin, *_ = deepspeed_tpu.initialize(
        model=_model(),
        config={**_cfg({"offload_optimizer": {"device": "cpu", "ratio": 0.5}}), **fp16})
    base, *_ = deepspeed_tpu.initialize(model=_model(), config={**_cfg(), **fp16})
    l0 = _run_steps(base, 4)
    l1 = _run_steps(twin, 4)
    np.testing.assert_allclose(l0, l1, rtol=3e-3)
    assert float(jax.device_get(twin.state.loss_scale.loss_scale)) == \
        float(jax.device_get(base.state.loss_scale.loss_scale))
    assert int(jax.device_get(twin.state.step)) == int(jax.device_get(base.state.step))


def test_offload_bf16_grad_transfer_close_to_fp32():
    """bf16 grad accumulation x CPU offload: grads cross to the host in bf16
    (half the D2H bytes — what the offload bench configs use) and the
    trajectory stays close to the fp32-accumulated offload run."""
    import jax.numpy as jnp

    def run(accum_fp32):
        cfg = _cfg({"offload_optimizer": {"device": "cpu"}})
        cfg["bf16"] = {"enabled": True, "accumulate_grads_in_fp32": accum_fp32}
        cfg["gradient_accumulation_steps"] = 2
        eng, *_ = deepspeed_tpu.initialize(model=_model(), config=cfg, seed=4)
        return eng, _run_steps(eng, 3)

    e_bf, l_bf = run(False)
    _, l_fp = run(True)
    assert e_bf._accum_dtype is jnp.bfloat16
    np.testing.assert_allclose(l_bf, l_fp, rtol=5e-2)


def test_twin_flow_checkpoint_restores_across_partitionings(tmp_path):
    """Checkpoints canonicalize the Twin-Flow opt_state (the two optax.masked
    partitions merge to ONE param-shaped moment tree on save, re-partition on
    load): a checkpoint saved under ratio=0.5 restores into
    a non-Twin-Flow engine AND into a different-ratio (0.75) engine, with
    identical multi-step trajectories. (Restored engines step freely now:
    load_checkpoint's 'fresh' placement restores into newly allocated
    committed buffers, so the seed-era orbax heap-corruption landmine no
    longer applies.)"""
    twin, *_ = deepspeed_tpu.initialize(
        model=_model(),
        config=_cfg({"offload_optimizer": {"device": "cpu", "ratio": 0.5}}))
    _run_steps(twin, 2)
    twin.save_checkpoint(str(tmp_path / "twin"))

    # twin -> non-twin: canonical atoms restore against the plain structure,
    # values identical leaf-for-leaf
    plain, *_ = deepspeed_tpu.initialize(model=_model(), config=_cfg())
    path, _ = plain.load_checkpoint(str(tmp_path / "twin"))
    assert path is not None
    canon = jax.device_get(twin.canonical_opt_state())
    restored = jax.device_get(plain.state.opt_state)
    canon_leaves = jax.tree_util.tree_leaves(canon)
    restored_leaves = jax.tree_util.tree_leaves(restored)
    assert len(canon_leaves) == len(restored_leaves)
    for a, b in zip(canon_leaves, restored_leaves):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))

    # twin -> twin under a DIFFERENT ratio: re-partitioned against the 0.75
    # hole placement, not the saver's
    twin2, *_ = deepspeed_tpu.initialize(
        model=_model(),
        config=_cfg({"offload_optimizer": {"device": "cpu", "ratio": 0.75}}))
    path2, _ = twin2.load_checkpoint(str(tmp_path / "twin"))
    assert path2 is not None
    assert int(jax.device_get(twin2.state.step)) == int(jax.device_get(twin.state.step))

    # multi-step post-restore trajectories coincide (and exercise the fresh-
    # buffer restore path under continued stepping — the old landmine shape)
    l_twin = _run_steps(twin, 3)
    l_plain = _run_steps(plain, 3)
    l_twin2 = _run_steps(twin2, 3)
    np.testing.assert_allclose(l_twin, l_plain, rtol=1e-5)
    np.testing.assert_allclose(l_twin, l_twin2, rtol=1e-5)


def test_twin_flow_universal_checkpoint_canonical(tmp_path):
    """The universal (mesh-independent) format canonicalizes Twin-Flow
    opt_state the same way: atoms from a ratio=0.5 engine restore into a
    non-twin engine (canonical paths), and a twin self-reload exercises the
    load-side re-partitioning. Restored engines keep stepping (fresh-buffer
    restore placement; the seed-era one-step fence is gone)."""
    twin, *_ = deepspeed_tpu.initialize(
        model=_model(),
        config=_cfg({"offload_optimizer": {"device": "cpu", "ratio": 0.5}}))
    _run_steps(twin, 2)
    twin.save_universal_checkpoint(str(tmp_path))

    from deepspeed_tpu.checkpoint.universal import load_universal

    plain, *_ = deepspeed_tpu.initialize(model=_model(), config=_cfg())
    load_universal(plain, str(tmp_path))
    canon = jax.device_get(twin.canonical_opt_state())
    rest = jax.device_get(plain.state.opt_state)
    for a, b in zip(jax.tree_util.tree_leaves(canon), jax.tree_util.tree_leaves(rest)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))

    load_universal(twin, str(tmp_path))  # self-reload: departition path
    l_twin = _run_steps(twin, 3)
    l_plain = _run_steps(plain, 3)
    np.testing.assert_allclose(l_twin, l_plain, rtol=1e-5)


def test_twin_flow_warns_on_bf16_grad_accumulation(caplog):
    """bf16.accumulate_grads_in_fp32=false is force-overridden to fp32 on the
    Twin-Flow path (its stats/partition programs need fp32 grads) — that must
    warn, not silently lie (the prescale_gradients stance: a knob that lies is
    worse than an error)."""
    import logging

    cfg = _cfg({"offload_optimizer": {"device": "cpu", "ratio": 0.5}})
    cfg["bf16"] = {"enabled": True, "accumulate_grads_in_fp32": False}
    lg = logging.getLogger("deepspeed_tpu")
    lg.propagate = True  # the repo logger defaults propagate=False; caplog
    try:                 # listens on the root logger
        with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
            deepspeed_tpu.initialize(model=_model(), config=cfg)
    finally:
        lg.propagate = False
    assert any("Twin-Flow" in r.getMessage() and "fp32" in r.getMessage()
               for r in caplog.records), caplog.records


def test_twin_flow_ratio_rejected_with_nvme(tmp_path):
    with pytest.raises(ValueError, match="Twin-Flow"):
        deepspeed_tpu.initialize(
            model=_model(),
            config=_cfg({"offload_optimizer": {
                "device": "nvme", "nvme_path": str(tmp_path), "ratio": 0.5}}),
        )


def test_twin_flow_ratio_bounds_and_param_offload_rejected():
    for bad in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError, match="ratio"):
            deepspeed_tpu.initialize(
                model=_model(),
                config=_cfg({"offload_optimizer": {"device": "cpu", "ratio": bad}}))
    with pytest.raises(NotImplementedError, match="offload_param"):
        deepspeed_tpu.initialize(
            model=_model(),
            config=_cfg({"offload_optimizer": {"device": "cpu", "ratio": 0.5},
                         "offload_param": {"device": "cpu"}}, stage=3))


def test_offload_optimizer_cpu_trajectory_matches_fused():
    base, *_ = deepspeed_tpu.initialize(model=_model(), config=_cfg())
    off, *_ = deepspeed_tpu.initialize(
        model=_model(), config=_cfg({"offload_optimizer": {"device": "cpu"}})
    )
    assert off.offload_mode in ("host-jit", "memories")
    l0 = _run_steps(base, 3)
    l1 = _run_steps(off, 3)
    np.testing.assert_allclose(l0, l1, rtol=2e-4)
    p0 = jax.device_get(base.state.params)
    p1 = jax.device_get(off.state.params)
    for a, b in zip(jax.tree_util.tree_leaves(p0), jax.tree_util.tree_leaves(p1)):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-5)


def test_offload_state_not_on_mesh():
    off, *_ = deepspeed_tpu.initialize(
        model=_model(), config=_cfg({"offload_optimizer": {"device": "cpu"}})
    )
    if off.offload_mode != "host-jit":
        pytest.skip("host-jit offload unavailable on this backend")
    _run_steps(off, 1)
    # master params + moments are committed to ONE host device, not spread
    # over the mesh (the device-memory drop on a real accelerator)
    for leaf in jax.tree_util.tree_leaves(off.state.params):
        assert len(leaf.sharding.device_set) == 1
    for leaf in jax.tree_util.tree_leaves(off.state.opt_state):
        if isinstance(leaf, jax.Array):
            assert len(leaf.sharding.device_set) == 1
    # the device-side view is only the bf16/compute-dtype params
    assert off._compute_dev is not None


def test_offload_nvme_roundtrip(tmp_path):
    off, *_ = deepspeed_tpu.initialize(
        model=_model(),
        config=_cfg({"offload_optimizer": {"device": "nvme", "nvme_path": str(tmp_path)}}),
    )
    assert off.offload_mode == "nvme"
    base, *_ = deepspeed_tpu.initialize(model=_model(), config=_cfg())
    l0 = _run_steps(base, 3)
    l1 = _run_steps(off, 3)
    np.testing.assert_allclose(l0, l1, rtol=2e-4)
    # between steps the moments live on disk, not in the state
    assert off._opt_on_nvme and off.state.opt_state is None
    assert any((tmp_path / "opt_state").rglob("*.bin"))
    # checkpoint materializes them back
    off.materialize_state()
    assert off.state.opt_state is not None


def test_offload_zero3_with_param_offload():
    off, *_ = deepspeed_tpu.initialize(
        model=_model(),
        config=_cfg(
            {"offload_optimizer": {"device": "cpu"}, "offload_param": {"device": "cpu"}},
            stage=3,
        ),
    )
    base, *_ = deepspeed_tpu.initialize(model=_model(), config=_cfg(stage=3))
    l0 = _run_steps(base, 2)
    l1 = _run_steps(off, 2)
    np.testing.assert_allclose(l0, l1, rtol=2e-4)
    # param offload: no persistent device-side weights between steps
    assert off._compute_dev is None


def test_param_only_offload_is_not_a_silent_noop():
    """offload_param without offload_optimizer must still offload (the
    reference supports standalone param offload; a parsed-but-dead knob is
    worse than an error)."""
    off, *_ = deepspeed_tpu.initialize(
        model=_model(), config=_cfg({"offload_param": {"device": "cpu"}}, stage=3)
    )
    assert off.offload_mode is not None
    _run_steps(off, 1)
    assert off._compute_dev is None  # nothing persists device-side


def test_offload_checkpoint_roundtrip(tmp_path):
    off, *_ = deepspeed_tpu.initialize(
        model=_model(), config=_cfg({"offload_optimizer": {"device": "cpu"}})
    )
    _run_steps(off, 2)
    step_before = off.global_steps
    off.save_checkpoint(str(tmp_path))
    _run_steps(off, 1)
    path, _ = off.load_checkpoint(str(tmp_path))
    assert path is not None
    assert off.global_steps == step_before
    _run_steps(off, 1)  # still trains after reload
