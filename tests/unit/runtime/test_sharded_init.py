"""Sharded construction (zero.Init analog — reference
partition_parameters.py:825): params materialize directly in their target
sharding under jit, bit-identical to the eager init-then-place path."""

import os

import jax
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

TC = TransformerConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                       num_layers=2, num_heads=4, max_seq_len=32)


def _cfg(stage=3):
    return {
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage, "param_persistence_threshold": 0},
        "mesh": {"fsdp": 8, "dp": 1},
        "steps_per_print": 1000,
    }


def test_sharded_init_matches_eager_init(devices):
    spec = causal_lm_spec(TC, example_seq_len=16)
    engine, *_ = deepspeed_tpu.initialize(model=spec, config=_cfg())

    # the engine's own seed path: init_rng is the first split of PRNGKey(seed)
    seed = engine.config.model.seed
    init_rng = jax.random.split(jax.random.PRNGKey(seed))[0]
    want = spec.init_fn(init_rng)

    got = engine.state.params
    for (ka, a), (kb, b) in zip(
        sorted(jax.tree_util.tree_flatten_with_path(want)[0], key=lambda kv: str(kv[0])),
        sorted(jax.tree_util.tree_flatten_with_path(got)[0], key=lambda kv: str(kv[0])),
    ):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=1e-6,
            err_msg=f"{ka} vs {kb}")


def test_sharded_init_places_leaves_sharded(devices):
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(TC, example_seq_len=16), config=_cfg())
    leaf = engine.state.params["embed"]["embedding"]
    # fsdp=8: the embedding's shards live on 8 distinct devices
    assert len(leaf.sharding.device_set) == 8
    assert not leaf.sharding.is_fully_replicated


def test_universal_checkpoint_streams_atoms(tmp_path, devices):
    """v2 universal checkpoints are tensorstore dirs (parallel streamed I/O),
    not one consolidated host .npz (round-2 verdict item 6)."""
    from deepspeed_tpu.checkpoint.universal import load_universal, save_universal

    e1, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(TC, example_seq_len=16), config=_cfg())
    batch = {"input_ids": np.random.default_rng(0).integers(0, 128, (8, 16), dtype=np.int32)}
    l1 = [float(e1.train_batch(batch)["loss"]) for _ in range(2)]
    path = save_universal(e1, str(tmp_path), sidecar=False)
    assert not os.path.exists(os.path.join(path, "atoms.npz"))
    assert not os.path.exists(os.path.join(path, "atoms_host.npz"))
    assert os.path.isdir(os.path.join(path, "atoms"))

    # reload into a DIFFERENT layout (stage-1, dp-only mesh) and continue
    e2, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(TC, example_seq_len=16),
        config={**_cfg(stage=1), "mesh": {"dp": 8}})
    load_universal(e2, str(tmp_path))
    l2 = float(e2.train_batch(batch)["loss"])
    l1b = float(e1.train_batch(batch)["loss"])
    np.testing.assert_allclose(l2, l1b, rtol=1e-4)


def test_zero_namespace_gathered_parameters(devices):
    """deepspeed_tpu.zero.GatheredParameters (reference deepspeed.zero):
    gathered full params are mutable inside the context and the mutation
    lands back in the sharded masters — and the next step consumes it."""
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu import zero
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
    from deepspeed_tpu.utils import safe_get_full_fp32_param

    cfg = TransformerConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                            num_layers=1, num_heads=2, max_seq_len=16)
    with zero.Init():  # API-compat context
        spec = causal_lm_spec(cfg, example_seq_len=16)
    eng, *_ = deepspeed_tpu.initialize(
        model=spec,
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3}, "mesh": {"fsdp": 8},
                "steps_per_print": 1000})
    with zero.GatheredParameters(eng) as params:
        assert isinstance(params["embed"]["embedding"], np.ndarray)
        params["embed"]["embedding"][:] = 0.125
    got = safe_get_full_fp32_param(eng, "embed/embedding")
    np.testing.assert_allclose(got, 0.125)
    m = eng.train_batch({"input_ids": np.zeros((eng.train_batch_size, 16), np.int32)})
    assert np.isfinite(float(m["loss"]))


def test_gathered_parameters_rejects_param_list():
    """Reference-signature misuse fails EAGERLY with a clear TypeError: the
    reference's GatheredParameters(params, modifier_rank=...) takes a
    parameter list, the TPU-native form takes the engine — passing anything
    without `.state` must not surface later as an opaque AttributeError
    (the divergence is documented in migrating-from-deepspeed.md)."""
    import pytest

    from deepspeed_tpu import zero

    for bad in ([np.zeros((2, 2))], {"w": np.zeros(3)}, None):
        with pytest.raises(TypeError, match="ENGINE.*deepspeed_tpu.initialize"):
            zero.GatheredParameters(bad, modifier_rank=0)
