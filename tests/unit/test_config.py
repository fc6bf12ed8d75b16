"""Config system tests (batch triad parity with reference runtime/config.py:938-1045)."""

import pytest

from deepspeed_tpu.config import DeepSpeedTPUConfig


def test_defaults():
    cfg = DeepSpeedTPUConfig({})
    assert cfg.train_batch_size == 1
    assert cfg.train_micro_batch_size_per_gpu == 1
    assert cfg.gradient_accumulation_steps == 1
    assert cfg.zero_config.stage == 0
    assert not cfg.fp16_enabled and not cfg.bf16_enabled


def test_batch_triad_all_given():
    cfg = DeepSpeedTPUConfig(
        {"train_batch_size": 16, "train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 2},
        dp_world_size=4,
    )
    assert cfg.train_batch_size == 16


def test_batch_triad_inconsistent_raises():
    with pytest.raises(ValueError):
        DeepSpeedTPUConfig(
            {"train_batch_size": 17, "train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 2},
            dp_world_size=4,
        )


def test_batch_triad_solve_gas():
    cfg = DeepSpeedTPUConfig(
        {"train_batch_size": 32, "train_micro_batch_size_per_gpu": 2}, dp_world_size=4
    )
    assert cfg.gradient_accumulation_steps == 4


def test_batch_triad_solve_micro():
    cfg = DeepSpeedTPUConfig(
        {"train_batch_size": 32, "gradient_accumulation_steps": 4}, dp_world_size=4
    )
    assert cfg.train_micro_batch_size_per_gpu == 2


def test_batch_triad_from_micro_only():
    cfg = DeepSpeedTPUConfig({"train_micro_batch_size_per_gpu": 3}, dp_world_size=2)
    assert cfg.train_batch_size == 6
    assert cfg.gradient_accumulation_steps == 1


def test_zero_section():
    cfg = DeepSpeedTPUConfig(
        {
            "zero_optimization": {
                "stage": 3,
                "offload_optimizer": {"device": "cpu", "pin_memory": True},
                "param_persistence_threshold": 1000,
            }
        }
    )
    z = cfg.zero_config
    assert z.stage == 3
    assert z.offload_optimizer_device == "cpu"
    assert z.param_persistence_threshold == 1000
    assert cfg.zero_enabled


def test_fp16_dynamic_loss_scale():
    cfg = DeepSpeedTPUConfig({"fp16": {"enabled": True, "initial_scale_power": 12}})
    assert cfg.fp16_enabled
    assert cfg.model.fp16.dynamic
    assert cfg.model.fp16.initial_scale_power == 12
    import jax.numpy as jnp

    assert cfg.compute_dtype == jnp.float16


def test_bf16():
    cfg = DeepSpeedTPUConfig({"bf16": {"enabled": True}})
    import jax.numpy as jnp

    assert cfg.compute_dtype == jnp.bfloat16


def test_unknown_keys_tolerated():
    cfg = DeepSpeedTPUConfig({"some_future_section": {"x": 1}, "train_micro_batch_size_per_gpu": 2})
    assert cfg.train_micro_batch_size_per_gpu == 2


def test_auto_values_dropped():
    cfg = DeepSpeedTPUConfig({"gradient_clipping": "auto"})
    assert cfg.gradient_clipping == 0.0


def test_optimizer_scheduler_sections():
    cfg = DeepSpeedTPUConfig(
        {
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3, "betas": [0.9, 0.95]}},
            "scheduler": {"type": "WarmupLR", "params": {"warmup_num_steps": 10}},
        }
    )
    assert cfg.model.optimizer.type == "AdamW"
    assert cfg.model.optimizer.params["lr"] == 1e-3
    assert cfg.model.scheduler.type == "WarmupLR"


def test_mesh_section():
    cfg = DeepSpeedTPUConfig({"mesh": {"fsdp": 4, "tp": 2, "dp": -1}})
    assert cfg.mesh_config.fsdp == 4
    assert cfg.mesh_config.tp == 2


def test_batch_triad_gas_only():
    # regression: a lone gradient_accumulation_steps must be honored, not reset
    cfg = DeepSpeedTPUConfig({"gradient_accumulation_steps": 4}, dp_world_size=2)
    assert cfg.gradient_accumulation_steps == 4
    assert cfg.train_batch_size == 8


def test_stage_auto_dropped():
    cfg = DeepSpeedTPUConfig({"zero_optimization": {"stage": "auto"}})
    assert cfg.zero_config.stage == 0


def test_strict_key_not_swallowed():
    # a config key literally named "strict" must pass through as an extra field
    cfg = DeepSpeedTPUConfig({"strict": True, "train_micro_batch_size_per_gpu": 2})
    assert cfg.train_micro_batch_size_per_gpu == 2


_GONE = {"enabled": True, "algorithm": "ring2d", "overlap_chunks": 4}


@pytest.mark.parametrize("config,owner,key", [
    ({"collectives": _GONE}, "EngineConfig", "collectives"),
    ({"numerics": {"enabled": True, "drift_ratio": 3.0}}, "NumericsConfig", "drift_ratio"),
    ({"numerics": {"max_probe_elems": 1024}}, "NumericsConfig", "max_probe_elems"),
    ({"zero_optimization": {"stage": 1, "no_such_zero_key": 3}}, "ZeroConfig", "no_such_zero_key"),
], ids=["collectives-block", "numerics-drift_ratio", "numerics-max_probe_elems", "any-other"])
def test_a_key_that_left_the_schema_is_an_unknown_key(caplog, config, owner, key):
    """The ``collectives`` block and the wire probes' two settings left the schema with the code they configured: a
    config that still carries one parses, keeps it among its section's extras, and is told so by the key's name, as
    any unknown key is."""
    import logging

    from deepspeed_tpu.telemetry import events

    events.reset_warn_once()
    lg = logging.getLogger("deepspeed_tpu")
    prev, lg.propagate = lg.propagate, True
    try:
        with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
            cfg = DeepSpeedTPUConfig({"train_micro_batch_size_per_gpu": 2, **config})
    finally:
        lg.propagate = prev
    section = cfg.model if owner == "EngineConfig" else getattr(cfg.model, next(iter(config)))
    assert type(section).__name__ == owner and key not in type(section).model_fields
    assert key in section.extra_fields()
    said = [r.getMessage() for r in caplog.records if "unknown config key" in r.getMessage()]
    assert len(said) == 1 and repr(key) in said[0] and owner in said[0], said
    assert cfg.train_micro_batch_size_per_gpu == 2
