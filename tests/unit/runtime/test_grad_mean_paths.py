"""The ``shard_map`` gradient paths (ZeRO++, LoCo, 1-bit) state their own collectives through the comm facade, and
the facade is ``jax.lax``: what a step lowers to follows from its engine's config and nothing a process keeps."""

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.comm import comms_logger
from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

_QGZ = {"stage": 2, "zero_quantized_gradients": True}
PATHS = {
    "zeropp": {"zero_optimization": _QGZ},
    "loco": {"zero_optimization": {**_QGZ, "loco_param": {"err_beta": 0.8, "reset_T": 64}}},
    "onebit": {"zero_optimization": {"stage": 1}, "gradient_compression": {"enabled": True}},
    "plain": {"zero_optimization": {"stage": 2}},
}


def _engine(path, **extra):
    model = TransformerConfig(vocab_size=64, hidden_size=64, intermediate_size=128, num_layers=2, num_heads=2,
                              max_seq_len=32)
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(model, example_seq_len=16),
        config={"train_micro_batch_size_per_gpu": 2, "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "gradient_clipping": 1.0, "steps_per_print": 10_000, **PATHS[path], **extra})
    return engine


def _lowered(engine):
    batch = {"input_ids": np.zeros((engine.train_batch_size, 16), np.int32)}
    return engine._build_train_step().lower(engine.state, engine._shard_global_batch(batch)).as_text()


def _count(text, kind):
    return text.count(f"stablehlo.{kind}")


@pytest.mark.parametrize("path", ["zeropp", "loco", "onebit"])
def test_a_gradient_path_s_mean_is_whole_collectives_and_no_hop(devices, path):
    comms_logger.configure(enabled=True)
    comms_logger.reset()
    try:
        engine = _engine(path)
        text = _lowered(engine)
        recorded = {row["op"]: row["count"] for row in comms_logger.summary()}
    finally:
        comms_logger.configure(enabled=False)
        comms_logger.reset()
    assert _count(text, "collective_permute") == 0
    leaves = len(jax.tree_util.tree_leaves(engine.state.params))
    if path == "onebit":  # the signs and scales of every leaf are gathered; the one all-reduce is the loss's mean
        assert _count(text, "all_reduce") == 1 and _count(text, "all_gather") == 2 * leaves
        return
    # a leaf's gradient leaves one way: sharded, by the int8 wire's two all-to-alls (values, scales); whole, by one
    # all-reduce of the facade's (``_facade_grad_mean``). The loss's mean is the all-reduce beyond them.
    assert recorded["all_to_all"] % 2 == 0 and recorded["all_to_all"] // 2 + recorded["all_reduce_mean"] == leaves
    assert recorded["all_reduce_mean"] > 0
    assert _count(text, "all_reduce") == recorded["all_reduce_mean"] + 1
    assert _count(text, "all_to_all") == recorded["all_to_all"]


def test_a_step_s_text_follows_from_its_engine_s_config_alone(devices):
    """No engine leaves routing behind for the next: four engines of two configs, built in turn in one process, lower
    the text of their config whatever was built before."""
    texts = [_lowered(_engine(path)) for path in ("zeropp", "plain", "zeropp", "plain")]
    assert texts[0] == texts[2] and texts[1] == texts[3] and texts[0] != texts[1]


def test_a_leftover_collectives_block_changes_nothing_of_the_step(devices):
    """``collectives`` is no key of the config any more: a block that once routed every facade call of this step over
    a ring of int8 hops is an unknown key, and the step lowers as it does without it."""
    block = {"enabled": True, "algorithm": "ring", "codec": "int8", "codecs": ["int8"], "overlap_chunks": 4,
             "fused_gemm_collectives": True, "observe": {"enabled": True}}
    with_block = _engine("zeropp", collectives=block)
    assert with_block.config.model.extra_fields() == {"collectives": block}
    assert _lowered(with_block) == _lowered(_engine("zeropp"))
