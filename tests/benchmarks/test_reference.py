"""The plain reference against the program's own model at a tiny gpt_neox
size, fp32 on both sides, so the only room is the order of the arithmetic."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import harness, program

ARCH = harness.load_architecture("gpt_neox")


@pytest.fixture(scope="module")
def tiny():
    from deepspeed_tpu.models import CausalLM

    from tests.benchmarks.conftest import TINY_MODEL

    config = dict(harness.load_config("pythia-410m"), **TINY_MODEL)
    model_cfg = program.model_config(config, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, config["vocab_size"], (3, 48), dtype=np.int32)
    params = CausalLM(model_cfg).init({"params": jax.random.PRNGKey(1)},
                                      {"input_ids": jnp.asarray(tokens)}, train=False)["params"]
    # zero-initialised biases would hide a dropped bias: make every leaf random
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree_util.tree_unflatten(
        tree, [l + 0.05 * jax.random.normal(k, l.shape) for l, k in zip(leaves, keys)])
    return config, model_cfg, params, tokens


def test_program_config_comes_from_the_published_keys(tiny):
    config, model_cfg, _, _ = tiny
    assert model_cfg.parallel_block and model_cfg.parallel_mlp_norm
    assert model_cfg.rotary_dim == 4 and model_cfg.activation == "gelu_exact"
    assert model_cfg.norm == "layernorm" and not model_cfg.tie_embeddings
    full = program.model_config(harness.load_config("pythia-1.4b"), jnp.bfloat16)
    assert (full.hidden_size, full.num_layers, full.num_heads, full.rotary_dim) == (2048, 24, 16, 32)


# the thirteen gpt_neox keys that ``published()`` let through until PR 27,
# when it became the file minus the benchmark's own notes
PARENT_WHITELIST = (
    "model_type", "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "max_position_embeddings", "hidden_act", "rotary_pct",
    "rotary_emb_base", "layer_norm_eps", "use_parallel_residual", "tie_word_embeddings")


@pytest.mark.parametrize("name", ["pythia-410m", "pythia-1.4b"])
def test_program_and_reference_get_what_the_whitelist_gave_them(name):
    import dataclasses

    from deepspeed_tpu.checkpoint.hf import config_from_hf

    config = harness.load_config(name)
    through_whitelist = {k: config[k] for k in PARENT_WHITELIST}
    assert program.model_config(config, jnp.bfloat16) == dataclasses.replace(
        config_from_hf(through_whitelist), dtype=jnp.bfloat16)
    # the reference reads keys by name: every one it had it still has, with the
    # same value; what is new to it are published keys it does not read
    now = program.published(config)
    assert through_whitelist.items() <= now.items()
    assert set(now) - set(PARENT_WHITELIST) == {"initializer_range", "bos_token_id", "eos_token_id"}
    assert not set(now) & set(program.NOTE_KEYS) and set(config) - set(now) == set(program.NOTE_KEYS)


def test_logits_agree_with_the_programs_model(tiny):
    from deepspeed_tpu.models import CausalLM

    config, model_cfg, params, tokens = tiny
    reference = harness.load_reference("gpt_neox")
    _, want = CausalLM(model_cfg).apply({"params": params}, {"input_ids": jnp.asarray(tokens)})
    got = reference.forward(ARCH.reference_weights(params), program.published(config),
                            jnp.asarray(tokens))
    assert program.relative_error(got, want) < 1e-5


def test_loss_agrees_with_the_programs_model(tiny):
    from deepspeed_tpu.models import CausalLM

    config, model_cfg, params, tokens = tiny
    reference = harness.load_reference("gpt_neox")
    want, _ = CausalLM(model_cfg).apply({"params": params}, {"input_ids": jnp.asarray(tokens)})
    got = reference.loss(ARCH.reference_weights(params), program.published(config),
                         jnp.asarray(tokens))
    assert float(got) == pytest.approx(float(want), rel=1e-5)


def test_reference_imports_nothing_of_the_program():
    import os

    src = open(os.path.join(harness.BENCH_DIR, "reference", "gpt_neox.py")).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2]
