"""ZeRO-3's own gathers in the scanned layer (``runtime/zero.py``:
``ScanGathers``, since PR 45), on four forced CPU devices: where a stacked
leaf's placement holds ``fsdp`` the products gather it themselves and the run
follows the stage-0 trajectory; everywhere else the step lowers to the text
it lowers to without the function."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
from deepspeed_tpu.models.transformer import SSMConfig
from deepspeed_tpu.topology import mesh as mesh_mod
from deepspeed_tpu.topology.mesh import build_mesh

_TOY = dict(vocab_size=256, hidden_size=64, num_heads=4, max_seq_len=32, dtype=jnp.float32,
            param_dtype=jnp.float32, attn_impl="xla")
MODELS = {
    # a gpt_neox block (parallel residual, two norms, partial rotary, biases, untied head), scanned
    "neox": TransformerConfig(
        num_layers=4, intermediate_size=256, norm="layernorm", activation="gelu_exact", position="rope",
        rotary_dim=4, parallel_block=True, parallel_mlp_norm=True, tie_embeddings=False, **_TOY),
    # a period of state-space, attention (grouped keys) and state-space layers, each with a gated MLP
    "hybrid": TransformerConfig(
        num_layers=6, num_kv_heads=2, intermediate_size=128, norm="rmsnorm", activation="silu_glu",
        position="none", qkv_bias=False, layer_types=("mamba", "attention", "mamba") * 2,
        ssm=SSMConfig(n_heads=8, head_dim=16, d_state=16, n_groups=1, d_conv=4, chunk_size=8), **_TOY),
    # latent attention (queries and keys through low ranks) and a gated MLP
    "latent": TransformerConfig(
        num_layers=4, intermediate_size=128, norm="rmsnorm", activation="silu_glu", position="rope",
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
        tie_embeddings=False, **_TOY),
}
_GATED, _ATTN, _SSM = ("w_gate", "w_up", "w_down"), ("wq", "wk", "wv", "wo"), ("ssm_in_proj", "ssm_out_proj")
# the products of a layer whose weight comes by the gather: (module path, names)
PRODUCTS = {
    "neox": [(("attn",), _ATTN), (("mlp",), ("w_up", "w_down"))],
    "hybrid": [(("layer_0", "ssm"), _SSM), (("layer_1", "attn"), _ATTN), (("layer_2", "ssm"), _SSM),
               *(((f"layer_{j}", "mlp"), _GATED) for j in range(3))],
    "latent": [(("attn",), ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")), (("mlp",), _GATED)],
}


def _engine(stage, mesh, zero_extra=None, model="neox"):
    replicas = mesh.get("dp", 1) * mesh.get("fsdp", 1)
    # SGD: AdamW divides a gradient by its own size, and a gradient counted twice trains the same
    config = {"train_micro_batch_size_per_gpu": 8 // replicas, "gradient_accumulation_steps": 2,
              "optimizer": {"type": "SGD", "params": {"lr": 0.5}}, "steps_per_print": 10 ** 6,
              "zero_optimization": {"stage": stage, "param_persistence_threshold": 1, **(zero_extra or {})}}
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(MODELS[model]), config=config, seed=3,
        mesh=build_mesh(devices=jax.devices()[:4], axis_sizes=mesh))
    assert engine.train_batch_size == 16
    return engine


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _tokens(step):
    return {"input_ids": np.random.default_rng(10 + step).integers(0, 256, (16, 32)).astype(np.int32)}


def _lowered(engine, names=False):
    return engine._build_train_step().lower(
        engine.state, engine._shard_global_batch(_tokens(0))).as_text(debug_info=names)


@pytest.mark.parametrize("case,stage,mesh,zero_extra", [
    ("neox", 3, {"fsdp": 4}, None),
    ("neox", 3, {"dp": 2, "fsdp": 2}, None),
    ("neox", 3, {"dp": 2, "fsdp": 2}, {"zero_hpz_partition_size": 2}),
    ("hybrid", 3, {"fsdp": 4}, None),
    ("latent", 3, {"dp": 2, "fsdp": 2}, None),
    ("partitioner", 3, {"fsdp": 2, "tp": 2}, None),
    ("same_text", 0, {"dp": 4}, None),
    ("same_text", 1, {"dp": 4}, None),
    ("same_text", 2, {"dp": 4}, None),
    ("same_text", 3, {"dp": 4}, None),
], ids=["fsdp4", "dp2-fsdp2", "hpz", "hybrid-fsdp4", "latent-dp2-fsdp2", "fsdp2-tp2",
        "stage0", "stage1", "stage2", "stage3-fsdp1"])
def test_the_scanned_layer_gathers_its_own_weights_only_under_zero3_over_fsdp(
        devices, monkeypatch, case, stage, mesh, zero_extra):
    if case == "same_text":
        engine = _engine(stage, mesh)
        text = _lowered(engine)
        assert engine.zero_gather_mb == 0 and "zero_gather" not in _lowered(engine, names=True)
        monkeypatch.setattr(mesh_mod, "dot_general_for", lambda path: None)
        assert _lowered(engine) == text
        return
    model = "neox" if case == "partitioner" else case
    plain, sharded = _engine(0, {"dp": 4}, model=model), _engine(stage, mesh, zero_extra, model)
    losses = [[float(e.train_batch(_tokens(i))["loss"]) for i in range(3)] for e in (plain, sharded)]
    np.testing.assert_allclose(losses[1], losses[0], rtol=2e-4)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(plain.module_state_dict()),
                            jax.tree_util.tree_leaves(sharded.module_state_dict())):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
    text = _lowered(sharded, names=True)
    if case == "partitioner":  # with tp longer than 1 the products stay the partitioner's (CHANGES.md, PR 45)
        assert sharded._scan_gathers is None and sharded.zero_gather_mb == 0 and "zero_gather" not in text
        return
    received = sharded._scan_gathers.received
    # every such product went through the gather (a silent fall-back shows here), and the span's count is theirs
    leaves = {("layers", *module, name, "kernel") for module, names in PRODUCTS[model] for name in names}
    assert set(received) == leaves
    shards, params = mesh["fsdp"], sharded.module_state_dict()
    stacked = sum(np.asarray(_at(params, path)).nbytes for path in leaves)
    assert sharded._scan_gathers.received_bytes == 2 * stacked * (shards - 1) // shards
    assert "zero_gather" in text and "zero_scatter" in text


@pytest.mark.parametrize("shards,shape,dim", [(4, (8, 6), 0), (4, (3, 12), 1), (2, (5, 2, 3), 1)],
                         ids=["even-rows", "odd-rows", "one-row"])
def test_the_ring_both_ways_is_a_reduce_scatter(devices, shards, shape, dim):
    """``zero._scatter`` against ``lax.psum_scatter``: a shard of an odd number of rows is cut unevenly, a shard of
    one row goes one way round."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.runtime import zero
    from deepspeed_tpu.utils.compat import shard_map

    mesh = build_mesh(devices=jax.devices()[:shards], axis_sizes={"fsdp": shards})
    dw = jnp.asarray(np.random.default_rng(0).standard_normal((shards, *shape)), jnp.float32)
    spec = P("fsdp", *[None] * len(shape))

    def both(dw):
        return (zero._scatter(dw[0], dim, shards)[None],
                lax.psum_scatter(dw[0], "fsdp", scatter_dimension=dim, tiled=True)[None])

    ring, whole = jax.jit(shard_map(both, mesh=mesh, in_specs=spec, out_specs=(spec, spec)))(dw)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(whole), rtol=1e-6, atol=1e-6)
