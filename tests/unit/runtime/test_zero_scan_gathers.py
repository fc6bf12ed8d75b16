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
_LATENT = dict(q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16)
_ROUTED = dict(num_experts=8, moe_drop_tokens=False, moe_router="sigmoid", moe_intermediate_size=32,
               moe_shared_experts=1)
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
    # EVA attention (windows of 32 in chunks of 4, the published toy of test_eva.py) and a gated MLP
    "eva": TransformerConfig(
        num_layers=3, num_kv_heads=4, intermediate_size=160, norm="rmsnorm", activation="silu_glu", position="rope",
        qkv_bias=False, rope_theta=1e5, eva_window=32, eva_chunk=4, norm_unit_offset=True, fp32_residual=True,
        num_pred_heads=8, tie_embeddings=False, **_TOY),
    # one dense layer, then two whose MLP is eight routed experts beside a shared one (test_latent_routed.py's toy)
    "routed": TransformerConfig(
        num_layers=3, intermediate_size=160, norm="rmsnorm", activation="silu_glu", position="rope",
        qkv_bias=False, rope_theta=1e6, rope_interleaved=True, **_LATENT, **_ROUTED, first_dense_layers=1,
        moe_routed_scale=1.8, tie_embeddings=False, **_TOY),
    # the same with four residual streams mixed by hyper-connections (test_xing.py's toy, its rotary plain)
    "hyper": TransformerConfig(
        num_layers=4, intermediate_size=160, norm="rmsnorm", activation="silu_glu", position="rope",
        qkv_bias=False, rope_interleaved=True, norm_eps=1e-6, **_LATENT, **_ROUTED, first_dense_layers=2,
        moe_routed_scale=2.0, hc_mult=4, tie_embeddings=False, **_TOY),
}
_GATED, _ATTN, _SSM = ("w_gate", "w_up", "w_down"), ("wq", "wk", "wv", "wo"), ("ssm_in_proj", "ssm_out_proj")
_LATENT_ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
# the products of a layer whose weight comes by the gather: (module path, names)
PRODUCTS = {
    "neox": [(("attn",), _ATTN), (("mlp",), ("w_up", "w_down"))],
    "hybrid": [(("layer_0", "ssm"), _SSM), (("layer_1", "attn"), _ATTN), (("layer_2", "ssm"), _SSM),
               *(((f"layer_{j}", "mlp"), _GATED) for j in range(3))],
    "latent": [(("attn",), _LATENT_ATTN), (("mlp",), _GATED)],
    "eva": [(("attn",), _ATTN), (("mlp",), _GATED)],
    "routed": [(("attn",), _LATENT_ATTN)],
    "hyper": [(("attn",), _LATENT_ATTN)],
}
# what else of the scanned layer is placed over ``fsdp`` past its first dimension, and stays the partitioner's to
# gather: vectors that no product reads, and the matrices of a block that asks for no gather of its own (a routed
# MLP's experts, shared expert and gate; EVA's and a hyper-connection's own projections). ROADMAP D5 calls that
# fall-back silent; this is what it is today.
_NORMS = ("attn_norm/scale", "mlp_norm/scale")
_LATENT_NORMS = ("attn/kv_norm/scale", "attn/q_norm/scale", *_NORMS)
_MOE = (*(f"moe/experts/{w}" for w in _GATED), *(f"moe/shared/{w}/kernel" for w in _GATED), "moe/gate/wg/kernel",
        "moe/gate/e_bias")
_SSM_REST = ("A_log", "D", "dt_bias", "ssm_conv/bias", "ssm_conv/kernel", "ssm_norm/scale")
STAYS = {
    "neox": (*(f"attn/{w}/bias" for w in _ATTN), "mlp/w_down/bias", *_NORMS, "attn_norm/bias", "mlp_norm/bias"),
    "hybrid": (*(f"layer_{j}/ssm/{w}" for j in (0, 2) for w in _SSM_REST), "layer_0/ssm_pre_norm/scale",
               "layer_2/ssm_pre_norm/scale", "layer_1/attn_norm/scale", *(f"layer_{j}/mlp_norm/scale" for j in range(3))),
    "latent": _LATENT_NORMS,
    "eva": ("attn/mu", "attn/phi", *_NORMS),
    "routed": (*_LATENT_NORMS, *_MOE),
    "hyper": (*_LATENT_NORMS, *_MOE, "attn_hc/b", "attn_hc/phi", "mlp_hc/b", "mlp_hc/phi"),
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
    ("hybrid", 3, {"dp": 2, "fsdp": 2}, None),
    ("latent", 3, {"fsdp": 4}, None),
    ("latent", 3, {"dp": 2, "fsdp": 2}, None),
    ("eva", 3, {"fsdp": 4}, None),
    ("eva", 3, {"dp": 2, "fsdp": 2}, None),
    ("routed", 3, {"fsdp": 4}, None),
    ("routed", 3, {"dp": 2, "fsdp": 2}, None),
    ("hyper", 3, {"fsdp": 4}, None),
    ("hyper", 3, {"dp": 2, "fsdp": 2}, None),
    ("partitioner", 3, {"fsdp": 2, "tp": 2}, None),
    ("same_text", 0, {"dp": 4}, None),
    ("same_text", 1, {"dp": 4}, None),
    ("same_text", 2, {"dp": 4}, None),
    ("same_text", 3, {"dp": 4}, None),
], ids=["fsdp4", "dp2-fsdp2", "hpz", "hybrid-fsdp4", "hybrid-dp2-fsdp2", "latent-fsdp4", "latent-dp2-fsdp2",
        "eva-fsdp4", "eva-dp2-fsdp2", "routed-fsdp4", "routed-dp2-fsdp2", "hyper-fsdp4", "hyper-dp2-fsdp2", "fsdp2-tp2",
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
    from deepspeed_tpu.runtime import zero

    placed = {path for path, (spec, _) in sharded._scan_gathers.leaves.items()
              if path[0] == "layers" and zero._held_dim(spec) is not None}
    assert {"/".join(path[1:]) for path in placed - leaves} == set(STAYS[model])
    shards, params = mesh["fsdp"], sharded.module_state_dict()
    stacked = sum(np.asarray(_at(params, path)).nbytes for path in leaves)
    assert sharded._scan_gathers.received_bytes == 2 * stacked * (shards - 1) // shards
    assert "zero_gather" in text and "zero_scatter" in text


# a shard's rows along the scattered dimension: an even number is halved, an odd one is cut unevenly, one row goes
# one way round
_ROWS = {"even-rows": lambda n: ((2 * n, 6), 0), "odd-rows": lambda n: ((3, 3 * n), 1),
         "one-row": lambda n: ((5, n, 3), 1)}


def _whole_numbers(shape, dtype, seed=0):
    """Sums over eight chips that are exact in bf16, whatever the order."""
    return jnp.asarray(np.random.default_rng(seed).integers(-4, 5, shape), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("rows", list(_ROWS))
@pytest.mark.parametrize("shards", [2, 4, 8])
def test_the_ring_both_ways_is_a_reduce_scatter(devices, shards, rows, dtype):
    """``zero._scatter`` against ``lax.psum_scatter`` and against NumPy's sum, to the bit."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.runtime import zero
    from deepspeed_tpu.utils.compat import shard_map

    shape, dim = _ROWS[rows](shards)
    mesh = build_mesh(devices=jax.devices()[:shards], axis_sizes={"fsdp": shards})
    dw = _whole_numbers((shards, *shape), dtype)
    spec = P("fsdp", *[None] * len(shape))

    def both(dw):
        return (zero._scatter(dw[0], dim, shards)[None],
                lax.psum_scatter(dw[0], "fsdp", scatter_dimension=dim, tiled=True)[None])

    fn = jax.jit(shard_map(both, mesh=mesh, in_specs=spec, out_specs=(spec, spec)))
    ring, whole = fn(dw)
    assert ring.dtype == dtype
    np.testing.assert_array_equal(np.asarray(ring, np.float32), np.asarray(whole, np.float32))
    total = np.asarray(dw, np.float32).sum(0)
    np.testing.assert_array_equal(np.asarray(ring, np.float32), np.stack(np.split(total, shards, dim)))
    # the ring is hops and nothing else of its own: shards - 1 each way, one way for a single row
    text = jax.jit(shard_map(lambda dw: zero._scatter(dw[0], dim, shards)[None], mesh=mesh, in_specs=spec,
                             out_specs=spec)).lower(dw).as_text()
    ways = 1 if shape[dim] == shards else 2
    assert text.count("stablehlo.collective_permute") == ways * (shards - 1)
    assert "stablehlo.reduce_scatter" not in text and "stablehlo.all_reduce" not in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("dim", [0, 1])
@pytest.mark.parametrize("shards", [2, 4, 8])
def test_the_gather_is_the_shards_side_by_side(devices, shards, dim, dtype):
    """``zero._gather``: every chip holds the shards concatenated along ``dim`` in the chips' order, by one whole
    ``all_gather`` under the ``zero_gather`` scope that a device trace reads."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.runtime import zero
    from deepspeed_tpu.utils.compat import shard_map

    mesh = build_mesh(devices=jax.devices()[:shards], axis_sizes={"fsdp": shards})
    w = _whole_numbers((shards, 3, 5), dtype, seed=1)
    fn = jax.jit(shard_map(lambda w: zero._gather(w[0], dim)[None], mesh=mesh, in_specs=P("fsdp"),
                           out_specs=P("fsdp"), check_vma=False))
    full = np.asarray(fn(w), np.float32)
    want = np.concatenate(list(np.asarray(w, np.float32)), dim)
    for chip in range(shards):
        np.testing.assert_array_equal(full[chip], want)
    text = fn.lower(w).as_text(debug_info=True)
    assert text.count("stablehlo.all_gather") == 1 and "collective_permute" not in text and "zero_gather" in text
