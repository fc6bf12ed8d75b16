"""Ulysses sequence parallelism + MoE/expert parallelism tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import MeshConfig
from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
from deepspeed_tpu.topology import build_mesh, mesh_context
from tests.unit.parallel.partial_manual import partial_manual_xfail


def _tokens(bs, seq, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, size=(bs, seq), dtype=np.int32)}


def _cfg(mesh=None, stage=0, micro=1):
    cfg = {
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage, "param_persistence_threshold": 1},
        "steps_per_print": 1000,
    }
    if mesh:
        cfg["mesh"] = mesh
    return cfg


SP_MODEL = TransformerConfig(
    vocab_size=256, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=4, max_seq_len=64,
)


class TestUlysses:
    def test_sp_matches_dp_baseline(self, devices):
        """sp=2 sequence sharding must reproduce the non-sp trajectory."""
        e1, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(SP_MODEL), config=_cfg(mesh={"dp": 4, "pp": 2}), seed=8
        )
        e2, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(SP_MODEL), config=_cfg(mesh={"dp": 4, "sp": 2}), seed=8
        )
        l1 = [float(e1.train_batch(_tokens(4, 32, seed=60 + i))["loss"]) for i in range(3)]
        l2 = [float(e2.train_batch(_tokens(4, 32, seed=60 + i))["loss"]) for i in range(3)]
        np.testing.assert_allclose(l1, l2, rtol=1e-4)

    def test_sp_with_zero3(self, devices):
        engine, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(SP_MODEL),
            config=_cfg(mesh={"dp": 2, "fsdp": 2, "sp": 2}, stage=3),
        )
        batch = _tokens(engine.train_batch_size, 32)
        losses = [float(engine.train_batch(batch)["loss"]) for _ in range(3)]
        assert losses[-1] < losses[0]

    def test_distributed_attention_class(self, devices):
        """Explicit shard_map DistributedAttention == local attention."""
        from deepspeed_tpu.ops import causal_attention
        from deepspeed_tpu.parallel.ulysses import DistributedAttention

        mesh = build_mesh(MeshConfig(dp=2, sp=4))
        B, S, H, D = 2, 16, 8, 8
        rng = jax.random.PRNGKey(0)
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, S, H, D)) for i in range(3))
        ref = causal_attention(q, k, v)
        with mesh_context(mesh):
            dist_attn = DistributedAttention(lambda q, k, v: causal_attention(q, k, v))
            out = jax.jit(dist_attn)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=5e-5, atol=5e-6)

    def test_distributed_attention_uneven_heads_raises(self, devices):
        from deepspeed_tpu.parallel.ulysses import DistributedAttention

        mesh = build_mesh(MeshConfig(sp=8))
        with mesh_context(mesh):
            da = DistributedAttention(lambda q, k, v: q)
            with pytest.raises(ValueError, match="not divisible"):
                da(jnp.zeros((1, 8, 4, 4)), jnp.zeros((1, 8, 4, 4)), jnp.zeros((1, 8, 4, 4)))


MOE_MODEL = TransformerConfig(
    vocab_size=256, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, max_seq_len=32, num_experts=4, moe_top_k=2,
    moe_capacity_factor=2.0,
)


class TestMoE:
    def test_moe_trains(self, devices):
        engine, *_ = deepspeed_tpu.initialize(model=causal_lm_spec(MOE_MODEL), config=_cfg())
        batch = _tokens(engine.train_batch_size, 16)
        losses = [float(engine.train_batch(batch)["loss"]) for _ in range(6)]
        assert losses[-1] < losses[0]

    @partial_manual_xfail
    def test_expert_parallel_matches_dense_ep(self, devices):
        """ep=4 sharded experts must reproduce the ep=1 trajectory."""
        e1, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(MOE_MODEL), config=_cfg(mesh={"dp": 2, "pp": 4}), seed=13
        )
        e2, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(MOE_MODEL), config=_cfg(mesh={"dp": 2, "ep": 4}), seed=13
        )
        l1 = [float(e1.train_batch(_tokens(2, 16, seed=80 + i))["loss"]) for i in range(3)]
        l2 = [float(e2.train_batch(_tokens(2, 16, seed=80 + i))["loss"]) for i in range(3)]
        np.testing.assert_allclose(l1, l2, rtol=1e-4)
        # expert weights actually sharded over ep
        w = e2.state.params["layers"]["moe"]["experts"]["w_up"]
        assert "ep" in str(w.sharding.spec), w.sharding.spec

    def test_gating_capacity_and_aux(self):
        from deepspeed_tpu.parallel.moe import top_k_gating

        T, E, C = 32, 4, 8
        logits = jax.random.normal(jax.random.PRNGKey(0), (T, E))
        l_aux, combine, dispatch, counts = top_k_gating(logits, 2, C, drop_tokens=True, use_rts=False)
        assert combine.shape == (T, E, C)
        assert dispatch.shape == (T, E, C)
        # capacity respected
        assert int(dispatch.sum(axis=(0,))[..., :].max()) <= C
        per_slot = dispatch.sum(axis=0)  # [E, C] tokens per slot
        assert float(per_slot.max()) <= 1.0 + 1e-6  # one token per slot
        assert float(l_aux) > 0
        # combine weights normalized per token: sum to 1 (kept) or 0 (dropped)
        w = np.asarray(combine.sum(axis=(1, 2)))
        assert np.all(np.isclose(w, 1.0, atol=1e-5) | np.isclose(w, 0.0)), w

    def test_top1_gating(self):
        from deepspeed_tpu.parallel.moe import top_k_gating

        logits = jax.random.normal(jax.random.PRNGKey(1), (16, 4))
        l_aux, combine, dispatch, counts = top_k_gating(logits, 1, 8, use_rts=False)
        # each token goes to at most one expert slot
        assert float(dispatch.sum(axis=(1, 2)).max()) <= 1.0 + 1e-6

    def test_no_drop_tokens_keeps_everything(self):
        from deepspeed_tpu.parallel.moe import top_k_gating

        # all tokens prefer expert 0: without drops, every token must be kept
        logits = jnp.tile(jnp.array([[10.0, 0.0, 0.0, 0.0]]), (16, 1))
        l_aux, combine, dispatch, counts = top_k_gating(
            logits, 1, capacity=2, drop_tokens=False, use_rts=False
        )
        w = np.asarray(combine.sum(axis=(1, 2)))
        assert np.all(np.isclose(w, 1.0, atol=1e-5)), w

    def test_unknown_gate_policy_raises(self, devices):
        from deepspeed_tpu.parallel.moe import MoEConfig, MoELayer

        layer = MoELayer(MoEConfig(num_experts=2, noisy_gate_policy="bogus"), 8, 16, train=True)
        with pytest.raises(ValueError, match="noisy_gate_policy"):
            layer.init(
                {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
                jnp.zeros((1, 4, 8)),
            )


# ----------------------------------------------------------------- PR-MoE

def test_pr_moe_residual_trains(devices):
    """PR-MoE residual expert + coefficient gate (reference moe/layer.py
    use_residual): trains, and the residual params exist."""
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    cfg = TransformerConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                            num_layers=2, num_heads=2, max_seq_len=16,
                            num_experts=4, moe_top_k=1, moe_use_residual=True)
    e, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=8),
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "mesh": {"dp": 4, "ep": 2}, "steps_per_print": 1000})
    p = e.state.params["layers"]["moe"]
    assert "residual_mlp" in p and "coefficient" in p
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 64, (8, 8), dtype=np.int32)}
    losses = [float(e.train_batch(batch)["loss"]) for _ in range(5)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses


def test_pyramid_moe_per_layer_experts(devices):
    """Pyramid expert counts per layer (dense -> 2 -> 4), scan disabled."""
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec
    import pytest as _pytest

    cfg = TransformerConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                            num_layers=3, num_heads=2, max_seq_len=16,
                            moe_layer_experts=(0, 2, 4), scan_layers=False)
    e, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg, example_seq_len=8),
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "mesh": {"dp": 4, "ep": 2}, "steps_per_print": 1000})
    p = e.state.params
    assert "mlp" in p["layer_0"] and "moe" not in p["layer_0"]
    assert p["layer_1"]["moe"]["experts"]["w_up"].shape[0] == 2
    assert p["layer_2"]["moe"]["experts"]["w_up"].shape[0] == 4
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 64, (8, 8), dtype=np.int32)}
    losses = [float(e.train_batch(batch)["loss"]) for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses

    # pyramid + scan is rejected with a clear error
    bad = TransformerConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                            num_layers=3, num_heads=2, max_seq_len=16,
                            moe_layer_experts=(0, 2, 4), scan_layers=True)
    with _pytest.raises(ValueError, match="scan_layers=False"):
        deepspeed_tpu.initialize(
            model=causal_lm_spec(bad, example_seq_len=8),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                    "steps_per_print": 1000})


@partial_manual_xfail
def test_alibi_model_under_sp_matches_dp(devices):
    """Bloom-style ALiBi + Ulysses sequence parallelism: the sharding-
    constraint form keeps the program global SPMD, so the per-head slope
    bias partitions with the head axis — sp=2 must reproduce the pure-dp
    trajectory."""
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    cfg = TransformerConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                            num_layers=2, num_heads=4, max_seq_len=64,
                            norm="layernorm", activation="gelu",
                            position="alibi", embed_norm=True)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, (8, 32), dtype=np.int32)

    def run(mesh_axes, gas):
        engine, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(cfg, example_seq_len=32),
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": gas,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 1}, "mesh": mesh_axes,
                    "steps_per_print": 10000, "seed": 7})
        losses = []
        for _ in range(3):
            m = engine.train_batch({"input_ids": ids[: engine.train_batch_size]})
            losses.append(float(np.asarray(m["loss"])))
        return losses

    # equal GLOBAL batch (8 rows, same data): dp=8 gas=1 vs sp2 x dp4 gas=2
    l_dp = run({"dp": 8}, gas=1)
    l_sp = run({"sp": 2, "dp": 4}, gas=2)
    np.testing.assert_allclose(l_sp, l_dp, rtol=2e-5, atol=2e-6)


class TestMoETPComposition:
    """ISSUE 15: ep x tp meshes route the MoE block through the explicit
    collective token dispatch (parallel/moe.py collective_moe_apply) instead
    of the old loud refusal at runtime/engine.py."""

    def test_collective_dispatch_matches_gspmd_on_ep_mesh(self, devices):
        """Forced collective dispatch reproduces the verified GSPMD ep-only
        trajectory on the SAME mesh — the correctness pin for the shard_map
        + facade all_to_all region itself (no cross-mesh init confounds)."""
        coll = TransformerConfig(**{**MOE_MODEL.__dict__,
                                    "moe_dispatch": "collective"})
        e1, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(MOE_MODEL), config=_cfg(mesh={"dp": 2, "ep": 4}),
            seed=13)
        e2, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(coll), config=_cfg(mesh={"dp": 2, "ep": 4}),
            seed=13)
        l1 = [float(e1.train_batch(_tokens(2, 16, seed=70 + i))["loss"])
              for i in range(3)]
        l2 = [float(e2.train_batch(_tokens(2, 16, seed=70 + i))["loss"])
              for i in range(3)]
        np.testing.assert_allclose(l1, l2, rtol=1e-4)

    def test_ep_tp_trains_and_matches_global_math(self, devices):
        """Acceptance: dp2 x ep2 x tp2 trains end-to-end, and the collective
        dispatch on that mesh reproduces the GLOBAL (1-device) math of the
        same loss on the engine's own trained params — the direct
        mis-routing pin (the GSPMD constraint path the engine used to
        refuse deviates ~0.5% here; the collective region must not).
        Cross-mesh trajectory comparison is impossible at identical params
        (sharded init draws per-shard RNG), so the reference is a replay,
        not a second engine."""
        from deepspeed_tpu.topology import mesh as mesh_mod
        from deepspeed_tpu.topology.mesh import set_mesh

        e2, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(MOE_MODEL),
            config=_cfg(mesh={"dp": 2, "ep": 2, "tp": 2}, micro=2), seed=21)
        assert e2.train_batch_size == 4
        l2 = [float(e2.train_batch(_tokens(4, 16, seed=90 + i))["loss"])
              for i in range(6)]
        assert l2[-1] < l2[0]  # end-to-end: the composition actually learns
        w = e2.state.params["layers"]["moe"]["experts"]["w_up"]
        assert "ep" in str(w.sharding.spec), w.sharding.spec
        # replay: same loss fn, same params, same rng — once through the
        # ep x tp collective dispatch, once as plain global math
        host = jax.device_get(e2.state.params)
        batch = _tokens(4, 16, seed=99)
        rng = jax.random.PRNGKey(7)
        set_mesh(e2.mesh)
        mesh_loss = float(jax.jit(e2.model.loss_fn)(host, batch, rng)[0])
        mesh_mod._ACTIVE_MESH = None  # no mesh: the unsharded reference
        global_loss = float(jax.jit(e2.model.loss_fn)(host, batch, rng)[0])
        np.testing.assert_allclose(mesh_loss, global_loss, rtol=1e-5)

    def test_ep_tp_unservable_shape_fails_loudly(self, devices):
        """The old blanket NotImplementedError is gone; what remains loud is
        a genuinely unservable ep x tp shape (experts not divisible by ep)
        — it must raise at trace time, never silently mis-route."""
        bad = TransformerConfig(**{**MOE_MODEL.__dict__, "num_experts": 3})
        with pytest.raises(ValueError, match="collective token dispatch"):
            engine, *_ = deepspeed_tpu.initialize(
                model=causal_lm_spec(bad),
                config=_cfg(mesh={"dp": 2, "ep": 2, "tp": 2}))
            engine.train_batch(_tokens(engine.train_batch_size, 16))


@pytest.mark.parametrize("owner,field", [
    ("TransformerConfig", "moe_dispatch_algorithm"), ("TransformerConfig", "moe_wire_codec"),
    ("MoEConfig", "dispatch_algorithm"), ("MoEConfig", "dispatch_codec")])
def test_the_dispatch_wire_takes_no_routing_option(owner, field):
    """The dispatch and combine cross ``ep`` as the facade's ``all_to_all``, which is ``jax.lax``'s: a model built
    with one of the options that chose another way fails as any unknown field does."""
    from deepspeed_tpu.parallel.moe import MoEConfig

    cls = {"TransformerConfig": TransformerConfig, "MoEConfig": MoEConfig}[owner]
    with pytest.raises(TypeError, match=field):
        cls(**{field: "ring"})
