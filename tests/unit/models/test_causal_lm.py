"""CausalLM model family tests on the CPU mesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import PRESETS, CausalLM, TransformerConfig, causal_lm_spec, transformer
from tests.unit.models.test_scan_residuals import CASES as RESIDUAL_CASES
from tests.unit.parallel.partial_manual import partial_manual_xfail


def _tokens(bs, seq, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, size=(bs, seq), dtype=np.int32)}


def _cfg(stage=0, mesh=None, micro=1, extra=None):
    cfg = {
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage, "param_persistence_threshold": 1},
        "steps_per_print": 1000,
    }
    if mesh:
        cfg["mesh"] = mesh
    if extra:
        cfg.update(extra)
    return cfg


TINY = TransformerConfig(
    vocab_size=256, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, max_seq_len=32,
)


def test_tiny_llama_trains(devices):
    engine, *_ = deepspeed_tpu.initialize(model=causal_lm_spec(TINY), config=_cfg())
    batch = _tokens(engine.train_batch_size, 16)
    losses = [float(engine.train_batch(batch)["loss"]) for _ in range(6)]
    assert losses[-1] < losses[0]
    # initial loss near ln(vocab)
    assert abs(losses[0] - np.log(256)) < 1.0


def test_gpt2_style_trains(devices):
    cfg = TransformerConfig(
        vocab_size=256, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, max_seq_len=32, norm="layernorm", activation="gelu",
        position="learned", tie_embeddings=True,
    )
    engine, *_ = deepspeed_tpu.initialize(model=causal_lm_spec(cfg), config=_cfg())
    batch = _tokens(engine.train_batch_size, 16)
    losses = [float(engine.train_batch(batch)["loss"]) for _ in range(6)]
    assert losses[-1] < losses[0]


@partial_manual_xfail
def test_tp_matches_pure_dp(devices):
    """tp=2 must reproduce the dp-only loss trajectory (same seed/data).

    The baseline uses an idle pp axis to get the same dp width (4) on 8
    devices, so both engines see identical global batches.
    """
    e1, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(TINY), config=_cfg(mesh={"dp": 4, "pp": 2}), seed=4
    )
    e2, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(TINY), config=_cfg(mesh={"dp": 4, "tp": 2}), seed=4
    )
    assert e1.train_batch_size == 4 and e2.train_batch_size == 4
    l1 = [float(e1.train_batch(_tokens(4, 16, seed=30 + i))["loss"]) for i in range(3)]
    l2 = [float(e2.train_batch(_tokens(4, 16, seed=30 + i))["loss"]) for i in range(3)]
    np.testing.assert_allclose(l1, l2, rtol=1e-4)
    # params are tp-sharded
    import jax

    sharded = [
        x for x in jax.tree_util.tree_leaves(e2.state.params)
        if any(ax == "tp" for e in x.sharding.spec for ax in (e if isinstance(e, tuple) else (e,)) if e)
    ]
    assert sharded, "expected at least one tp-sharded parameter"


def test_zero3_tp_composition(devices):
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(TINY), config=_cfg(stage=3, mesh={"dp": 2, "fsdp": 2, "tp": 2})
    )
    batch = _tokens(engine.train_batch_size, 16)
    losses = [float(engine.train_batch(batch)["loss"]) for _ in range(4)]
    assert losses[-1] < losses[0]


def test_remat_and_no_scan_match(devices):
    base = causal_lm_spec(TINY)
    remat_cfg = TransformerConfig(**{**TINY.__dict__, "remat": True})
    e1, *_ = deepspeed_tpu.initialize(model=base, config=_cfg(), seed=11)
    e2, *_ = deepspeed_tpu.initialize(model=causal_lm_spec(remat_cfg), config=_cfg(), seed=11)
    b = _tokens(e1.train_batch_size, 16, seed=5)
    l1 = float(e1.train_batch(b)["loss"])
    l2 = float(e2.train_batch(b)["loss"])
    np.testing.assert_allclose(l1, l2, rtol=1e-5)


def test_presets_exist():
    assert "llama3-8b" in PRESETS and "gpt2-125m" in PRESETS
    assert PRESETS["llama3-8b"].num_params() > 7e9
    assert 1.0e8 < PRESETS["gpt2-125m"].num_params() < 2.0e8


@pytest.mark.parametrize("n_exp,top_k,residual", [(0, 2, False), (4, 2, False), (4, 1, True)])
def test_num_params_matches_init(devices, n_exp, top_k, residual):
    """Analytic num_params == actual initialized leaf count (dense/MoE/PR-MoE)."""
    import jax

    cfg = TransformerConfig(**{
        **TINY.__dict__, "num_experts": n_exp, "moe_top_k": top_k,
        "moe_use_residual": residual,
    })
    engine, *_ = deepspeed_tpu.initialize(model=causal_lm_spec(cfg), config=_cfg())
    actual = sum(x.size for x in jax.tree.leaves(engine.state.params))
    assert actual == cfg.num_params()
    if n_exp:
        assert cfg.num_active_params() < cfg.num_params()
    else:
        assert cfg.num_active_params() == cfg.num_params()


def test_padding_mask(devices):
    engine, *_ = deepspeed_tpu.initialize(model=causal_lm_spec(TINY), config=_cfg())
    batch = _tokens(engine.train_batch_size, 16)
    mask = np.ones((engine.train_batch_size, 16), np.int32)
    mask[:, 8:] = 0
    batch["attention_mask"] = mask
    m = engine.train_batch(batch)
    assert np.isfinite(m["loss"])


def test_attention_kernels_tp_sharded(devices):
    """Regression: q/k/v kernels must carry the tp placement (keystr paths)."""
    engine, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(TINY), config=_cfg(mesh={"dp": 4, "tp": 2})
    )
    wq = engine.state.params["layers"]["attn"]["wq"]["kernel"]
    assert "tp" in str(wq.sharding.spec), wq.sharding.spec
    wo = engine.state.params["layers"]["attn"]["wo"]["kernel"]
    assert "tp" in str(wo.sharding.spec), wo.sharding.spec


def test_sparse_attention_model_trains(devices):
    """attn_impl='sparse' (reference sparse_attention config section): the
    model runs the tile-skipping kernels fwd+bwd through the engine, and a
    DENSE layout reproduces the standard path exactly."""
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    common = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
                  num_layers=2, num_heads=4, max_seq_len=64,
                  norm="layernorm", activation="gelu", position="learned")
    ids = np.random.default_rng(0).integers(0, 128, (8, 64), dtype=np.int32)

    def run(**extra):
        engine, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(TransformerConfig(**common, **extra),
                                 example_seq_len=64),
            config={"train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "AdamW", "params": {"lr": 5e-3}},
                    "zero_optimization": {"stage": 1},
                    "steps_per_print": 10000, "seed": 3})
        return [float(np.asarray(engine.train_batch({"input_ids": ids})["loss"]))
                for _ in range(3)]

    # dense layout == exact attention (XLA path) trajectory
    l_dense_layout = run(attn_impl="sparse",
                         sparse_attention={"mode": "dense", "block": 16})
    l_exact = run(attn_impl="xla")
    np.testing.assert_allclose(l_dense_layout, l_exact, rtol=2e-5, atol=2e-6)

    # bigbird layout trains (loss decreases through the sparse bwd kernels)
    l_bb = run(attn_impl="sparse",
               sparse_attention={"mode": "bigbird", "block": 16,
                                 "num_random_blocks": 1,
                                 "num_sliding_window_blocks": 2})
    assert l_bb[-1] < l_bb[0]


# ---------------------------------------------------------------------------
# What a layer recomputes in its backward (since PR 38) changes no gradient:
# the flax model against the same mathematics written out below, which has no
# scan, no module and no checkpoint.

def _plain_loss(params, cfg, ids):
    """``CausalLM(cfg).apply(..., train=True)[0]`` the plain way: a Python loop
    over the layers of the stacked tree, ``jax.numpy`` and nothing of the
    program's, rounding to ``cfg.dtype`` where the modules do (a product's
    output, a norm's output; statistics, scores and the loss in fp32)."""
    f32, dt = jnp.float32, cfg.dtype
    B, S = ids.shape
    hd = cfg.hidden_size // cfg.num_heads

    def norm(p, x):
        xf = x.astype(f32)
        if cfg.norm == "rmsnorm":
            y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + cfg.norm_eps)
            return (y * p["scale"].astype(f32)).astype(x.dtype)
        mean, mean2 = xf.mean(-1, keepdims=True), jnp.square(xf).mean(-1, keepdims=True)
        var = jnp.maximum(0.0, mean2 - jnp.square(mean))  # flax's LayerNorm: the fast variance
        y = (xf - mean) * (jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"]) + p["bias"]
        return y.astype(jnp.result_type(x, p["scale"], p["bias"]))

    def dense(p, x, spec):
        y = jnp.einsum(spec, x.astype(dt), p["kernel"].astype(dt))
        return y + p["bias"].astype(dt) if "bias" in p else y

    def rotary(x):
        rd = cfg.rotary_dim or hd
        inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rd, 2, dtype=f32) / rd))
        angles = jnp.arange(S, dtype=f32)[:, None] * inv  # [S, rd/2]
        cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
        x1, x2 = x[..., :rd // 2].astype(f32), x[..., rd // 2:rd].astype(f32)
        turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)
        return jnp.concatenate([turned, x[..., rd:]], -1)

    def attention(p, h):
        q, k, v = (dense(p[w], h, "bsh,hnd->bsnd") for w in ("wq", "wk", "wv"))
        q, k = rotary(q), rotary(k)
        groups = cfg.num_heads // cfg.kv_heads
        qg = q.reshape(B, S, cfg.kv_heads, groups, hd).astype(f32) * hd ** -0.5
        scores = jnp.einsum("bqngd,bknd->bngqk", qg, k.astype(f32))
        scores = jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, jnp.finfo(f32).min)
        probs = jax.nn.softmax(scores, -1).astype(v.dtype)
        out = jnp.einsum("bngqk,bknd->bqngd", probs, v).reshape(B, S, cfg.num_heads, hd)
        return dense(p["wo"], out, "bsnd,ndh->bsh")

    def mlp(p, h):
        up = dense(p["w_up"], h, "bsh,hf->bsf")
        if cfg.activation == "silu_glu":
            act = jax.nn.silu(dense(p["w_gate"], h, "bsh,hf->bsf")) * up
        else:
            act = jax.nn.gelu(up, approximate=cfg.activation != "gelu_exact")
        return dense(p["w_down"], act, "bsf,fh->bsh")

    x = params["embed"]["embedding"].astype(dt)[ids]
    for i in range(cfg.num_layers):
        p = jax.tree.map(lambda leaf: leaf[i], params["layers"])
        if cfg.parallel_block:
            x = x + attention(p["attn"], norm(p["attn_norm"], x)) + mlp(p["mlp"], norm(p["mlp_norm"], x))
        else:
            x = x + attention(p["attn"], norm(p["attn_norm"], x))
            x = x + mlp(p["mlp"], norm(p["mlp_norm"], x))
    logits = dense(params["lm_head"], norm(params["final_norm"], x), "bsh,hv->bsv").astype(f32)
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    return -jnp.take_along_axis(logp, ids[:, 1:, None], -1).mean()


def _unwrapped(monkeypatch):
    """``models/transformer.py`` as it was before PR 38: no sub-layer keeps
    its inputs alone, and the erf form of gelu is ``jax.nn.gelu``'s own."""
    monkeypatch.setattr(transformer, "_made_again", lambda what: what)
    monkeypatch.setattr(transformer, "_mlp_activation", transformer.act_fn)


def _case(name, dtype, seed):
    """(config in ``dtype``, token ids, parameters in ``dtype`` with every
    leaf drawn off its initial value: no bias is checked at 0, no norm weight at 1)."""
    cfg = dataclasses.replace(RESIDUAL_CASES[name][0], dtype=jnp.dtype(dtype))
    ids = jnp.asarray(_tokens(2, 32, seed=seed)["input_ids"])
    params = CausalLM(cfg).init({"params": jax.random.PRNGKey(seed)}, {"input_ids": ids}, train=False)["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return cfg, ids, tree.unflatten([(leaf + 0.05 * jax.random.normal(key, leaf.shape)).astype(cfg.dtype)
                                     for leaf, key in zip(leaves, keys)])


def _loss_and_grads(cfg, params, ids):
    """Of the flax model as ``models/transformer.py`` builds it NOW. Not jitted: each operation rounds
    as written, where XLA's fusions on the CPU would keep more digits in one program than in the other."""
    return jax.value_and_grad(lambda p: CausalLM(cfg).apply({"params": p}, {"input_ids": ids}, train=True)[0])(params)


def _limit(reference, dtype, largest):
    """The most a gradient entry may differ, given its leaf's ``largest``
    entry. Against the plain block the reimplementation's own order of sums
    and roundings is in the reading (fp32 up to 1.4e-6 of the largest, bf16 up
    to 0.040: the SAME from the parent's module); against the unwrapped
    modules only what this PR's wraps change is: fp32 up to 6.2e-7; bf16 up
    to three units in the last place of the largest entry where a wrap makes
    again (in one fused program, which keeps more digits) what autodiff kept,
    up to seven where the erf's slope is rounded ONCE and not as autodiff's
    three pieces. The test after this one holds both to the fp32 gradients."""
    if (reference, dtype) == ("unwrapped", "bfloat16"):
        return 16 * 2.0 ** (np.floor(np.log2(largest)) - 7)
    return {("plain", "float32"): 1e-5, ("plain", "bfloat16"): 0.1, ("unwrapped", "float32"): 2e-6}[reference, dtype] * largest


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("reference", ["plain", "unwrapped"])
@pytest.mark.parametrize("case", ["llama", "llama_remat", "pythia", "pythia_remat", "pythia_sequential"])
def test_gradients_are_those_of_a_block_without_a_checkpoint(case, reference, dtype, monkeypatch):
    """Every parameter's gradient, and the loss, against (a) the plain block
    above and (b) this module with its wraps taken out again."""
    cfg, ids, params = _case(case, dtype, seed=7)
    loss, grads = _loss_and_grads(cfg, params, ids)
    if reference == "plain":
        want_loss, want = jax.value_and_grad(_plain_loss)(params, cfg, ids)
        np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6 if dtype == "float32" else 1e-2)
    else:
        _unwrapped(monkeypatch)
        want_loss, want = _loss_and_grads(cfg, params, ids)
        assert float(loss) == float(want_loss)  # the forward is the same instructions
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want)):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        largest = np.abs(ref).max()
        assert largest > 0, jax.tree_util.keystr(path)
        limit = _limit(reference, dtype, largest)
        assert np.abs(got - ref).max() <= limit, (jax.tree_util.keystr(path), np.abs(got - ref).max(), limit)


@pytest.mark.parametrize("case", ["llama", "pythia", "pythia_sequential"])
def test_bf16_gradients_are_no_further_from_the_fp32_gradients(case, monkeypatch):
    """bf16 gradients (norms and sigmoid made again; the erf's slope kept,
    rounded once) against the fp32 gradients at the same parameters: each
    leaf's root-mean-square error is at most 1.5 x that of the unwrapped
    modules (read: 0.83-1.26 x, the noise of one rounding)."""
    cfg, ids, params = _case(case, "bfloat16", seed=7)
    exact = _loss_and_grads(dataclasses.replace(cfg, dtype=jnp.float32),
                            jax.tree.map(lambda a: a.astype(jnp.float32), params), ids)[1]
    kept = _loss_and_grads(cfg, params, ids)[1]
    _unwrapped(monkeypatch)
    autodiff = _loss_and_grads(cfg, params, ids)[1]
    for want, got, ref in zip(*map(jax.tree.leaves, (exact, kept, autodiff))):
        error = [np.sqrt(np.mean((np.asarray(g, np.float32) - np.asarray(want)) ** 2)) for g in (got, ref)]
        assert error[0] <= 1.5 * error[1], error


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["llama", "pythia"])
def test_inference_logits_are_bitwise_those_of_the_unwrapped_modules(case, dtype, monkeypatch):
    """``train=False`` logits with this PR's wraps taken out again: the same bits."""
    cfg, ids, params = _case(case, dtype, seed=9)
    logits = CausalLM(cfg).apply({"params": params}, {"input_ids": ids}, train=False)[1]
    _unwrapped(monkeypatch)
    plain = CausalLM(cfg).apply({"params": params}, {"input_ids": ids}, train=False)[1]
    assert logits.dtype == plain.dtype
    assert np.array_equal(np.asarray(logits, np.float32), np.asarray(plain, np.float32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_erf_gelu_that_keeps_its_slope(dtype):
    """Its value is ``jax.nn.gelu``'s bit for bit, differentiated or not; its
    slope is autodiff's to one unit in the last place."""
    x = (3 * jax.random.normal(jax.random.PRNGKey(0), (4096,))).astype(dtype)
    want, pull = jax.vjp(lambda v: jax.nn.gelu(v, approximate=False), x)
    got, pull_kept = jax.vjp(transformer._gelu_exact, x)
    for value in (got, transformer._gelu_exact(x)):
        assert np.array_equal(np.asarray(value, np.float32), np.asarray(want, np.float32))
    slope, slope_kept = (np.asarray(f(jnp.ones_like(x))[0], np.float32) for f in (pull, pull_kept))
    assert np.abs(slope_kept - slope).max() <= (2.0 ** -23 if dtype == "float32" else 2.0 ** -7) * 1.5
