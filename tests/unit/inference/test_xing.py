"""``xing4_0`` at a toy size with the published structure (two dense layers,
then routed ones; four residual streams mixed by hyper-connections around
latent attention with YaRN rotary) against the benchmark's plain reference
``benchmarks/reference/xing4_0.py``: the flax forward, its loss and gradients,
``InferenceEngineV2.put`` and ``generate`` through the latent cache, the
Sinkhorn rounds alone, YaRN's numbers by hand, the HF mapping. And the pin on
what must NOT have moved: ``hc_mult`` 0 builds the block it built before.

Tolerances. fp32: 2e-5 relative L2 of logits (a round-off of 6e-8 a sum
through 4 layers of 20 Sinkhorn rounds each; read 1.6e-6 to 1.8e-6 over three
seeds), 2e-4 of a leaf's largest entry for gradients (the backward goes
through the same rounds twice). bf16 through the cache: 0.08 (read 0.019-0.037
over three seeds with ``a_res`` drawn about 4: the mix is made from bf16
streams, and ``a_res`` multiplies that rounding before the ``exp``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import harness, program
from deepspeed_tpu.checkpoint.hf import config_from_hf
from deepspeed_tpu.inference import cache, paged
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig, yarn_frequencies
from deepspeed_tpu.ops import mhc

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}
TOY = dict(
    model_type="xing4_0", vocab_size=128, hidden_size=64, intermediate_size=160,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4, max_position_embeddings=256,
    moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    first_k_dense_replace=2, routed_scaling_factor=2, norm_topk_prob=True, q_lora_rank=24,
    kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16, rms_norm_eps=1e-6,
    rope_theta=10000, rope_scaling=YARN, topk_method="noaux_tc", n_group=1, topk_group=1,
    tie_word_embeddings=False, attention_bias=False, hidden_act="silu", num_nextn_predict_layers=1,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
LENS = (20, 31, 7)
DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


def toy_params(dtype, seed=0):
    """The flax initialiser's parameters with EVERY leaf perturbed (norm
    scales off one, the correction bias too)."""
    cfg = dataclasses.replace(config_from_hf(TOY), dtype=dtype)
    params = CausalLM(cfg).init({"params": jax.random.PRNGKey(seed)},
                                {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return cfg, jax.tree_util.tree_unflatten(
        tree, [(a + 0.05 * jax.random.normal(k, a.shape)).astype(dtype) for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def files():
    return harness.load_reference("xing4_0"), harness.load_architecture("xing4_0")


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, TOY["vocab_size"], (3, 40), dtype=np.int32)


def engine_of(cfg, params, dtype_name, **kw):
    conf = dict(dtype=dtype_name, kv_cache_dtype=dtype_name, max_seqs=8, decode_chain=4, kv_block_size=16,
                num_kv_blocks=64, row_bucket=4, chunk_bucket=32, hbm_check="off", max_seq_len=256)
    return InferenceEngineV2(cfg, params, dict(conf, **kw))


def pinned(picks_by_step, shape, routed_layers, k):
    all_picks = np.broadcast_to(np.arange(k, dtype=np.int32), shape + (routed_layers, k)).copy()
    for step, (_, picks) in enumerate(picks_by_step):
        for i, n in enumerate(LENS):
            start = 0 if step == 0 else n + step - 1
            all_picks[i, start:start + len(picks[i])] = picks[i]
    return all_picks


# (a) the flax module against the reference: logits, then loss and gradients of one step
def test_flax_forward_against_the_reference(files, tokens):
    reference, architecture = files
    cfg, params = toy_params(jnp.float32)
    assert set(params) == {"embed", "dense_0", "dense_1", "layers", "final_norm", "lm_head"}
    assert params["dense_1"]["mlp_hc"]["phi"].shape == (4 * 64, 24)
    assert params["layers"]["attn_hc"]["alpha"].shape == (2, 3)
    _, logits = CausalLM(cfg).apply({"params": params}, {"input_ids": jnp.asarray(tokens)})
    want = reference.forward(architecture.reference_weights(params), TOY, jnp.asarray(tokens))
    assert program.relative_error(np.asarray(logits, np.float32), want) <= 2e-5


def test_loss_and_gradients_of_one_step_against_the_reference(files, tokens):
    reference, architecture = files
    cfg, params = toy_params(jnp.float32)
    ids = jnp.asarray(tokens[:2, :16])

    def ours(p):
        return CausalLM(cfg).apply({"params": p}, {"input_ids": ids}, train=True)[0]

    def theirs(p):
        # the reference hands its logits to the host's memory: back, for the loss
        logits = jax.device_put(reference.forward(architecture.reference_weights(p), TOY, ids),
                                jax.memory.Space.Device)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1).mean()

    (loss, grads), (want, want_grads) = jax.value_and_grad(ours)(params), jax.value_and_grad(theirs)(params)
    assert abs(float(loss) - float(want)) <= 2e-5 * float(want)
    flat, flat_want = jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(want_grads)
    assert len(flat) == len(flat_want)
    for (path, got), wanted in zip(flat, flat_want):
        name, scale = jax.tree_util.keystr(path), float(jnp.abs(wanted).max())
        # every leaf, the hyper-connections' too, moves the loss; the correction bias picks and never weighs
        assert scale > 0 or "e_bias" in name, name
        assert float(jnp.abs(got - wanted).max()) <= 2e-4 * scale, name


# (b) put and generate through the latent cache: prefill, then tokens fed one at a time
@pytest.mark.parametrize("dtype,limit", [("fp32", 2e-5), ("bf16", 0.08)])
def test_put_through_the_cache_against_the_reference(files, tokens, dtype, limit):
    reference, architecture = files
    cfg, params = toy_params(DTYPES[dtype])
    engine = engine_of(cfg, params, dtype)
    uids, steps = [1, 2, 3], []
    for step in range(3):
        fed = [tokens[i, (0 if step == 0 else n + step - 1):n + step] for i, n in enumerate(LENS)]
        steps.append(engine.put_with_picks(uids, fed))
    picks = pinned(steps, tokens.shape, cfg.routed_layers, cfg.moe_top_k)
    weights = architecture.reference_weights(engine.params)
    want = np.asarray(reference.forward(weights, TOY, jnp.asarray(tokens), jnp.asarray(picks)))
    for step, (logits, _) in enumerate(steps):
        rows = np.stack([want[i, n + step - 1] for i, n in enumerate(LENS)])
        assert program.relative_error(logits, rows) <= limit, step
    if dtype == "fp32":
        shortfall = np.asarray(reference.route_shortfall(weights, TOY, jnp.asarray(tokens), jnp.asarray(picks)))
        assert max(shortfall[i, :n + 2].max() for i, n in enumerate(LENS)) <= 1e-5


def test_generate_through_prefill_and_chains_picks_the_reference_s_tokens(files, tokens):
    reference, architecture = files
    cfg, params = toy_params(jnp.float32)
    engine = engine_of(cfg, params, "fp32")
    prompts = [tokens[i, :n] for i, n in enumerate(LENS)]
    outs, picks = engine.generate_with_picks(prompts, max_new_tokens=6)  # a prefill, a chain of 4, one of 1
    full = tokens.copy()
    all_picks = np.broadcast_to(np.arange(2, dtype=np.int32), tokens.shape + (2, 2)).copy()
    for i, (p, o) in enumerate(zip(prompts, outs)):
        full[i, len(p):len(p) + len(o)] = o
        all_picks[i, :len(picks[i])] = picks[i]
    want = np.asarray(reference.forward(architecture.reference_weights(engine.params), TOY,
                                        jnp.asarray(full), jnp.asarray(all_picks)))
    for i, (p, o) in enumerate(zip(prompts, outs)):
        assert [int(want[i, len(p) + j - 1].argmax()) for j in range(len(o))] == list(o)


def test_the_v1_engine_and_tensor_parallel_serving_say_why_not():
    from deepspeed_tpu.inference.model import init_cache

    cfg, params = toy_params(jnp.float32)
    with pytest.raises(NotImplementedError, match="InferenceEngineV2"):
        init_cache(cfg, 2, 64)
    with pytest.raises(ValueError, match="hyper-connections .* with tp=2"):
        engine_of(cfg, params, "fp32", tp_size=2)
    with pytest.raises(ValueError, match="at least two streams"):
        TransformerConfig(hc_mult=4, parallel_block=True)


# (c) Sinkhorn alone
def test_sinkhorn_columns_sum_to_one_and_rows_nearly():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(4.0 * rng.normal(size=(4, 4, 3, 50)) + rng.normal(size=(4, 4, 1, 1)), jnp.float32)
    m = mhc.sinkhorn(jnp.exp(logits), 20, 1e-6)
    assert float(jnp.abs(m.sum(0) - 1).max()) <= 2e-6  # columns, normalised last: s / (s + eps)
    # rows: what 20 rounds leave at logits of standard deviation 4 (read 0.051 at the worst of 150
    # matrices, 0.003 at the median); two rounds leave 0.3
    assert float(jnp.abs(m.sum(1) - 1).max()) <= 0.1 and float(jnp.median(jnp.abs(m.sum(1) - 1))) <= 0.01
    assert float(jnp.abs(mhc.sinkhorn(jnp.exp(logits), 2, 1e-6).sum(1) - 1).max()) > 0.1
    assert float(m.min()) >= 0


def test_sinkhorn_is_finite_at_the_clamp_s_ends():
    ends = jnp.asarray([[-30.0, 30.0, -30.0, 30.0], [30.0, 30.0, -30.0, -30.0],
                        [-30.0, -30.0, -30.0, -30.0], [30.0, 30.0, 30.0, 30.0]])[:, :, None]
    m = mhc.sinkhorn(jnp.exp(ends), 20, 1e-6)
    assert bool(jnp.isfinite(m).all()) and float(jnp.abs(m.sum(0) - 1).max()) <= 1e-5
    # a logit outside the clamp is the one at its end: exp(31) and exp(30) mix alike
    cfg, params = toy_params(jnp.float32)
    hp = params["dense_0"]["attn_hc"]
    x = mhc.spread(jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.hidden_size)), 4)
    kw = dict(norm_eps=1e-6, iters=20, eps=1e-6)
    wide = mhc.mix(x, hp["phi"], hp["b"], hp["alpha"].at[2].set(400.0), clamp=(-30.0, 30.0), **kw)
    assert bool(jnp.isfinite(wide.res).all()) and float(wide.res.max()) <= 1 + 1e-5


def test_the_mix_reads_and_writes_as_the_equations_say():
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.normal(size=(4, 3, 8)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    mixed = mhc.Mix(jnp.asarray(rng.uniform(size=(4, 3)), jnp.float32),
                    jnp.asarray(rng.uniform(size=(4, 3)), jnp.float32),
                    jnp.asarray(rng.uniform(size=(4, 4, 3)), jnp.float32))
    np.testing.assert_allclose(mhc.read(X, mixed), jnp.einsum("it,itc->tc", mixed.pre, X), rtol=1e-6)
    np.testing.assert_allclose(mhc.write(X, y, mixed),
                               jnp.einsum("ijt,jtc->itc", mixed.res, X) + mixed.post[..., None] * y[None],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(mhc.collapse(mhc.spread(y, 4)), 4 * y)


# (d) YaRN by hand, for the published keys: d 64, theta 10,000, factor 64 over 4,096
def test_yarn_frequencies_and_scale_by_hand():
    inv_freq, softmax = yarn_frequencies(64, 10000.0, tuple(sorted(YARN.items())))
    # corr(32) = 64 ln(4096 / (64 pi)) / (2 ln 10000) = 10.47, corr(1) = 22.51: low 10, high 23
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv_freq[:11], plain[:11], rtol=1e-6)          # ramp 0: as they were
    np.testing.assert_allclose(inv_freq[23:], plain[23:] / 64, rtol=1e-6)      # ramp 1: 64 times slower
    np.testing.assert_allclose(inv_freq[16], plain[16] * ((6 / 13) / 64 + 7 / 13), rtol=1e-6)  # ramp 6/13
    with pytest.raises(ValueError, match="mscale 0.5 != mscale_all_dim 1"):  # cos and sin would be scaled
        yarn_frequencies(64, 10000.0, tuple(sorted(dict(YARN, mscale=0.5).items())))
    assert abs(softmax - (0.1 * np.log(64) + 1) ** 2) < 1e-12 and abs(softmax - 2.00474) < 1e-5
    cfg = config_from_hf(dict(TOY, qk_nope_head_dim=128, qk_rope_head_dim=64))
    assert abs(cfg.latent_rotary.softmax_scale - 192 ** -0.5 * 2.00474) < 1e-7
    plain_cfg = config_from_hf(dict(TOY, rope_scaling=None))
    assert plain_cfg.latent_rotary == (None, 20 ** -0.5)
    with pytest.raises(ValueError, match="only type 'yarn' is taken"):
        config_from_hf(dict(TOY, rope_scaling={"type": "linear", "factor": 2}))
    with pytest.raises(ValueError, match="type 'yarn' is taken"):
        config_from_hf(dict(TOY, model_type="glm4_moe_lite", rope_scaling=YARN))


def test_yarn_changes_the_attention_and_absorbed_is_plain():
    from deepspeed_tpu.models.transformer import LatentAttention

    cfg, params = toy_params(jnp.float32)
    attn = params["dense_0"]["attn"]
    N, C, bs = 2, 24, 16
    x = jax.random.normal(jax.random.PRNGKey(3), (N, C, cfg.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (N, C)) + 100
    plain = LatentAttention(cfg).apply({"params": attn}, x, None, positions, False)
    unscaled = LatentAttention(dataclasses.replace(cfg, rope_scaling=None)).apply(
        {"params": attn}, x, None, positions, False)
    assert float(jnp.abs(plain - unscaled).max()) > 1e-2
    pool = cache.init_pool(cfg, 16, bs, jnp.float32)
    tables = jnp.asarray([[0, 1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13, 14, 15]], jnp.int32)
    new_lens = jnp.full((N,), C, jnp.int32)
    put = paged._page_writer(tables, positions - 100, new_lens, bs, pool.k.shape[0])
    # the same scores from position 0: rotary is relative, and the paged path starts a chunk there
    plain0 = LatentAttention(cfg).apply({"params": attn}, x, None, positions - 100, False)
    absorbed, _ = paged._latent_attention(attn, cfg, x, positions - 100, new_lens, tables, bs, pool.k, put,
                                          jnp.int32(0))
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(plain0), atol=2e-5, rtol=2e-5)


# (e) hc_mult 0 is the block it was
def test_without_hyper_connections_the_block_is_the_one_it_was():
    """The same toy with and without the new fields' defaults spelled out
    gives one jaxpr, and that jaxpr has no stream axis, no ``exp`` of a mix and
    the two residual adds a layer (the census of every other toy is pinned by
    ``test_latent_routed.py -k parents`` and ``test_sublayer_names.py``)."""
    base = dict(TOY, model_type="glm4_moe_lite", rope_scaling=None)
    for key in ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max"):
        del base[key]
    cfg = config_from_hf(base)
    assert cfg.hc_mult == 0 and cfg.rope_scaling is None and cfg.hc_params == 0
    ids = jnp.zeros((1, 8), jnp.int32)
    params = CausalLM(cfg).init({"params": jax.random.PRNGKey(0)}, {"input_ids": ids}, train=False)["params"]
    assert "attn_hc" not in params["dense_0"] and "mlp_hc" not in params["layers"]
    text = str(jax.make_jaxpr(lambda p: CausalLM(cfg).apply({"params": p}, {"input_ids": ids}))(params))
    with_hc = config_from_hf(dict(base, model_type="xing4_0", hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
                                  mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30))
    hc_params = CausalLM(with_hc).init({"params": jax.random.PRNGKey(0)}, {"input_ids": ids}, train=False)["params"]
    hc_text = str(jax.make_jaxpr(lambda p: CausalLM(with_hc).apply({"params": p}, {"input_ids": ids}))(hc_params))
    assert "f32[4,1,8,64]" in hc_text and "f32[4,1,8,64]" not in text
    assert hc_text.count(" exp ") > text.count(" exp ")
    assert cfg.num_params() == sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert with_hc.num_params() == sum(a.size for a in jax.tree_util.tree_leaves(hc_params))


# (f) the HF mapping on the catalog's config
def test_config_from_hf_on_the_published_config():
    config = harness.load_config("xing4.0-29b-a4b")
    cfg = config_from_hf(program.published(config))
    assert (cfg.num_layers, cfg.first_dense_layers, cfg.routed_layers) == (7, 2, 5)
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (3584, 32, 768, 512, 128, 64, 128)
    assert (cfg.num_experts, cfg.moe_top_k, cfg.moe_shared_experts, cfg.expert_width,
            cfg.intermediate_size, cfg.vocab_size) == (64, 4, 1, 1024, 9216, 131072)
    assert (cfg.moe_router, cfg.moe_renormalize, cfg.moe_routed_scale) == ("sigmoid", True, 2.0)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert dict(cfg.rope_scaling)["factor"] == 64 and cfg.rope_theta == 10000.0 and cfg.rope_interleaved
    assert cfg.param_dtype == jnp.bfloat16 and cfg.norm_eps == 1e-6 and not cfg.tie_embeddings
    assert cache.latent_pool_width(cfg) == 640 and cfg.hc_params == 344_091
    architecture = harness.load_architecture("xing4_0")
    assert cfg.num_params() == architecture.total_params(program.published(config)) == 4_920_866_746
    hash(cfg)  # a jit static argument
