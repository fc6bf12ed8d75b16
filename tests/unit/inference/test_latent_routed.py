"""A latent-attention routed decoder at a toy size with the published
structure (one dense layer, then routed ones: a sigmoid router with a
correction bias large enough to change picks, a shared expert), against the
benchmark's plain reference ``benchmarks/reference/glm4_moe_lite.py``: the
flax forward, ``InferenceEngineV2.put`` through the latent cache in the
absorbed form, the picks the programs hand out, the router's two dispatch
regimes, the HF mapping. And the pin on what must NOT have moved: a toy of
every shape of cache traces the programs it traced (``test_programs_are_the_parents``)."""

import collections
import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import harness, program
from deepspeed_tpu.checkpoint.hf import config_from_hf, convert_hf_state, latent_moe_hf_state
from deepspeed_tpu.inference import cache, paged
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import CausalLM

TOY = dict(
    model_type="glm4_moe_lite", vocab_size=128, hidden_size=64, intermediate_size=160,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4, max_position_embeddings=256,
    moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    first_k_dense_replace=1, routed_scaling_factor=1.8, norm_topk_prob=True, q_lora_rank=24,
    kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16, rms_norm_eps=1e-5,
    rope_theta=1e6, topk_method="noaux_tc", n_group=1, topk_group=1, rope_scaling=None,
    partial_rotary_factor=1, tie_word_embeddings=False, attention_bias=False, hidden_act="silu",
    num_nextn_predict_layers=1)
LENS = (20, 31, 7)
DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


def toy_params(dtype, seed=0):
    """The flax initialiser's parameters with EVERY leaf perturbed (norm
    scales off one, the correction bias too), as ``toy_moe_in_fp32`` does."""
    cfg = dataclasses.replace(config_from_hf(TOY), dtype=dtype)
    params = CausalLM(cfg).init({"params": jax.random.PRNGKey(seed)},
                                {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return cfg, jax.tree_util.tree_unflatten(
        tree, [(a + 0.05 * jax.random.normal(k, a.shape)).astype(dtype) for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def files():
    return harness.load_reference("glm4_moe_lite"), harness.load_architecture("glm4_moe_lite")


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, TOY["vocab_size"], (3, 40), dtype=np.int32)


def engine_of(cfg, params, dtype_name, **kw):
    conf = dict(dtype=dtype_name, kv_cache_dtype=dtype_name, max_seqs=8, decode_chain=4, kv_block_size=16,
                num_kv_blocks=64, row_bucket=4, chunk_bucket=32, hbm_check="off", max_seq_len=256)
    return InferenceEngineV2(cfg, params, dict(conf, **kw))


def only_the_latent_kernel(monkeypatch):
    """``auto`` as the chip resolves it for the latent attention alone: the
    Pallas kernel (in interpret mode here), everything else XLA's."""
    from deepspeed_tpu.ops import registry

    import deepspeed_tpu.ops.pallas.paged_attention  # noqa: F401  (registers the kernel)

    def dispatch(op, impl="auto"):
        if op == "latent_paged_attention":
            return registry.available_impls(op)["pallas"]
        return registry.dispatch(op, impl)

    monkeypatch.setattr(paged, "dispatch", dispatch)


def put_three_steps(engine, tokens):
    """Prefill, then two tokens through the cache: logits and picks a step."""
    uids, out = [1, 2, 3], []
    for step in range(3):
        fed = [tokens[i, (0 if step == 0 else n + step - 1):n + step] for i, n in enumerate(LENS)]
        out.append(engine.put_with_picks(uids, fed))
    for uid in uids:
        engine.flush(uid)
    return out


def pinned(picks_by_step, shape, routed_layers, k):
    all_picks = np.broadcast_to(np.arange(k, dtype=np.int32), shape + (routed_layers, k)).copy()
    for step, (_, picks) in enumerate(picks_by_step):
        for i, n in enumerate(LENS):
            start = 0 if step == 0 else n + step - 1
            all_picks[i, start:start + len(picks[i])] = picks[i]
    return all_picks


# (a) the flax forward against the reference
@pytest.mark.parametrize("dtype,limit", [("fp32", 1e-5), ("bf16", 0.12)])
def test_flax_forward_against_the_reference(files, tokens, dtype, limit):
    reference, architecture = files
    cfg, params = toy_params(DTYPES[dtype])
    _, logits = CausalLM(cfg).apply({"params": params}, {"input_ids": jnp.asarray(tokens)})
    want = reference.forward(architecture.reference_weights(params), TOY, jnp.asarray(tokens))
    assert program.relative_error(np.asarray(logits, np.float32), want) <= limit


# (b) put: prefill, then two tokens through the latent cache, absorbed
@pytest.mark.parametrize("dtype,impl,limit", [("fp32", "xla", 1e-5), ("fp32", "kernel", 1e-5),
                                              ("bf16", "xla", 0.03), ("bf16", "kernel", 0.03)])
def test_put_through_the_latent_cache_against_the_reference(files, tokens, monkeypatch, dtype, impl, limit):
    reference, architecture = files
    if impl == "kernel":
        only_the_latent_kernel(monkeypatch)
    cfg, params = toy_params(DTYPES[dtype])
    engine = engine_of(cfg, params, dtype)
    assert engine.pool.v is None and engine.pool.k.shape == (3 * 64, 16, 128)  # one slab, 32 + 8 -> 128
    steps = put_three_steps(engine, tokens)
    # at the program's own picks: a flipped pick is not an error of the arithmetic
    picks = pinned(steps, tokens.shape, cfg.routed_layers, cfg.moe_top_k)
    want = np.asarray(reference.forward(architecture.reference_weights(engine.params), TOY,
                                        jnp.asarray(tokens), jnp.asarray(picks)))
    for step, (logits, _) in enumerate(steps):
        rows = np.stack([want[i, n + step - 1] for i, n in enumerate(LENS)])
        assert program.relative_error(logits, rows) <= limit, step


# the kernel alone: a row without pages, a table wider than a row's pages,
# a chunk cut into query tiles, a token and its drafts; and what the prompt
# path's form (a tile and a page-chunk from the shapes) has to get right: a
# context of five chunks under a tile that straddles a chunk's edge, lengths
# that are no multiple of the tile, a row with no live token, and a tile whose
# first live position is a chunk's LAST column (255) or the one before it (254)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)], ids=["fp32", "bf16"])
@pytest.mark.parametrize("C,H,lens,starts", [
    (1, 4, (1, 1, 0, 1), (0, 37, 0, 63)),
    (5, 4, (5, 3, 0, 5), (11, 0, 0, 40)),
    (32, 4, (32, 7, 0, 20), (0, 16, 0, 30)),
    (80, 20, (80, 37, 0, 71), (1000, 300, 0, 771)),
    (80, 32, (80, 37, 0, 71), (1000, 300, 0, 771)),
    (80, 20, (64, 17, 0, 80), (255, 511, 0, 254)),
    (80, 32, (64, 17, 0, 80), (255, 511, 0, 254)),
], ids=["decode", "drafts", "chunk-in-tiles", "five-chunks-20-heads", "five-chunks-32-heads",
        "chunk-edge-20-heads", "chunk-edge-32-heads"])
def test_latent_kernel_against_the_gather(dtype, tol, C, H, lens, starts):
    from deepspeed_tpu.ops.pallas.paged_attention import _latent_form, flash_decode_latent

    N, W, V, bs = 4, 128, 96, 16
    P = 12 if C <= 32 else 72  # 12 columns, rows hold at most 5 pages; or 72, up to 68
    pages = 40 if C <= 32 else N * P
    rng = np.random.default_rng(C)
    pool = jnp.asarray(rng.normal(size=(pages, bs, W)), dtype)
    q = jnp.asarray(rng.normal(size=(N, C, H, W)), dtype)
    tables = jnp.asarray(rng.permutation(pages)[:N * P].reshape(N, P) if N * P <= pages
                         else rng.integers(0, pages, (N, P)), jnp.int32)
    positions = jnp.asarray(np.asarray(starts)[:, None] + np.arange(C)[None, :], jnp.int32)
    new_lens = jnp.asarray(lens, jnp.int32)
    if C == 80:  # the cases are written for this form: tiles of 64 tokens, chunks of 256
        assert _latent_form(C, H, W, V, q.dtype.itemsize, P, bs) == (64, 16)
    args = (q, pool, tables, positions, bs, 0.25, V)
    got = np.asarray(flash_decode_latent(*args, new_lens=new_lens), np.float32)
    if C == 80:
        # against the gather in float32 on the same (rounded) numbers: in bf16 the gather rounds
        # its scores, and of these cases' 10^5 outputs one to three then lie 0.032-0.046 off
        args = (q.astype(jnp.float32), pool.astype(jnp.float32), *args[2:])
    want = np.asarray(paged._xla_latent_paged_attention(*args, new_lens=new_lens), np.float32)
    assert np.isfinite(got).all()
    for n in range(N):  # live tokens only: a dead one attends to nothing in the kernel
        np.testing.assert_allclose(got[n, :lens[n]], want[n, :lens[n]], atol=tol, rtol=tol)
    assert not got[2].any()  # the row without pages writes zeros


@pytest.mark.parametrize("N,C,H,P,form", [(8, 2048, 32, 256, (32, 32)), (64, 256, 20, 128, (32, 16)),
                                          (8, 5, 20, 128, (5, 16)), (64, 1, 32, 256, (1, 16))],
                         ids=["xing-prefill", "glm-prefill", "drafts", "decode"])
def test_the_form_comes_from_the_shapes_and_asks_for_no_vmem_of_its_own(N, C, H, P, form):
    """At both latent cells' shapes: the tile and the chunk ``_latent_form``
    picks (the most that fit the 16 MiB Mosaic scopes by default, a chunk no
    wider than the call's tokens), the grid and the query block that follow,
    and NO ``vmem_limit_bytes``: at 48 MiB the xing prefill's dispatch gather
    lost its source's place in fast memory (0.72 -> 3.55 ms a layer-call)."""
    from deepspeed_tpu.ops.pallas.paged_attention import _latent_form, flash_decode_latent

    assert _latent_form(C, H, 640, 512, 2, P, 16) == form
    s = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda q, pool, bt, pos, n: flash_decode_latent(q, pool, bt, pos, 16, 0.0625, 512, new_lens=n))(
        s((N, C, H, 640), jnp.bfloat16), s((100, 16, 640), jnp.bfloat16), s((N, P), jnp.int32),
        s((N, C), jnp.int32), s((N,), jnp.int32))
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    grid = call.params["grid_mapping"]
    rows = -(-form[0] * H // 16) * 16
    assert grid.grid == (N * -(-C // form[0]),) and grid.num_index_operands == 2
    assert tuple(getattr(d, "block_size", d) for d in grid.block_mappings[0].block_shape) == (1, rows, 640)
    assert call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes is None


@pytest.mark.parametrize("H,rows", [(20, 32), (32, 32)], ids=["20-heads", "32-heads"])
def test_one_token_a_row_lowers_to_the_kernel_it_was(H, rows):
    """The decode path (``C == 1``) is not the prompt path's to change: the
    ``pallas_call`` of a ``(64, 1)`` call at both latent cells' heads has the
    grid, the scalar operands, the blocks, the scratch and the compiler's
    parameters it had before PR 52 chose a prompt's form from its shapes, and
    its body is the jaxpr it was, equation for equation and nested as it was
    (the text as PR 51's tree printed it: what stands inside the walk's loop
    and what outside it is part of that)."""
    from deepspeed_tpu.ops.pallas.paged_attention import flash_decode_latent

    N, W, V, bs, P, pages = 64, 640, 512, 16, 128, 100
    s = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda q, pool, bt, pos, n: flash_decode_latent(q, pool, bt, pos, bs, 0.0625, V, new_lens=n))(
        s((N, 1, H, W), jnp.bfloat16), s((pages, bs, W), jnp.bfloat16), s((N, P), jnp.int32),
        s((N, 1), jnp.int32), s((N,), jnp.int32))
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    grid = call.params["grid_mapping"]
    assert grid.grid == (N,) and grid.num_index_operands == 2  # the table, the contexts
    assert [tuple(getattr(d, "block_size", d) for d in b.block_shape) for b in grid.block_mappings] == [
        (1, rows, W), (1, rows, 1), (pages, bs, W), (1, rows, V)]
    scratch = call.params["jaxpr"].invars[-grid.num_scratch_operands:]
    assert [str(v.aval) for v in scratch] == [
        f"Ref<vmem>{{bfloat16[2,16,{bs},{W}]}}", f"Ref<vmem>{{float32[{rows},{V}]}}",
        f"Ref<vmem>{{float32[{rows},128]}}", f"Ref<vmem>{{float32[{rows},128]}}",
        "Ref<semaphore_mem>{dma_sem[2]}", "Ref<smem>{int32[1]}"]
    assert [(v.aval.shape, str(v.aval.dtype)) for v in call.invars] == [
        ((N, P), "int32"), ((N,), "int32"), ((N, rows, W), "bfloat16"), ((N, rows, 1), "int32"),
        ((pages, bs, W), "bfloat16")]
    mosaic = call.params["compiler_params"]["mosaic_tpu"]
    assert mosaic.dimension_semantics == ("arbitrary",) and mosaic.vmem_limit_bytes is None
    with open(os.path.join(os.path.dirname(__file__), "data", "latent_decode_kernel_at_pr51.txt")) as f:
        assert str(call.params["jaxpr"]) == f.read()


# (c) the picks
def test_picks_are_the_reference_s_own_and_come_out_of_the_same_programs(files, tokens):
    reference, architecture = files
    cfg, params = toy_params(jnp.float32)
    engine = engine_of(cfg, params, "fp32")
    plain = [engine.put([1, 2, 3], [tokens[i, :n] for i, n in enumerate(LENS)])]
    for uid in (1, 2, 3):
        engine.flush(uid)
    compiled = engine.jit_cache_size()
    steps = put_three_steps(engine, tokens)
    assert engine.jit_cache_size() == compiled  # put's own program, chunk bucket and all
    np.testing.assert_array_equal(plain[0], steps[0][0])
    for (_, picks), fed in zip(steps, (LENS, (1, 1, 1), (1, 1, 1))):
        assert [p.shape for p in picks] == [(n, 2, 2) for n in fed] and picks[0].dtype == np.int32
    weights = architecture.reference_weights(engine.params)
    all_picks = pinned(steps, tokens.shape, 2, 2)
    shortfall = np.asarray(reference.route_shortfall(weights, TOY, jnp.asarray(tokens), jnp.asarray(all_picks)))
    fed = np.zeros(tokens.shape, bool)
    for i, n in enumerate(LENS):
        fed[i, :n + 2] = True
    assert shortfall[fed].max() <= 1e-5  # its own top_k(s + b), in fp32
    # the bias changes picks: the reference without it would have gone elsewhere
    no_bias = dict(weights, routed=dict(weights["routed"], router_bias=0 * weights["routed"]["router_bias"]))
    assert np.asarray(reference.route_shortfall(no_bias, TOY, jnp.asarray(tokens),
                                                jnp.asarray(all_picks)))[fed].max() > 0.5


def test_a_router_that_drops_the_bias_fails_the_audit(files, tokens):
    reference, architecture = files
    cfg, params = toy_params(jnp.float32)
    weights = architecture.reference_weights(params)
    params = jax.tree_util.tree_map(lambda a: a, params)
    params["layers"]["moe"]["gate"]["e_bias"] = 0 * params["layers"]["moe"]["gate"]["e_bias"]
    steps = put_three_steps(engine_of(cfg, params, "fp32"), tokens)
    shortfall = np.asarray(reference.route_shortfall(
        weights, TOY, jnp.asarray(tokens), jnp.asarray(pinned(steps, tokens.shape, 2, 2))))
    assert shortfall[0, :LENS[0]].max() > 0.5


def test_generate_with_picks_covers_every_token_fed(files, tokens):
    reference, architecture = files
    cfg, params = toy_params(jnp.float32)
    engine = engine_of(cfg, params, "fp32")
    prompts = [tokens[i, :n] for i, n in enumerate(LENS)]
    plain = engine.generate(prompts, max_new_tokens=6)
    compiled = engine.jit_cache_size()
    outs, picks = engine.generate_with_picks(prompts, max_new_tokens=6)
    assert engine.jit_cache_size() == compiled and engine.picks_log is None
    full, all_picks = tokens.copy(), np.broadcast_to(np.arange(2, dtype=np.int32), tokens.shape + (2, 2)).copy()
    for i, (p, o) in enumerate(zip(prompts, outs)):
        np.testing.assert_array_equal(o, plain[i])
        assert picks[i].shape == (len(p) + len(o) - 1, 2, 2)
        full[i, len(p):len(p) + len(o)] = o
        all_picks[i, :len(picks[i])] = picks[i]
    weights = architecture.reference_weights(engine.params)
    want = np.asarray(reference.forward(weights, TOY, jnp.asarray(full), jnp.asarray(all_picks)))
    shortfall = np.asarray(reference.route_shortfall(weights, TOY, jnp.asarray(full), jnp.asarray(all_picks)))
    for i, (p, o) in enumerate(zip(prompts, outs)):
        assert [int(want[i, len(p) + j - 1].argmax()) for j in range(len(o))] == list(o)
        assert shortfall[i, :len(picks[i])].max() <= 1e-5
    assert 2 <= engine.last_experts_touched <= 6  # 3 rows x 2 picks over 8 experts
    # what the decode product read: the picks of the program's 8 rows, the five pad rows' too
    assert engine.last_experts_touched <= engine.last_experts_read <= 8


def test_generate_with_picks_with_chains_ahead_is_the_serial_drivers(tokens):
    """Three chains, two of them dispatched ahead: the picks of a chain ahead
    are logged with the rows and the positions it was dispatched with, and
    come out as a driver's that runs one chain at a time."""
    from .test_chain_ahead import serial_driver

    cfg, params = toy_params(jnp.float32)
    prompts = [tokens[i, :n] for i, n in enumerate(LENS)]
    engine, serial = engine_of(cfg, params, "fp32"), engine_of(cfg, params, "fp32")
    n_new = 1 + 3 * engine.config.decode_chain
    outs, picks = engine.generate_with_picks(prompts, max_new_tokens=n_new)
    want, want_picks = serial._with_picks(lambda: serial_driver(serial, prompts, n_new), len(prompts))
    assert (engine.chain_steps, engine.chains_ahead, serial.chains_ahead) == (3, 2, 0)
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(outs[i], want[i])
        assert picks[i].shape == (len(p) + n_new - 1, 2, 2)
        np.testing.assert_array_equal(picks[i], want_picks[i])
    assert 2 <= engine.last_experts_touched <= 6


def test_picks_are_for_routed_models_only():
    cfg = config_from_hf(dict(model_type="gpt_neox", vocab_size=64, hidden_size=32, intermediate_size=64,
                              num_hidden_layers=1, num_attention_heads=2, max_position_embeddings=64))
    params = CausalLM(cfg).init({"params": jax.random.PRNGKey(0)},
                                {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    engine = engine_of(cfg, params, "fp32", max_seq_len=64)
    with pytest.raises(ValueError, match="no routed layer"):
        engine.put_with_picks([1], [np.arange(4, dtype=np.int32)])


# (d) absorbed = non-absorbed attention, on one layer
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 5e-2)], ids=["fp32", "bf16"])
def test_absorbed_attention_is_the_plain_one(dtype, tol):
    from deepspeed_tpu.models.transformer import LatentAttention

    cfg, params = toy_params(dtype)
    attn = params["dense_0"]["attn"]
    N, C, bs = 2, 24, 16
    x = jax.random.normal(jax.random.PRNGKey(3), (N, C, cfg.hidden_size), dtype)
    positions = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (N, C))
    plain = LatentAttention(cfg).apply({"params": attn}, x, None, positions, False)
    pool = cache.init_pool(cfg, 8, bs, dtype)
    tables = jnp.asarray([[0, 1, 7, 7], [2, 3, 7, 7]], jnp.int32)
    new_lens = jnp.full((N,), C, jnp.int32)
    put = paged._page_writer(tables, positions, new_lens, bs, pool.k.shape[0])
    absorbed, pk = paged._latent_attention(attn, cfg, x, positions, new_lens, tables, bs, pool.k, put,
                                           jnp.int32(0))
    np.testing.assert_allclose(np.asarray(absorbed, np.float32), np.asarray(plain, np.float32),
                               atol=tol, rtol=tol)
    assert np.asarray(pk[:4], np.float32).any() and not np.asarray(pk[4:], np.float32).any()
    assert not np.asarray(pk[..., cfg.kv_lora_rank + cfg.qk_rope_head_dim:], np.float32).any()  # the lane padding


# (e) the two dispatch regimes of the routed layer agree across T = 2E
@pytest.mark.parametrize("router", ["sigmoid", "softmax"])
def test_dense_and_grouped_dispatch_agree(router):
    from deepspeed_tpu.inference.model import _moe_with_picks

    cfg, params = toy_params(jnp.float32)
    cfg = dataclasses.replace(cfg, moe_router=router)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
    E = cfg.num_experts
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 2 * E, cfg.hidden_size))
    grouped, picks_g = _moe_with_picks(lp, cfg, x)  # T = 2E: grouped
    dense, picks_d = _moe_with_picks(lp, cfg, x[:, :2 * E - 1])  # T = 2E - 1: every expert
    np.testing.assert_allclose(grouped[:, :-1], dense, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(picks_g[:-1], picks_d)
    assert picks_g.shape == (2 * E, cfg.moe_top_k) and picks_g.dtype == jnp.int32


def test_the_router_is_the_published_one():
    from deepspeed_tpu.parallel.moe import route

    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.asarray([-1.0, 0.0, 0.0, 0.5])
    weights, picks = route(logits, 2, kind="sigmoid", bias=bias, renormalize=True, scale=1.8)
    s = jax.nn.sigmoid(logits[0])
    assert sorted(np.asarray(picks[0]).tolist()) == [1, 3]  # by s + b: expert 0 loses its lead
    np.testing.assert_allclose(np.sort(np.asarray(weights[0])),
                               np.sort(1.8 * np.asarray(s[jnp.asarray([1, 3])] / (s[1] + s[3]))), rtol=1e-6)
    weights, picks = route(logits, 2, kind="softmax")
    assert sorted(np.asarray(picks[0]).tolist()) == [0, 1] and abs(float(weights.sum()) - 1) < 1e-6


# (f) the HF mapping
def test_config_from_hf_on_the_published_config():
    config = harness.load_config("glm-4.7-flash")
    cfg = config_from_hf(program.published(config))
    assert (cfg.num_layers, cfg.first_dense_layers, cfg.routed_layers) == (8, 1, 7)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.num_heads) == (768, 512, 192, 64, 256, 20)
    assert (cfg.num_experts, cfg.moe_top_k, cfg.moe_shared_experts, cfg.expert_width,
            cfg.intermediate_size) == (64, 4, 1, 1536, 10240)
    assert (cfg.moe_router, cfg.moe_renormalize, cfg.moe_routed_scale) == ("sigmoid", True, 1.8)
    assert cfg.param_dtype == jnp.bfloat16 and cfg.rope_theta == 1e6 and not cfg.tie_embeddings
    assert cache.latent_pool_width(cfg) == 640
    architecture = harness.load_architecture("glm4_moe_lite")
    assert cfg.num_params() == architecture.total_params(program.published(config)) == 5_166_248_384


def test_hf_names_there_and_back_without_the_mtp_layer():
    cfg, params = toy_params(jnp.float32)
    state = latent_moe_hf_state(jax.tree_util.tree_map(np.asarray, params), cfg)
    assert state["model.layers.1.self_attn.kv_b_proj.weight"].shape == (4 * (12 + 16), 32)
    assert state["model.layers.2.mlp.experts.7.down_proj.weight"].shape == (64, 32)
    assert "model.layers.0.mlp.gate_proj.weight" in state and "model.layers.0.mlp.gate.weight" not in state
    state["model.layers.3.eh_proj.weight"] = np.zeros((4, 4))  # the MTP layer's keys are not read
    state["model.layers.3.self_attn.kv_b_proj.weight"] = np.zeros((4, 4))
    back = convert_hf_state(state, cfg)  # family detected from the keys
    there = dict(jax.tree_util.tree_leaves_with_path(back))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        np.testing.assert_array_equal(np.asarray(leaf), there[path])
    with pytest.raises(ValueError, match="glm4_moe_lite"):
        config_from_hf({"model_type": "no_such_family"})


def test_a_quantized_latent_pool_is_refused():
    cfg, params = toy_params(jnp.bfloat16)
    with pytest.raises(ValueError, match="no quantized form"):
        engine_of(cfg, params, "bf16", kv_cache_dtype="int8")


# (g) what must not have moved: every shape of cache traces the programs it traced
def _census(jaxpr, counts):
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _census(inner, counts)
    return counts


GPT_NEOX = dict(
    model_type="gpt_neox", vocab_size=256, hidden_size=64, intermediate_size=256, num_hidden_layers=2,
    num_attention_heads=4, max_position_embeddings=128, rotary_pct=0.25, rotary_emb_base=10000,
    layer_norm_eps=1e-5, use_parallel_residual=True, hidden_act="gelu", tie_word_embeddings=False)
# a toy of every shape of cache: (the module that holds its published config (None: this one, "": GPT_NEOX),
# the census' file, block size, max_seq_len, a prompt's chunk, with_picks, kv_quant)
PROGRAM_TOYS = {
    "gpt_neox": ("", "gpt_neox_programs_at_pr36.json", 16, 128, 32, False, None),
    "gpt_neox_int8": ("", "gpt_neox_int8_programs_at_pr58.json", 16, 128, 32, False, "int8"),
    "glm4_moe_lite": (None, "glm4_moe_lite_programs_at_pr50.json", 16, 128, 32, True, None),
    "granitemoehybrid": ("test_hybrid", "granitemoehybrid_programs_at_pr58.json", 4, 128, 8, False, None),
    "qwen3_next": ("test_qwen3_next", "qwen3_next_programs_at_pr58.json", 16, 256, 64, True, None),
    "evabyte": ("test_eva", "evabyte_programs_at_pr58.json", 4, 256, 32, False, None),
    "cohere2_moe": ("test_cohere2_moe", "cohere2_moe_programs_at_pr58.json", 8, 192, 16, True, None),
    "glm_moe_dsa": ("test_glm_moe_dsa", "glm_moe_dsa_programs_at_pr58.json", 8, 64, 64, True, None),
    "xing4_0": ("test_xing", "xing4_0_programs_at_pr58.json", 16, 256, 32, True, None),
}
PROGRAMS = [(toy, name) for toy in PROGRAM_TOYS for name in ("step", "chain") + ("spec",) * (toy == "gpt_neox_int8")]


def _programs(cfg, bs=16, max_seq_len=128, chunk=32, kv_quant=None, rows=4, **kw):
    """The jaxprs of ``step``, ``chain`` and (where no picks are asked) the speculative chain for ``cfg`` at
    fixed toy shapes: 32 pages a layer, 8 state slots, a ring a row; the block table as the plan lays it out."""
    params = jax.eval_shape(lambda k: CausalLM(cfg).init(
        {"params": k}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"], jax.random.PRNGKey(0))
    plan = cache.cache_plan(cfg, bs, max_seq_len)
    pools = jax.eval_shape(lambda: plan.init(32, rows * plan.ring_columns, 8, jnp.float32, kv_quant=kv_quant))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    chain_args = (i32(rows, plan.max_pages), jax.ShapeDtypeStruct((rows,), jnp.bool_), i32(rows),
                  jax.ShapeDtypeStruct((2,), jnp.uint32))
    programs = {
        "step": lambda: jax.make_jaxpr(lambda p, pool, t, pos, n, bt: paged.ragged_forward(
            p, cfg, pool, t, pos, n, bt, bs, **kw))(
            params, pools, i32(rows, chunk), i32(rows, chunk), i32(rows), i32(rows, plan.max_pages)),
        "chain": lambda: jax.make_jaxpr(lambda p, pool, t, pos, bt, a, b, r: paged.ragged_decode_chain(
            p, cfg, pool, t, pos, bt, bs, a, b, r, 4, None, **kw))(params, pools, i32(rows), i32(rows), *chain_args),
        "spec": lambda: jax.make_jaxpr(lambda p, pool, t, pos, bt, a, b, r, h, n: paged.ragged_spec_decode_chain(
            p, cfg, pool, t, pos, bt, bs, a, b, r, 4, None, h, n, n_spec=2))(
            params, pools, i32(rows), i32(rows), *chain_args, i32(rows, 64), i32(rows)),
    }
    return pools, programs


def _same_as_recorded(jaxpr, parent):
    assert dict(sorted(_census(jaxpr.jaxpr, collections.Counter()).items())) == parent["primitives"]
    assert len(jaxpr.jaxpr.invars) == parent["inputs"]  # no new operand
    assert [[list(v.aval.shape), str(v.aval.dtype)] for v in jaxpr.jaxpr.outvars] == parent["outputs"]


@pytest.mark.parametrize("toy,name", PROGRAMS, ids=[f"{toy}-{name}" for toy, name in PROGRAMS])
def test_programs_are_the_parents(toy, name):
    """The census of a program's jaxpr (its primitives counted through every
    inner jaxpr, the number of operands, the outputs' shapes) against the one
    recorded by this very code on the commit the file names, for a toy of every
    shape of cache. ``gpt_neox``: ``step`` on PR 32's commit, ``chain`` on
    PR 36's, which hands back the scan's carry (two more outputs, each row's
    next token and position, and a row starts live only with a budget: one
    ``gt``, one ``and``) and takes no new operand. ``glm4_moe_lite``, routed and
    latent, picks and all, on PR 50's: against PR 40's ``step`` (the toy's
    prefill takes the ragged path, whose combine is k gathers and a sum and
    whose sort is inverted by a second sort) and PR 36's ``chain``, both: the
    layer scan closes over the routed experts' three stacked leaves and slices
    each by its own index where it scanned them (three ``dynamic_slice`` with
    their index's clamp, ``lt`` ``add`` ``select_n``, and ``squeeze``; one
    ``iota``, the index), no new operand; ``chain`` alone: ``touched`` is ``[K,
    routed layers, 3]``. The others (a quantized pool and its speculative
    chain, a Mamba-2 hybrid, a Gated DeltaNet hybrid, EVA, a sliding kind, an
    indexed latent, hyper-connections) were recorded on PR 58's commit BEFORE
    PR 59 moved what a model caches into ``inference/cache.py`` and handed
    every program one ``Pools``: a ``None`` field has no leaves, so each takes
    the operands it took, in their order. Re-record one only with a change that
    means to move its program."""
    module, recorded, bs, max_seq_len, chunk, picks, kv_quant = PROGRAM_TOYS[toy]
    published = GPT_NEOX if module == "" else TOY if module is None else importlib.import_module(
        f"tests.unit.inference.{module}").TOY
    pools, programs = _programs(config_from_hf(published), bs, max_seq_len, chunk, kv_quant,
                                **({"with_picks": True} if picks else {}))
    if toy == "gpt_neox":
        assert pools.kv.k.shape == pools.kv.v.shape == (64, 16, 64)  # keys AND values
        assert jax.tree_util.tree_leaves(pools) == jax.tree_util.tree_leaves(pools.kv)  # and nothing beside them
    if toy == "glm4_moe_lite":
        assert pools.kv.v is None  # the latent pool
    with open(os.path.join(os.path.dirname(__file__), "data", recorded)) as f:
        _same_as_recorded(programs[name](), json.load(f)[name])
