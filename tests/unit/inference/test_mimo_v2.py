"""``mimo_v2`` (MiMo-V2.5's language model) at a toy size with the published
STRUCTURE (the pattern ``[0, 1, 1, 1, 1, 0, 1]``: a leading dense global layer,
then one period of four sliding layers, a global one and a sliding one, all
routed; kv heads 1 global and 2 sliding, queries and keys of 24 beside values
of 16, a sink a head in the sliding layers' softmax, the values times 0.707,
rotary over the first third of a head at two bases; a window of 8 on pages of
4: a ring of 3 pages) against the benchmark's plain reference
``benchmarks/reference/mimo_v2.py``: the flax forward, and ``InferenceEngineV2``
through TWO CLASSES OF PAGE OF UNEQUAL GEOMETRY on one block table: ``put`` of
fresh prompts shorter than, equal to and longer than the window, then tokens
through the ring past two wraps of it, logits and not tokens, at the program's
own picks; whole and as a share of an expert-parallel layer, whose parts add
up to the uncut layer.

Tolerances. fp32: 5e-5 relative L2 of logits (read under 2e-6)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import harness, program
from deepspeed_tpu.checkpoint.hf import config_from_hf
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import CausalLM

WINDOW, BLOCK = 8, 4
TOY = dict(
    model_type="mimo_v2", vocab_size=256, hidden_size=32, intermediate_size=48, num_hidden_layers=7,
    num_attention_heads=4, num_key_value_heads=1, head_dim=24, v_head_dim=16, swa_num_attention_heads=4,
    swa_num_key_value_heads=2, swa_head_dim=24, swa_v_head_dim=16, max_position_embeddings=512,
    hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1], moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], sliding_window=WINDOW,
    sliding_window_size=WINDOW, attention_chunk_size=WINDOW, add_swa_attention_sink_bias=True,
    add_full_attention_sink_bias=False, attention_value_scale=0.707, partial_rotary_factor=0.334, rope_theta=1e7,
    swa_rope_theta=1e4, layernorm_epsilon=1e-5, hidden_act="silu", attention_bias=False, tie_word_embeddings=False,
    moe_intermediate_size=16, n_routed_experts=8, n_shared_experts=None, num_experts_per_tok=2, norm_topk_prob=True,
    scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1, routed_scaling_factor=None,
    rope_scaling={"rope_type": "default", "type": "default"}, attention_projection_layout="fused_qkv")
SHARES = {"whole": TOY, "rank1": dict(TOY, n_routed_experts=2, expert_parallel={"size": 4, "rank": 1})}
ENGINE = {"dtype": "fp32", "kv_cache_dtype": "fp32", "kv_block_size": BLOCK, "num_kv_blocks": 96, "chunk_bucket": 16,
          "max_seq_len": 96, "max_seqs": 4, "decode_chain": 4, "row_bucket": 1, "max_ragged_batch_size": 512,
          "hbm_check": "off"}
LENGTHS = (5, 8, 19, 30)  # shorter than, equal to and longer than the window (two of them past a ring's round)
STEPS = 30  # tokens through the ring after the prompt: the ring of 12 slots is written round twice and more


def rel(got, want):
    return program.relative_error(got, want)


def toy_params(published, seed=0):
    """The program's parameter tree (its shapes, traced and not run: compiled or op by op the seven unrolled
    layers' init takes a worker twenty seconds) with every leaf drawn N(0, 0.15): no leaf at a constant."""
    cfg = config_from_hf(published)
    shapes = jax.eval_shape(lambda: CausalLM(cfg).init(
        {"params": jax.random.PRNGKey(0)}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"])
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    rng = np.random.default_rng(seed)
    return cfg, jax.tree_util.tree_unflatten(
        tree, [jnp.asarray(rng.normal(0.0, 0.15, a.shape), jnp.float32) for a in leaves])


def module_logits(cfg, params, tokens):
    """The flax module's logits over ``tokens`` [rows, 64], in one compiled call."""
    return jax.jit(lambda p, ids: CausalLM(cfg).apply({"params": p}, {"input_ids": ids}, train=False)[1])(
        params, jnp.asarray(tokens))


@pytest.fixture(scope="module")
def files():
    return harness.load_reference("mimo_v2"), harness.load_architecture("mimo_v2")


@pytest.fixture(scope="module")
def whole():
    return toy_params(TOY)


@pytest.fixture(scope="module", params=list(SHARES))
def toy(request, whole):
    published = SHARES[request.param]
    return (published,) + (whole if request.param == "whole" else toy_params(published))


def sequences(total=64, seed=0):
    return np.random.default_rng(seed).integers(0, TOY["vocab_size"], (len(LENGTHS), total)).astype(np.int32)


def test_the_config_is_read_from_the_published_keys():
    cfg = config_from_hf(SHARES["rank1"])
    assert cfg.layer_types == ("attention",) + ("sliding_attention",) * 4 + ("attention", "sliding_attention")
    assert cfg.first_dense_layers == 1 and cfg.period == cfg.layer_types[1:] and not cfg.parallel_block
    assert (cfg.kv_heads, cfg.dims_per_head, cfg.v_head_dim, cfg.rope_theta, cfg.rotary_dim) == (1, 24, 16, 1e7, 8)
    own = cfg.sliding
    assert (own.window, own.num_kv_heads, own.head_dim, own.v_head_dim, own.rope_theta, own.sink, own.global_rope) == (
        WINDOW, 2, 24, 16, 1e4, True, True)
    assert (cfg.norm, cfg.norm_unit_offset, cfg.value_multiplier, cfg.tie_embeddings) == ("rmsnorm", True, 0.707, False)
    assert (cfg.moe_router, cfg.moe_router_bias, cfg.moe_renormalize, cfg.moe_shared_experts) == ("sigmoid", True, True, 0)
    assert (cfg.num_experts, cfg.router_experts, cfg.first_expert, cfg.expert_width) == (2, 8, 2, 16)
    assert (cfg.attention_layers, cfg.sliding_layers, cfg.routed_layers) == (2, 5, 6)


def test_the_catalog_row_cut_as_the_cell_has_it_gives_the_issue_s_widths_and_size(files):
    _, architecture = files
    held = harness.load_config("mimo-v2.5")
    published = program.published(held)
    cfg = config_from_hf(published)
    shapes = jax.eval_shape(lambda: CausalLM(cfg).init(
        {"params": jax.random.PRNGKey(0)}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"])
    in_the_tree = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert in_the_tree == architecture.total_params(published) == cfg.num_params() == 3_429_955_392
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.sliding.num_kv_heads) == (4096, 64, 4, 8)
    assert (cfg.dims_per_head, cfg.v_head_dim, cfg.sliding.head_dim, cfg.sliding.v_head_dim) == (192, 128, 192, 128)
    assert (cfg.sliding.window, cfg.intermediate_size, cfg.expert_width, cfg.rotary_dim) == (128, 16384, 2048, 64)
    assert (cfg.rope_theta, cfg.sliding.rope_theta, cfg.value_multiplier) == (1e7, 1e4, 0.707)
    assert (cfg.num_layers, cfg.num_experts, cfg.router_experts, cfg.moe_top_k, cfg.vocab_size) == (7, 16, 256, 8, 19072)
    assert shapes["layers"]["layer_0"]["attn"]["sink"].shape == (1, 64)
    assert shapes["layers"]["layer_4"]["attn"]["wk"]["kernel"].shape == (1, 4096, 4, 192)
    assert shapes["layers"]["layer_0"]["attn"]["wv"]["kernel"].shape == (1, 4096, 8, 128)
    # two classes of page, each at its own geometry: 40 KiB a global page, 80 KiB a ring page, a ring of 9
    from deepspeed_tpu.inference.cache import cache_plan

    plan = cache_plan(cfg, 16, 3104)
    assert [(c.name, c.layers, c.heads, c.width, c.second) for c in plan.classes] == [
        ("kv", 2, 4, 768, 512), ("ring", 5, 8, 1536, 1024)]
    assert [c.page_bytes(16, 2) for c in plan.classes] == [40 * 1024, 80 * 1024]
    assert (plan.ring_columns, plan.max_pages, plan.bytes_per_token(jnp.bfloat16)) == (9, 194 + 9, 5120)


@pytest.mark.parametrize("changed,said", [
    ({"add_full_attention_sink_bias": True}, "add_full_attention_sink_bias"), ({"n_shared_experts": 1}, "n_shared_experts"),
    ({"attention_bias": True}, "attention_bias"), ({"scoring_func": "softmax"}, "scoring_func"),
    ({"n_group": 2}, "grouped expert choice"), ({"moe_layer_freq": [0, 1, 0, 1, 1, 1, 1]}, "moe_layer_freq"),
    ({"hybrid_layer_pattern": [0] * 7}, "no sliding layer"), ({"attention_chunk_size": 64}, "attention_chunk_size"),
    ({"rope_scaling": {"rope_type": "yarn"}}, "rope scaling"), ({"swa_num_attention_heads": 8}, "swa_num_attention_heads"),
], ids=["global_sink", "shared_expert", "bias", "softmax_router", "groups", "dense_in_the_middle", "no_sliding",
        "chunk_size", "rope_scaling", "swa_heads"])
def test_what_the_mapping_does_not_build_is_refused_by_name(changed, said):
    with pytest.raises(ValueError, match="mimo_v2 with.*" + said):
        config_from_hf(dict(TOY, **changed))


def test_what_a_pattern_still_refuses_it_says_and_no_more():
    from deepspeed_tpu.models.transformer import TransformerConfig

    plain = dict(vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2, num_heads=4)
    kinds = dict(layer_types=("sliding_attention", "attention"), sliding={"window": 8})
    TransformerConfig(**plain, **kinds, first_dense_layers=1, num_experts=4, moe_router="sigmoid")  # built now
    with pytest.raises(ValueError, match="no parallel_block and no leading dense layers but in a pattern of the two"):
        TransformerConfig(**plain, **kinds, first_dense_layers=1, num_experts=4, parallel_block=True)  # not both
    with pytest.raises(ValueError, match="layer pattern"):
        TransformerConfig(**plain, layer_types=("mamba", "attention"), first_dense_layers=1, num_experts=4,
                          ssm={"d_state": 8, "n_heads": 4, "head_dim": 16, "n_groups": 1})
    with pytest.raises(ValueError, match="v_head_dim=16 without a latent"):
        TransformerConfig(**plain, v_head_dim=16)


@pytest.mark.parametrize("over,said", [
    ({"prefix_cache": True}, "prefix_cache"), ({"spec_decode": 2}, "spec_decode"),
    ({"kv_cache_dtype": "int8"}, "kv_cache_dtype='int8'"), ({"chunk_bucket": 6}, "chunk_bucket=6")],
    ids=["prefix_cache", "spec_decode", "int8_ring", "part_pages"])
def test_what_does_not_serve_with_this_model_is_refused_by_name(whole, over, said):
    cfg, params = whole
    with pytest.raises(ValueError, match="sliding kind.*" + said):
        InferenceEngineV2(cfg, params, dict(ENGINE, **over))


def test_the_module_is_the_reference(files, toy):
    reference, architecture = files
    published, cfg, params = toy
    tokens = sequences()
    logits = module_logits(cfg, params, tokens)
    want = reference.forward(architecture.reference_weights(params), program.published(published), tokens)
    assert rel(logits, want) < 5e-5
    assert rel(logits[:, :WINDOW], want[:, :WINDOW]) < 5e-5 and rel(logits[:, -1], want[:, -1]) < 5e-5


def test_prefill_then_decode_through_the_ring_past_two_wraps_is_the_reference(files, toy):
    """``put`` of four fresh prompts, then 30 tokens a row one at a time: the
    ring of 3 pages (12 slots under a window of 8) is written round twice and
    more, the global table grows, the two classes' pages are counted apart at
    their own geometries; every step's logits against the reference's full
    forward pinned to the program's own picks, and the picks audited; then the
    serving loop's own tokens (fused prefill, chains of four with the ring's
    roll on the device) against the module's greedy ones."""
    reference, architecture = files
    published, cfg, params = toy
    eng = InferenceEngineV2(cfg, params, dict(ENGINE))
    kv, ring = eng.pools.kv, eng.pools.ring
    assert (kv.k.shape[1:], kv.v.shape[1:], ring.k.shape[1:], ring.v.shape[1:]) == ((4, 24), (4, 16), (4, 48), (4, 32))
    assert (kv.k.shape[0], ring.k.shape[0]) == (2 * 96, 5 * 4 * 3) and eng.plan.ring_columns == 3
    seqs = sequences()
    uids = [10, 11, 12, 13]
    routing = program.routing(architecture, published)
    assert (routing.layers, routing.experts, routing.k) == (6, 8, 2)
    picks = np.broadcast_to(np.arange(routing.k, dtype=np.int32), seqs.shape + (routing.layers, routing.k)).copy()
    got = []
    for step in range(STEPS + 1):
        starts = [0 if step == 0 else n + step - 1 for n in LENGTHS]
        fed = [seqs[i, starts[i]:n + step] for i, n in enumerate(LENGTHS)]
        logits, row_picks = eng.put_with_picks(uids, fed)
        for i, (start, f) in enumerate(zip(starts, fed)):
            picks[i, start:start + len(f)] = row_picks[i]
        got.append(np.asarray(logits))
    weights, plain = architecture.reference_weights(params), program.published(published)
    want = np.asarray(reference.forward(weights, plain, seqs, picks))
    shortfall = np.asarray(reference.route_shortfall(weights, plain, seqs, picks))
    for step in range(STEPS + 1):
        at = np.stack([want[i, n + step - 1] for i, n in enumerate(LENGTHS)])
        assert rel(got[step], at) < 5e-5, step
    fed_to = np.zeros(seqs.shape, bool)
    for i, n in enumerate(LENGTHS):
        fed_to[i, :n + STEPS] = True
    assert float(shortfall[fed_to].max()) < 1e-3  # the program's picks are this router's own
    # two classes in one allocator: a seat's ring stops at its 3 pages, the global pages grow with the context
    stats = eng.stats()
    assert stats["kv_ring_pages_held"] == 4 * 3 and eng.state.allocators[1].free_blocks == 0
    assert stats["kv_global_pages_held"] == sum(-(-(n + STEPS) // BLOCK) for n in LENGTHS)
    assert stats["ring_pages_overwritten"] == 5 * sum(-(-(n + STEPS) // BLOCK) - max(-(-n // BLOCK), 3) for n in LENGTHS)
    assert (stats["kv_ring_bytes"], stats["kv_global_bytes"]) == (12 * 4 * 5 * (48 + 32) * 4, 96 * 4 * 2 * (24 + 16) * 4)
    for uid in uids:
        eng.flush(uid)
    assert eng.state.allocators[1].free_blocks == eng.ring_blocks == 12 and eng.state.free_blocks == eng.num_kv_blocks
    # freed and reused: the serving loop on the same pools
    prompts = [seqs[i, :n] for i, n in enumerate(LENGTHS)]
    outs = eng.generate(prompts, max_new_tokens=20)
    full = seqs.copy()  # (the model is causal: what lies past a row's last generated token moves nothing before it)
    for i, (prompt, out) in enumerate(zip(prompts, outs)):
        full[i, len(prompt):len(prompt) + len(out)] = out
    greedy = np.asarray(jnp.argmax(module_logits(cfg, params, full), -1))
    for i, (prompt, out) in enumerate(zip(prompts, outs)):
        assert len(out) == 20 and np.array_equal(greedy[i, len(prompt) - 1:len(prompt) + len(out) - 1], out)
    assert eng.state.allocators[1].free_blocks == 12 and eng.state.free_blocks == eng.num_kv_blocks


def test_every_table_handed_to_a_step_lies_inside_its_class_s_pool_under_every_layer_s_first_page(whole, monkeypatch):
    """The host's edge of the pool, which is the only one since PR 62: the paged kernel's page copies carry no
    bounds check (``disable_bounds_checks``), so a page index past the pool would read another array's memory
    and say nothing. Every row ``SequenceDescriptor.table_into`` fills for a step program (prefills, single
    tokens, chains: the serving loop over four prompts, 20 tokens each, through the ring's wraps) holds, in the
    global columns, pages of the first allocator and, in the ring's, pages of the second, dead columns 0; and a
    class's array is exactly its layers times its allocator's pages, so the LAST layer's first page plus the
    largest entry is still a row of it."""
    from deepspeed_tpu.inference import ragged

    cfg, params = whole
    eng = InferenceEngineV2(cfg, params, dict(ENGINE))
    handed = []
    table_into = ragged.SequenceDescriptor.table_into

    def recorded(self, row):
        table_into(self, row)
        handed.append(row.copy())

    monkeypatch.setattr(ragged.SequenceDescriptor, "table_into", recorded)
    seqs = sequences()
    eng.generate([seqs[i, :n] for i, n in enumerate(LENGTHS)], max_new_tokens=20)
    layout, (plain, ring) = eng.state.layout, eng.state.allocators
    first = layout.summary_cols
    assert len(handed) > 20 and all(row.shape == (first + layout.window_pages,) for row in handed)
    tables = np.stack(handed)
    assert tables.min() >= 0 and tables[:, :first].max() < plain.num_blocks and tables[:, first:].max() < ring.num_blocks
    assert tables[:, :first].max() > 0 and tables[:, first:].max() > 0  # (pages were handed in both classes)
    kinds = cfg.layer_types
    for pool, allocator, layers in ((eng.pools.kv, plain, kinds.count("attention")),
                                    (eng.pools.ring, ring, len(kinds) - kinds.count("attention"))):
        assert pool.k.shape[0] == pool.v.shape[0] == layers * allocator.num_blocks
        assert (layers - 1) * allocator.num_blocks + allocator.num_blocks - 1 < pool.k.shape[0]


def test_the_four_ranks_routed_parts_add_up_to_the_uncut_layer_and_a_row_with_no_held_pick_gets_zeros(files):
    """Over all four ranks of a four-way share of one routed layer (8 experts,
    2 held a chip, 2 a token, NO shared expert): the ranks' routed terms add up
    to the UNCUT reference's routed layer with nothing counted twice; the picks
    every rank hands out are the uncut router's; and a row none of whose picks
    a rank holds gets exact zeros from it."""
    from deepspeed_tpu.inference.model import _moe_with_picks

    reference, architecture = files
    size, held = 4, 2
    cfg, params = toy_params(TOY, seed=3)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["layer_4"])
    w = {k: a[0] for k, a in architecture.reference_weights(params)["period"][4].items()}
    h = jax.random.normal(jax.random.PRNGKey(5), (48, TOY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.routed(h, w, tuple(w[k] for k in reference.EXPERT_LEAVES), TOY, None)
        select = jax.nn.sigmoid(h @ w["router"]) + w["router_bias"]
        uncut_picks = np.sort(np.asarray(jax.lax.top_k(select, 2)[1]), axis=-1)
    total = np.zeros_like(np.asarray(uncut))
    bare = 0
    for rank in range(size):
        rank_cfg = config_from_hf(dict(TOY, n_routed_experts=held, expert_parallel={"size": size, "rank": rank}))
        assert (rank_cfg.first_expert, rank_cfg.router_experts) == (rank * held, size * held)
        moe = lp["moe"]
        mine = dict(moe, experts={n: a[rank * held:(rank + 1) * held] for n, a in moe["experts"].items()})
        part, picks = _moe_with_picks(mine, rank_cfg, h[None])
        assert np.array_equal(np.sort(np.asarray(picks), axis=-1), uncut_picks)
        none_held = ~((uncut_picks // held) == rank).any(-1)
        assert none_held.any() and not np.asarray(part[0])[none_held].any()  # zeros, and the residual alone goes on
        bare += int(none_held.sum())
        total += np.asarray(part[0])
    assert rel(total, uncut) < 1e-5 and bare > 48


@pytest.mark.parametrize("kernel", ["dense", "flash", "paged_xla", "paged_kernel"])
def test_a_sink_of_minus_infinity_is_no_sink_a_large_one_drives_the_output_to_zero_and_the_edge_is_held_to_the_key(
        kernel):
    """Every implementation of the two kinds' attention (the dense fallback, the
    flash forward in interpret mode, the paged fallback and the paged kernel
    over a rolled ring), at keys of 24 beside values of 16: a sink of -inf is
    no sink, a sink of +40 leaves nothing of the output, a sink changes the
    denominator alone; and the band's edge and the ring's first live slot are
    held to the key: the key ``window`` positions back moves nothing, the one
    after it does."""
    from deepspeed_tpu.inference.paged import _xla_paged_attention
    from deepspeed_tpu.ops.attention import _xla_causal_attention, first_live
    from deepspeed_tpu.ops.pallas.flash_attention import flash_causal_attention
    from deepspeed_tpu.ops.pallas.paged_attention import flash_decode_paged

    H, Hkv, D, Dv, S, W, bs = 4, 2, 24, 16, 36, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(ks[0], (1, S, H, D)), jax.random.normal(ks[1], (1, S, Hkv, D)),
               jax.random.normal(ks[2], (1, S, Hkv, Dv)))
    sink = jax.random.normal(ks[3], (H,))
    t = S - 5  # the query the paged forms answer for (its ring's newest page has slots past it, which it must not see)

    def attend(k, v, sink):
        if kernel in ("dense", "flash"):
            fn = _xla_causal_attention if kernel == "dense" else functools.partial(flash_causal_attention, block_q=8,
                                                                                  block_k=8)
            return fn(q, k, v, window=W, **({} if sink is None else {"sink": sink}))[0, t]
        # the ring as the engine rolls it: 3 pages of 4 slots, the oldest live block first
        R = W // bs + 1
        low = int(first_live(jnp.asarray(t), W))
        oldest = low // bs
        pool_k = k[0, oldest * bs:(oldest + R) * bs].reshape(R, bs, Hkv * D)
        pool_v = v[0, oldest * bs:(oldest + R) * bs].reshape(R, bs, Hkv * Dv)
        fn = _xla_paged_attention if kernel == "paged_xla" else flash_decode_paged
        out = fn(q[:, t:t + 1], pool_k, pool_v, jnp.arange(R, dtype=jnp.int32)[None], jnp.asarray([[t - oldest * bs]]),
                 bs, new_lens=jnp.ones((1,), jnp.int32), first_live=jnp.asarray([[low - oldest * bs]]),
                 **({} if sink is None else {"sink": sink}))
        return out[0, 0]

    bare = attend(k, v, None)
    assert bare.shape == (H, Dv)
    assert rel(attend(k, v, jnp.full((H,), -jnp.inf)), bare) < 1e-6
    assert float(jnp.abs(attend(k, v, jnp.full((H,), 40.0))).max()) < 1e-12
    sunk = attend(k, v, sink)
    shrink = sunk / bare  # one factor a head: the sink joins the denominator and nothing else
    assert float(jnp.abs(shrink - shrink[:, :1]).max()) < 1e-5 and bool((shrink[:, 0] < 1).all())
    # the dense statement of the same thing, for every kernel
    a = jnp.einsum("hd,shd->hs", q[0, t].reshape(Hkv, H // Hkv, D).reshape(H, D),
                   jnp.repeat(k[0], H // Hkv, axis=1)) * D ** -0.5
    seen = (jnp.arange(S) <= t) & (t - jnp.arange(S) < W)
    e = jnp.where(seen[None], jnp.exp(a - a.max()), 0.0)
    want = jnp.einsum("hs,shd->hd", e / (e.sum(-1, keepdims=True) + jnp.exp(sink - a.max())[:, None]),
                      jnp.repeat(v[0], H // Hkv, axis=1))
    assert rel(sunk, want) < 1e-5
    moved = lambda at: k.at[0, at].add(3.0)  # noqa: E731
    assert rel(attend(moved(t - W), v, sink), sunk) < 1e-6  # the key the window has left behind
    assert rel(attend(moved(t - W + 1), v, sink), sunk) > 1e-3  # the window's oldest key


CONTROLS = ("no_sink", "no_value_scale", "rope_all", "bases_swapped", "kv_groups", "e4m3_ring", "e4m3_global",
            "dead_slot", "bias_weighs", "ranks_2_to_k1")


@pytest.mark.parametrize("control", CONTROLS)
def test_the_plants_of_tools_mimo_controls_are_seen_in_float32(files, whole, control, monkeypatch):
    """Each fault ``tools/mimo_controls.py`` plants, at the toy in float32,
    through ``put`` of two prompts past the window and tokens through the ring
    past a wrap: every one moves the logits by hundreds of times the sound
    reading (under 5e-6: the test above), and the router that could not have
    made its picks reads a shortfall over a sigma."""
    import importlib.util
    import os

    from deepspeed_tpu.checkpoint import hf
    from deepspeed_tpu.inference import paged
    from deepspeed_tpu.models import transformer
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.parallel import moe

    reference, architecture = files
    root = os.path.dirname(harness.BENCH_DIR)
    spec = importlib.util.spec_from_file_location("mimo_controls", os.path.join(root, "tools", "mimo_controls.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    _, params = whole  # the published model's weights, made before any plant
    weights, seqs = architecture.reference_weights(params), sequences()
    lengths, steps = (19, 30), 7
    for module, name in ((hf, "config_from_hf"), (harness, "load_workload"), (attention, "first_live"), (paged, "_qkv"),
                         (paged, "paged_attention"), (moe, "route"), (transformer, "sliding_kind")):
        monkeypatch.setattr(module, name, getattr(module, name))
    monkeypatch.setitem(paged._ATTENTION, "windowed", paged._ATTENTION["windowed"])
    if control == "dead_slot":  # (the tool's plant learns the page from the cell's file: here the toy's 4 slots)
        live = attention.first_live
        attention.first_live = lambda positions, window: live(positions, window) // BLOCK * BLOCK
    else:
        tool.PLANTS[control]()
    eng = InferenceEngineV2(hf.config_from_hf(TOY), params, dict(ENGINE))
    picks = np.zeros((2, 64, 6, 2), np.int32) + np.arange(2, dtype=np.int32)
    got = []
    for step in range(steps + 1):
        starts = [0 if step == 0 else n + step - 1 for n in lengths]
        fed = [seqs[2 + i, starts[i]:n + step] for i, n in enumerate(lengths)]
        logits, row_picks = eng.put_with_picks([1, 2], fed)
        for i, (start, f) in enumerate(zip(starts, fed)):
            picks[i, start:start + len(f)] = row_picks[i]
        got.append(np.asarray(logits))
    want = np.asarray(reference.forward(weights, TOY, seqs[2:], picks))
    short = np.asarray(reference.route_shortfall(weights, TOY, seqs[2:], picks))
    err = max(rel(got[s], np.stack([want[i, n + s - 1] for i, n in enumerate(lengths)])) for s in range(steps + 1))
    short = max(float(short[i, :n + steps].max()) for i, n in enumerate(lengths))
    assert (short > 1.0) if control == "ranks_2_to_k1" else (err > 5e-4), (control, err, short)
