"""``glm_moe_dsa`` (GLM-5) at a toy size with the published structure (latent
attention whose every layer has a learned indexer that keeps ``index_topk``
cached tokens a query, a leading dense layer before routed ones under a sigmoid
router with a correction bias, a shared expert) against the benchmark's plain
reference ``benchmarks/reference/glm_moe_dsa.py``: the flax forward, and
``InferenceEngineV2`` through the latent pool and the INDEX pool on one block
table (``put``, chains), logits and not tokens, at the program's own picks;
whole and as a share of an expert-parallel layer, whose parts add up to the
uncut layer. ``index_topk`` (16) is UNDER every context here but the ones that
say otherwise, so a dense walk fails each comparison.

Tolerances. fp32: 5e-5 relative L2 of logits (read 2e-6 on three layers)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import harness, program
from deepspeed_tpu.checkpoint.hf import (config_from_hf, convert_hf_state, detect_family, latent_moe_hf_state)
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import CausalLM

TOY = dict(
    model_type="glm_moe_dsa", vocab_size=512, hidden_size=64, intermediate_size=128, num_hidden_layers=3,
    num_attention_heads=4, max_position_embeddings=512, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, first_k_dense_replace=1, n_routed_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, n_shared_experts=1, routed_scaling_factor=2.5, norm_topk_prob=True, n_group=1,
    topk_group=1, rms_norm_eps=1e-5, index_n_heads=4, index_head_dim=16, index_topk=16, indexer_rope_interleave=True,
    rope_interleave=True, rope_parameters={"rope_theta": 1000000, "rope_type": "default"}, scoring_func="sigmoid",
    tie_word_embeddings=False, hidden_act="silu")
SHARES = {"whole": TOY, "rank1": dict(TOY, n_routed_experts=4, expert_parallel={"size": 2, "rank": 1})}
ENGINE = {"dtype": "fp32", "kv_cache_dtype": "fp32", "kv_block_size": 8, "num_kv_blocks": 64, "chunk_bucket": 64,
          "max_seq_len": 64, "max_seqs": 4, "decode_chain": 4, "hbm_check": "off"}
TOPK, LAYERS = TOY["index_topk"], TOY["num_hidden_layers"]


def rel(got, want):
    return program.relative_error(got, want)


def toy_params(published, seed=0):
    cfg = config_from_hf(published)
    params = CausalLM(cfg).init({"params": jax.random.PRNGKey(seed)},
                                {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return cfg, jax.tree_util.tree_unflatten(
        tree, [a + 0.05 * jax.random.normal(k, a.shape) for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def files():
    return harness.load_reference("glm_moe_dsa"), harness.load_architecture("glm_moe_dsa")


@pytest.fixture(scope="module", params=list(SHARES))
def toy(request):
    published = SHARES[request.param]
    return (published,) + toy_params(published)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, TOY["vocab_size"], (2, 48)).astype(np.int32)


def engine(toy, **over):
    _, cfg, params = toy
    return InferenceEngineV2(cfg, params, dict(ENGINE, **over))


def test_the_config_is_read_from_the_published_keys():
    cfg = config_from_hf(SHARES["rank1"])
    assert cfg.rope_theta == 1e6  # under rope_parameters: the top level has none, and 10,000 would be taken in silence
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (4, 16, 16)
    assert (cfg.num_experts, cfg.router_experts, cfg.first_expert) == (4, 8, 4)
    assert cfg.moe_router == "sigmoid" and cfg.moe_routed_scale == 2.5 and cfg.first_dense_layers == 1
    assert config_from_hf(dict(TOY, rope_theta=5.0)).rope_theta == 1e6  # rope_parameters wins


def test_the_catalog_row_cut_as_the_cell_has_it_gives_issue_55_s_size(files):
    _, architecture = files
    held = harness.load_config("glm-5")
    cfg = config_from_hf(program.published(held))
    assert cfg.num_params() == architecture.total_params(program.published(held)) == 4_727_340_800
    assert (cfg.num_layers, cfg.first_dense_layers, cfg.num_experts, cfg.router_experts) == (6, 1, 16, 256)
    assert (cfg.num_heads, cfg.index_heads, cfg.index_head_dim, cfg.index_topk, cfg.moe_top_k) == (64, 32, 128, 2048, 8)


@pytest.mark.parametrize("changed,said", [
    ({"rope_scaling": {"type": "yarn", "factor": 4.0, "original_max_position_embeddings": 4096}}, "rope_scaling"),
    ({"n_group": 4, "topk_group": 2}, "n_group"),
    ({"index_key_dtype": "float8_e4m3fn"}, "fp8 or int8 index key"),
    ({"index_topk": 0}, "index_topk"),
    ({"indexer_rope_interleave": False}, "indexer_rope_interleave"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}}, "rope_type"),
], ids=["rope_scaling", "n_group", "fp8_index_key", "no_indexer", "rope_halves", "scaled_rope_parameters"])
def test_what_the_mapping_does_not_build_is_refused_by_name(changed, said):
    with pytest.raises(ValueError, match=said):
        config_from_hf(dict(TOY, **changed))


@pytest.mark.parametrize("over,said", [
    ({"spec_decode": 2}, "multi-token-prediction"), ({"prefix_cache": True}, "prefix_cache"),
    ({"kv_cache_dtype": "int8"}, "fp8 or int8")], ids=["mtp_as_a_proposer", "prefix_cache", "int8_index_key"])
def test_what_does_not_serve_with_an_indexer_is_refused_by_name(over, said):
    with pytest.raises(ValueError, match=said):
        engine((TOY,) + toy_params(TOY), **over)


def test_an_indexer_needs_latent_attention_and_a_one_stream_block():
    from deepspeed_tpu.models.transformer import TransformerConfig

    plain = dict(vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2, num_heads=4)
    with pytest.raises(ValueError, match="sparse-attention indexer"):
        TransformerConfig(**plain, index_topk=8, index_heads=2, index_head_dim=8)
    with pytest.raises(ValueError, match="tp="):  # and its heads are not partitioned
        from deepspeed_tpu.topology.mesh import build_mesh

        _, cfg, params = (TOY,) + toy_params(TOY)
        InferenceEngineV2(cfg, params, dict(ENGINE), mesh=build_mesh(axis_sizes={"tp": 2, "dp": -1}))


def test_the_flax_forward_is_the_reference_s(files, toy, tokens):
    reference, architecture = files
    published, cfg, params = toy
    logits = CausalLM(cfg).apply({"params": params}, {"input_ids": jnp.asarray(tokens)}, train=False)[1]
    want = reference.forward(architecture.reference_weights(params), published, jnp.asarray(tokens))
    assert rel(logits, want) < 5e-5
    # and the selection carries it: the reference that keeps every candidate is another model
    dense = reference.forward(architecture.reference_weights(params), dict(published, index_topk=4096),
                              jnp.asarray(tokens))
    assert rel(logits[:, TOPK:], np.asarray(dense)[:, TOPK:]) > 1e-2
    assert rel(logits[:, :TOPK], np.asarray(dense)[:, :TOPK]) < 5e-5  # while t + 1 <= index_topk every one is kept


def test_pools_hold_a_latent_and_an_index_key_a_token_on_one_block_table(toy):
    from deepspeed_tpu.inference import cache

    eng = engine(toy)
    assert eng.pool.k.shape == (LAYERS * 64, 8, 128) and eng.pool.v.shape == (LAYERS * 64, 8, 128)
    assert cache.index_pool_width(toy[1]) == 128 and eng.kv_bytes_per_token == LAYERS * (128 + 128) * 4
    assert cache.index_pool_width(dataclasses.replace(toy[1], index_topk=0, index_heads=0, index_head_dim=0)) == 0


def test_put_then_decode_through_both_pools_is_the_reference_s_full_forward(files, toy, tokens):
    """Rows of different lengths: one prompt of 40 (over ``index_topk``), one of
    10 (under it), then tokens fed one at a time through the cache: the short
    row crosses ``index_topk`` DURING decode (its 17th token). Every logit
    against the reference's full forward; the program's selection is one the
    reference's own scores make (``select_shortfall`` 0), and pinned there the
    reference gives the same logits."""
    reference, architecture = files
    published, cfg, params = toy
    eng = engine(toy)
    weights = architecture.reference_weights(params)
    want = np.asarray(reference.forward(weights, published, jnp.asarray(tokens)))
    lens = [40, 10]
    routed, k = LAYERS - 1, TOY["num_experts_per_tok"]
    picks = np.broadcast_to(np.arange(k, dtype=np.int32), (2, 48, routed, k)).copy()
    selected = np.zeros((2, 48, LAYERS, 2), np.int32)
    got, p, s = architecture.put_with_selected(eng, [1, 2], [tokens[i, :lens[i]] for i in range(2)])
    for i in range(2):
        assert rel(got[i], want[i, lens[i] - 1]) < 5e-5
        picks[i, :lens[i]], selected[i, :lens[i]] = p[i], s[i]
    for step in range(8):
        got, p, s = architecture.put_with_selected(eng, [1, 2], [tokens[i, lens[i] + step:lens[i] + step + 1]
                                                                 for i in range(2)])
        for i in range(2):
            assert rel(got[i], want[i, lens[i] + step]) < 5e-5, (i, step)
            picks[i, lens[i] + step], selected[i, lens[i] + step] = p[i][0], s[i][0]
    kept = np.unpackbits(selected.view(np.uint8), axis=-1, bitorder="little").sum(-1)  # [2, 48, layers]
    for i in range(2):
        n = lens[i] + 8
        assert (kept[i, :n] == np.minimum(np.arange(n) + 1, TOPK)[:, None]).all()
    assert kept[1, 15].tolist() == [16] * LAYERS and kept[1, 17].tolist() == [16] * LAYERS  # crossed while decoding
    pinned = np.asarray(reference.forward(weights, published, jnp.asarray(tokens), picks, selected))
    short = np.asarray(reference.select_shortfall(weights, published, jnp.asarray(tokens), picks, selected))
    for i in range(2):
        n = lens[i] + 8
        assert float(short[i, :n].max()) == 0.0
        assert rel(pinned[i, :n], want[i, :n]) < 1e-6


def test_a_selection_that_is_not_the_indexer_s_reads_sigmas(files, toy, tokens):
    """The most recent ``index_topk`` tokens for the indexer's: ``select_shortfall`` reads it."""
    reference, architecture = files
    published, _, params = toy
    recent = np.zeros((2, 48, LAYERS, 2), bool).repeat(32, axis=-1)
    for t in range(48):
        recent[:, t, :, max(0, t + 1 - TOPK):t + 1] = True
    words = np.packbits(recent, axis=-1, bitorder="little").view(np.int32)
    short = np.asarray(reference.select_shortfall(architecture.reference_weights(params), published,
                                                  jnp.asarray(tokens), None, words))
    assert float(short[:, :TOPK].max()) == 0.0 and float(short[:, 2 * TOPK:].mean()) > 0.5


def test_generate_follows_the_reference_and_counts_what_it_scored_and_kept(files, toy, tokens):
    reference, architecture = files
    published, _, params = toy
    eng = engine(toy)
    prompts = [tokens[0, :30], tokens[1, :12]]
    outs, picks = architecture.generate_with_picks(eng, prompts, 9)
    assert [len(o) for o in outs] == [9, 9] and [p.shape[0] for p in picks] == [38, 20]
    full = np.stack([np.concatenate([p, o, np.zeros(48 - len(p) - len(o), np.int32)]) for p, o in zip(prompts, outs)])
    want = np.asarray(reference.forward(architecture.reference_weights(params), published, jnp.asarray(full)))
    for i, (p, o) in enumerate(zip(prompts, outs)):
        for j, tok in enumerate(o):  # greedy: each token the reference's own best, or within float32's error of it
            row = want[i, len(p) + j - 1]
            assert row.max() - row[tok] < 1e-4
    # the newest chain: what a live row's query scored (every position up to its own) and kept, a layer
    assert eng.last_tokens_kept == TOPK and eng.last_tokens_scored > TOPK


def test_spans_say_what_a_prefill_fed_and_what_a_chain_scored_and_kept(toy, tokens):
    from deepspeed_tpu.telemetry import get_tracer

    tracer = get_tracer()
    tracer.configure(enabled=True)
    tracer.reset()
    try:
        engine(toy).generate([tokens[0, :30], tokens[1, :12]], max_new_tokens=6)
        events = [e for e in tracer.events() if e["kind"] == "span"]
    finally:
        tracer.configure(enabled=False)
        tracer.reset()
    fed = [e["args"]["fed"] for e in events if e["name"] == "serve:dispatch" and e["args"].get("kind") == "prefill"]
    assert fed == ["0:30 0:12"]
    accept = [e["args"] for e in events if e["name"] == "serve:accept" and e["args"].get("kind") == "chain"]
    assert accept and all(a["tokens_kept"] <= TOPK and a["tokens_scored"] >= a["tokens_kept"] for a in accept)
    # the prefill counted off its own selection: rows of 30 and 12 queries from position 0, a query and layer
    (prefill,) = [e["args"] for e in events if e["name"] == "serve:accept" and e["args"].get("kind") == "prefill"]
    scored = sum(n * (n + 1) // 2 for n in (30, 12))
    kept = sum(sum(min(t + 1, TOPK) for t in range(n)) for n in (30, 12))
    assert prefill["queries"] == 42
    assert prefill["tokens_scored"] == pytest.approx(scored / 42) and prefill["tokens_kept"] == pytest.approx(kept / 42)


def test_a_block_table_no_wider_than_index_topk_takes_every_candidate(files, toy, tokens):
    """16 positions a row: nothing is scored or selected (the dense walk), and the index key is still written."""
    reference, architecture = files
    published, _, params = toy
    eng = engine(toy, max_seq_len=16, chunk_bucket=16)
    got, _, selected = architecture.put_with_selected(eng, [1], [tokens[0, :12]])
    want = np.asarray(reference.forward(architecture.reference_weights(params), published, jnp.asarray(tokens[:1, :12])))
    assert selected is None and rel(got[0], want[0, 11]) < 5e-5
    assert float(jnp.abs(eng.pool.v).sum()) > 0


def _layer_and_input(rows, hidden=64, experts=8, width=32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    n = lambda k, *shape: jax.random.normal(k, shape) * shape[-2] ** -0.5  # noqa: E731
    lp = {"gate": {"wg": {"kernel": n(keys[0], hidden, experts)}, "e_bias": 0.05 * jax.random.normal(keys[8], (experts,))},
          "experts": {"w_gate": n(keys[1], experts, hidden, width), "w_up": n(keys[2], experts, hidden, width),
                      "w_down": n(keys[3], experts, width, hidden)},
          "shared": {"w_gate": {"kernel": n(keys[4], hidden, width)}, "w_up": {"kernel": n(keys[5], hidden, width)},
                     "w_down": {"kernel": n(keys[6], width, hidden)}}}
    return lp, jax.random.normal(keys[7], (1, rows, hidden))


@pytest.mark.parametrize("rows", [5, 40], ids=["every-expert-product", "sorted-dispatch"])
def test_the_four_shares_terms_and_the_shared_expert_once_add_up_to_the_uncut_layer(files, rows):
    """Over all four ranks of a four-way share of one routed layer under the
    SIGMOID router with its correction bias (8 experts, 2 held a chip, 2 a
    token, scaled 2.5), the routed terms add up, with the shared expert (which
    every chip computes alike) counted once, to what the UNCUT reference gives
    for the whole layer; each part is the reference's own share of that rank;
    the picks every rank hands out are the uncut router's."""
    from deepspeed_tpu.inference.model import _moe_with_picks

    reference, _ = files
    size, held = 4, 2
    lp, x = _layer_and_input(rows)
    ref_w = {"router": lp["gate"]["wg"]["kernel"], "router_bias": lp["gate"]["e_bias"],
             "shared_gate": lp["shared"]["w_gate"]["kernel"], "shared_up": lp["shared"]["w_up"]["kernel"],
             "shared_down": lp["shared"]["w_down"]["kernel"]}
    leaves = lambda lo, hi: tuple(lp["experts"][n][lo:hi] for n in reference.EXPERT_LEAVES)  # noqa: E731
    whole = dict(TOY, n_routed_experts=size * held)
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.experts(x[0], ref_w, leaves(0, size * held), whole, None)
        shared = reference.glu(x[0], ref_w["shared_gate"], ref_w["shared_up"], ref_w["shared_down"])
        select = jax.nn.sigmoid(x[0] @ ref_w["router"]) + ref_w["router_bias"]
    uncut_picks = np.sort(np.asarray(jax.lax.top_k(select, 2)[1]), axis=-1)
    total = np.zeros_like(np.asarray(uncut))
    for rank in range(size):
        published = dict(TOY, n_routed_experts=held, expert_parallel={"size": size, "rank": rank})
        cfg = config_from_hf(published)
        assert (cfg.first_expert, cfg.router_experts) == (rank * held, 8)
        mine = dict(lp, experts={n: a[rank * held:(rank + 1) * held] for n, a in lp["experts"].items()})
        part, picks = _moe_with_picks(mine, cfg, x)
        assert np.array_equal(np.sort(np.asarray(picks), axis=-1), uncut_picks)
        with jax.default_matmul_precision("highest"):
            ref_part, shortfall = reference.experts(x[0], ref_w, leaves(rank * held, (rank + 1) * held), published,
                                                    np.asarray(picks))
        assert rel(part[0], ref_part) < 1e-5 and float(shortfall.max()) <= 0
        total += np.asarray(part[0]) - np.asarray(shared)
    assert rel(total + np.asarray(shared), uncut) < 1e-5
    assert rel(total, np.asarray(uncut) - np.asarray(shared)) < 1e-5  # and it is not the shared expert that carries it


def test_the_published_leaf_names_there_and_back_with_the_indexer_s_four(toy):
    """``_convert_glm_moe_dsa`` on a toy state dict under the published names: the indexer's ``wq_b``, ``wk``,
    ``k_norm`` (weight and bias) and ``weights_proj`` a layer; a state with them is NOT taken for glm4_moe_lite."""
    published, cfg, params = toy
    state = latent_moe_hf_state(jax.tree_util.tree_map(np.asarray, params), cfg)
    assert state["model.layers.1.self_attn.indexer.wq_b.weight"].shape == (4 * 16, 32)
    assert state["model.layers.0.self_attn.indexer.wk.weight"].shape == (16, 64)
    assert state["model.layers.2.self_attn.indexer.weights_proj.weight"].shape == (4, 64)
    assert {"model.layers.0.self_attn.indexer.k_norm.weight", "model.layers.0.self_attn.indexer.k_norm.bias"} <= set(state)
    first = cfg.first_expert  # a chip's share reads (and writes) its own experts' numbers
    assert f"model.layers.1.mlp.experts.{first}.up_proj.weight" in state
    assert f"model.layers.1.mlp.experts.{first + cfg.num_experts}.up_proj.weight" not in state
    assert detect_family(state) == "glm_moe_dsa"
    assert detect_family({k: v for k, v in state.items() if ".indexer." not in k}) == "glm4_moe_lite"
    state["model.layers.3.eh_proj.weight"] = np.zeros((4, 4))  # the MTP layer's keys are not read
    back = dict(jax.tree_util.tree_leaves_with_path(convert_hf_state(state, cfg)))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        np.testing.assert_array_equal(np.asarray(leaf), back[path])


def test_the_scopes_of_the_indexer_are_in_the_compiled_programs_and_its_leaves_name_their_products(toy, tokens):
    eng = engine(toy)
    _, cfg, params = toy
    text = eng._step_fn(2, 64).lower(
        params, eng.pools, jnp.zeros((2, 64), jnp.int32), jnp.zeros((2, 64), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, eng.max_pages), jnp.int32)).as_text(debug_info=True)
    for scope in ("mla/dsa_index/idx_wq", "mla/dsa_index/idx_wk", "mla/dsa_index/idx_k_norm", "mla/dsa_index/idx_w",
                  "mla/dsa_select", "mla/dsa_attend", "kv_write"):
        assert scope in text, scope


def test_the_reference_imports_nothing_of_the_program_and_reads_the_latent_one_beside_it(files):
    import os

    src = open(os.path.join(harness.BENCH_DIR, "reference", "glm_moe_dsa.py")).read()
    assert "deepspeed_tpu" not in src.replace("the system under test", "") and "glm4_moe_lite.py" in src
    assert 'default_matmul_precision("highest")' in src
