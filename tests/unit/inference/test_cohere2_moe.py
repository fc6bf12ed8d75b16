"""``cohere2_moe`` (Command A+) at a toy size with the published structure (three
sliding-window layers under a band, rotary in adjacent pairs, to one full layer
with no position term, every layer ONE parallel block: attention, a sigmoid
router without a correction bias and AVERAGED shared experts all read one
bias-free LayerNorm; a tied embedding) against the benchmark's plain reference
``benchmarks/reference/cohere2_moe.py``: the flax forward, and
``InferenceEngineV2`` through TWO CLASSES OF PAGE on one block table (global
columns that grow, a ring the sliding layers write round): ``put`` of fresh
prompts shorter than, equal to and longer than the window, then tokens through
the ring past two wraps of it, logits and not tokens, at the program's own
picks; whole and as a share of an expert-parallel layer, whose parts add up to
the uncut layer. The window is 32 keys at a block of 8: a ring of 5 pages.

Tolerances. fp32: 5e-5 relative L2 of logits (read under 3e-6)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import harness, program
from deepspeed_tpu.checkpoint.hf import config_from_hf
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.cache import RingLayout
from deepspeed_tpu.inference.ragged import StateManager
from deepspeed_tpu.models import CausalLM

WINDOW, BLOCK = 32, 8
TOY = dict(
    model_type="cohere2_moe", vocab_size=512, hidden_size=64, intermediate_size=32, num_hidden_layers=4,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16, max_position_embeddings=512,
    layer_types=["sliding_attention"] * 3 + ["full_attention"], layer_switch=4, sliding_window=WINDOW,
    num_experts=8, num_experts_per_tok=2, num_shared_experts=4, shared_expert_combination_strategy="average",
    expert_selection_fn="sigmoid", norm_topk_prob=True, first_k_dense_replace=0, layer_norm_eps=1e-5,
    rope_parameters={"rope_theta": 50000, "rope_type": "default"}, rope_theta=50000, rotary_pct=1,
    position_embedding_type="rope_gptj", logit_scale=1, tie_word_embeddings=True, use_parallel_block=True,
    use_qk_norm=False, use_gated_activation=True, attention_bias=False, hidden_act="silu")
SHARES = {"whole": TOY, "rank1": dict(TOY, num_experts=4, expert_parallel={"size": 2, "rank": 1})}
ENGINE = {"dtype": "fp32", "kv_cache_dtype": "fp32", "kv_block_size": BLOCK, "num_kv_blocks": 96, "chunk_bucket": 16,
          "max_seq_len": 192, "max_seqs": 4, "decode_chain": 4, "row_bucket": 1, "max_ragged_batch_size": 512,
          "hbm_check": "off"}
LENGTHS = (20, 32, 75, 104)  # shorter than, equal to and longer than the window (two of them past a ring's round)


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
    return harness.load_reference("cohere2_moe"), harness.load_architecture("cohere2_moe")


@pytest.fixture(scope="module", params=list(SHARES))
def toy(request):
    published = SHARES[request.param]
    return (published,) + toy_params(published)


def engine(toy, **over):
    _, cfg, params = toy
    return InferenceEngineV2(cfg, params, dict(ENGINE, **over))


def sequences(total=192, seed=0):
    return np.random.default_rng(seed).integers(0, TOY["vocab_size"], (len(LENGTHS), total)).astype(np.int32)


def test_the_config_is_read_from_the_published_keys():
    cfg = config_from_hf(SHARES["rank1"])
    assert cfg.layer_types == ("sliding_attention",) * 3 + ("attention",) and cfg.parallel_block
    assert (cfg.sliding.window, cfg.sliding.global_rope) == (WINDOW, False)
    assert (cfg.norm, cfg.norm_bias, cfg.rope_interleaved, cfg.rope_theta) == ("layernorm", False, True, 50000.0)
    assert (cfg.moe_router, cfg.moe_router_bias, cfg.moe_renormalize) == ("sigmoid", False, True)
    assert (cfg.moe_shared_experts, cfg.moe_shared_average, cfg.expert_width) == (4, True, 32)
    assert (cfg.num_experts, cfg.router_experts, cfg.first_expert) == (4, 8, 4)
    assert cfg.tie_embeddings and cfg.logits_scaling == 1.0 and cfg.attention_layers == 1 and cfg.sliding_layers == 3
    # written out from layer_switch where the config has no list
    assert config_from_hf({k: v for k, v in TOY.items() if k != "layer_types"}).layer_types == cfg.layer_types


def test_the_catalog_row_cut_as_the_cell_has_it_gives_issue_57_s_size(files):
    _, architecture = files
    held = harness.load_config("command-a-plus-05-2026")
    cfg = config_from_hf(program.published(held))
    shapes = jax.eval_shape(lambda: CausalLM(cfg).init(
        {"params": jax.random.PRNGKey(0)}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"])
    in_the_tree = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert in_the_tree == architecture.total_params(program.published(held)) == cfg.num_params() == 4_733_292_544
    assert (cfg.num_layers, cfg.num_experts, cfg.router_experts, cfg.moe_top_k) == (4, 16, 128, 8)
    assert (cfg.num_heads, cfg.kv_heads, cfg.dims_per_head, cfg.sliding.window) == (128, 8, 128, 4096)


@pytest.mark.parametrize("changed,said", [
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"), ({"use_qk_norm": True}, "use_qk_norm"),
    ({"use_parallel_block": False}, "use_parallel_block"), ({"expert_selection_fn": "softmax"}, "expert_selection_fn"),
    ({"shared_expert_combination_strategy": "sum"}, "shared_expert_combination_strategy"),
    ({"position_embedding_type": "rope_neox"}, "position_embedding_type"), ({"rotary_pct": 0.5}, "rotary_pct"),
    ({"layer_types": ["linear_attention"] * 4}, "layer_types"), ({"sliding_window": None}, "sliding_window"),
], ids=["leading_dense", "qk_norm", "sequential_block", "softmax_router", "shared_summed", "rope_halves",
        "partial_rotary", "another_kind", "no_window"])
def test_what_the_mapping_does_not_build_is_refused_by_name(changed, said):
    with pytest.raises(ValueError, match=said):
        config_from_hf(dict(TOY, **changed))


@pytest.mark.parametrize("over,said", [
    ({"prefix_cache": True}, "prefix_cache"), ({"spec_decode": 2}, "spec_decode"),
    ({"kv_cache_dtype": "int8"}, "kv_cache_dtype='int8'"), ({"chunk_bucket": 12}, "chunk_bucket=12"),
    ({"tp_size": 2}, "tp=2")], ids=["prefix_cache", "spec_decode", "int8_pool", "part_pages", "tp"])
def test_what_does_not_serve_with_a_sliding_kind_is_refused_by_name(over, said):
    with pytest.raises(ValueError, match="sliding kind.*" + said):
        engine((TOY,) + toy_params(TOY), **over)


def test_migration_the_v1_engine_a_chunk_past_zero_and_the_undrawn_pairings_are_refused_by_name():
    from deepspeed_tpu.inference.model import init_cache
    from deepspeed_tpu.models.transformer import TransformerConfig

    toy = (TOY,) + toy_params(TOY)
    eng = engine(toy)
    eng.put([1], [np.arange(20, dtype=np.int32)])
    with pytest.raises(ValueError, match="KV-block migration of a model with a sliding kind"):
        eng.export_request(1)
    # a preempted row's resume feeds its whole context again, as a fresh prompt; a chunk after tokens is refused,
    # and so is one token after tokens beside another row's fresh prompt: a call of chunks computes no ring read
    with pytest.raises(ValueError, match="uid 1: 5 token.s. after 20.*takes fresh prompts alone.*R3b"):
        eng.put([1], [np.arange(5, dtype=np.int32)])
    with pytest.raises(ValueError, match="uid 1: 1 token.s. after 20.*takes fresh prompts alone.*R3b"):
        eng.put([2, 1], [np.arange(9, dtype=np.int32), np.arange(1, dtype=np.int32)])
    with pytest.raises(NotImplementedError, match="sliding kind.*v1 engine"):
        init_cache(toy[1], 1, 64)
    plain = dict(vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2, num_heads=4)
    with pytest.raises(ValueError, match="go together"):
        TransformerConfig(**plain, layer_types=("sliding_attention", "attention"))
    with pytest.raises(ValueError, match="give layer_types"):
        TransformerConfig(**plain, sliding={"window": 8})
    with pytest.raises(ValueError, match="plain attention under a band"):
        TransformerConfig(**plain, layer_types=("sliding_attention", "attention"), sliding={"window": 8},
                          position="alibi")
    # the other pairings of a pattern with a parallel block stay refused: a dense MLP, a state-space layer
    with pytest.raises(ValueError, match="layer pattern"):
        TransformerConfig(**plain, layer_types=("sliding_attention", "attention"), sliding={"window": 8},
                          parallel_block=True)


def test_the_module_is_the_reference(files, toy):
    reference, architecture = files
    published, cfg, params = toy
    tokens = sequences(120)
    _, logits = CausalLM(cfg).apply({"params": params}, {"input_ids": jnp.asarray(tokens)}, train=False)
    want = reference.forward(architecture.reference_weights(params), program.published(published), tokens)
    assert rel(logits, want) < 5e-5
    assert rel(logits[:, :WINDOW], want[:, :WINDOW]) < 5e-5 and rel(logits[:, -1], want[:, -1]) < 5e-5


def test_prefill_then_decode_through_the_ring_past_two_wraps_is_the_reference(files, toy):
    """``put`` of four fresh prompts (one call each: the token budget), then 82
    tokens a row one at a time: the ring of 5 pages is written round twice and
    more, the global table grows; every step's logits against the reference's
    full forward pinned to the program's own picks, and the picks audited."""
    reference, architecture = files
    published, cfg, params = toy
    eng = engine(toy, max_ragged_batch_size=16 * 7)  # one prompt of 104 padded to 112 a call
    seqs = sequences()
    uids, steps = [10, 11, 12, 13], 82
    routing = program.routing(architecture, published)
    picks = np.broadcast_to(np.arange(routing.k, dtype=np.int32),
                            seqs.shape + (routing.layers, routing.k)).copy()
    got = []
    for step in range(steps + 1):
        starts = [0 if step == 0 else n + step - 1 for n in LENGTHS]
        fed = [seqs[i, starts[i]:n + step] for i, n in enumerate(LENGTHS)]
        logits, row_picks = eng.put_with_picks(uids, fed)
        for i, (start, f) in enumerate(zip(starts, fed)):
            picks[i, start:start + len(f)] = row_picks[i]
        got.append(np.asarray(logits))
    weights, plain = architecture.reference_weights(params), program.published(published)
    want = np.asarray(reference.forward(weights, plain, seqs, picks))
    shortfall = np.asarray(reference.route_shortfall(weights, plain, seqs, picks))
    for step in range(steps + 1):
        at = np.stack([want[i, n + step - 1] for i, n in enumerate(LENGTHS)])
        assert rel(got[step], at) < 5e-5, step
    fed_to = np.zeros(seqs.shape, bool)
    for i, n in enumerate(LENGTHS):
        fed_to[i, :n + steps] = True
    assert float(shortfall[fed_to].max()) < 1e-3  # the program's picks are this router's own
    stats = eng.stats()
    ring = RingLayout(WINDOW, BLOCK, ENGINE["max_seq_len"]).window_pages
    assert stats["kv_ring_pages_held"] == sum(min(-(-(n + steps) // BLOCK), ring) for n in LENGTHS)
    assert stats["kv_global_pages_held"] == sum(-(-(n + steps) // BLOCK) for n in LENGTHS)
    assert stats["ring_pages_overwritten"] > 0
    for uid in uids:
        eng.flush(uid)
    assert eng.state.allocators[1].free_blocks == eng.ring_blocks and eng.state.free_blocks == eng.num_kv_blocks


def test_generate_is_the_module_s_greedy_tokens(toy):
    """The serving loop itself: fused prefills (one prompt a call), decode
    chains of four with the ring's roll computed on the device inside a chain."""
    _, cfg, params = toy
    eng = engine(toy, max_ragged_batch_size=16 * 7)
    seqs = sequences()
    prompts = [seqs[i, :n] for i, n in enumerate(LENGTHS)]
    outs = eng.generate(prompts, max_new_tokens=60)
    for prompt, out in zip(prompts, outs):
        full = np.concatenate([prompt, out])
        _, logits = CausalLM(cfg).apply({"params": params}, {"input_ids": jnp.asarray(full[None])}, train=False)
        assert np.array_equal(np.asarray(jnp.argmax(logits[0], -1))[len(prompt) - 1:len(full) - 1], out)


def test_a_put_over_the_token_budget_goes_in_several_calls_and_reads_the_same(toy):
    seqs = sequences()
    fed = [seqs[i, :n] for i, n in enumerate(LENGTHS)]
    one = engine(toy, max_ragged_batch_size=4 * 16 * 7)
    several = engine(toy, max_ragged_batch_size=16 * 7)
    a, picks_a = one.put_with_picks([1, 2, 3, 4], fed)
    b, picks_b = several.put_with_picks([1, 2, 3, 4], fed)
    assert (one.dispatch_count, several.dispatch_count) == (1, 4)
    assert rel(b, a) < 1e-5 and all(np.array_equal(x, y) for x, y in zip(picks_a, picks_b))


def test_the_eight_ranks_routed_parts_the_shared_part_and_attention_once_add_up_to_the_uncut_layer(files):
    """Over all eight ranks of an eight-way share of one layer (16 experts, 2
    held a chip, 2 a token): the ranks' routed terms, plus the averaged shared
    experts (which every chip computes alike) counted once, plus attention
    counted once, plus the residual, are what the UNCUT reference gives for the
    whole layer; the picks every rank hands out are the uncut router's."""
    from deepspeed_tpu.inference.model import _moe_with_picks

    reference, architecture = files
    size, held = 8, 2
    whole = dict(TOY, num_experts=size * held, num_hidden_layers=1, layer_types=["sliding_attention"])
    cfg, params = toy_params(whole, seed=3)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["layer_0"])
    w = {k: a[0] for k, a in architecture.reference_weights(params)["period"][0].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, TOY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.layer(x[0], w, "sliding_attention", whole, None)
        h = reference.layer_norm(x[0], w["norm"], whole["layer_norm_eps"])
        attn = reference.attention(h, w, whole, "sliding_attention")
        ns, f = whole["num_shared_experts"], whole["intermediate_size"]
        shared = sum(reference.glu(h, w["shared_gate"][:, j * f:(j + 1) * f], w["shared_up"][:, j * f:(j + 1) * f],
                                   w["shared_down"][j * f:(j + 1) * f]) for j in range(ns)) / ns
        uncut_picks = np.sort(np.asarray(jax.lax.top_k(jax.nn.sigmoid(h @ w["router"]), 2)[1]), axis=-1)
    total = np.zeros_like(np.asarray(uncut))
    for rank in range(size):
        published = dict(whole, num_experts=held, expert_parallel={"size": size, "rank": rank})
        rank_cfg = config_from_hf(published)
        assert (rank_cfg.first_expert, rank_cfg.router_experts) == (rank * held, size * held)
        moe = lp["moe"]
        mine = dict(moe, experts={n: a[rank * held:(rank + 1) * held] for n, a in moe["experts"].items()})
        part, picks = _moe_with_picks(mine, rank_cfg, h[None])
        assert np.array_equal(np.sort(np.asarray(picks), axis=-1), uncut_picks)
        total += np.asarray(part[0]) - np.asarray(shared)  # this rank's routed terms alone
    assert rel(np.asarray(x[0]) + np.asarray(attn) + total + np.asarray(shared), uncut) < 1e-5
    assert float(np.linalg.norm(total)) > 0.1 * float(np.linalg.norm(np.asarray(shared)))  # the routed part is no rounding


def test_no_ring_page_goes_to_two_rows_and_both_classes_come_back_at_flush():
    layout = RingLayout(WINDOW, BLOCK, 192)
    assert (layout.window_pages, layout.summary_cols, layout.width) == (5, 24, 29)
    state = StateManager(40, BLOCK, max_seqs=4, max_blocks_per_seq=layout.width, layout=layout, ring_blocks=12)
    rng = np.random.default_rng(0)
    seen = {}
    for step in range(60):
        uid = int(rng.integers(0, 4))
        new = int(rng.integers(1, 30)) if uid not in seen else 1
        if not state.can_schedule([uid], [new]):
            state.flush(uid)
            seen.pop(uid, None)
            continue
        seq = state.extend(uid, new)
        seq.seen_tokens += new  # (what the engine does under a ring: nothing closes and nothing is freed)
        seen[uid] = seen.get(uid, 0) + new
        assert (seq.n_summary, seq.n_window) == layout.pages(seen[uid], 0)
        rings = [s._table[layout.summary_cols: layout.summary_cols + s.n_window] for s in state._seqs.values()]
        globals_ = [s._table[: s.n_summary] for s in state._seqs.values()]
        for held in (rings, globals_):
            pages = np.concatenate(held)
            assert len(set(pages.tolist())) == len(pages)  # no page of a class in two rows, or twice in one
        assert state.allocators[1].free_blocks == 12 - sum(len(r) for r in rings)
        assert state.free_blocks == 40 - sum(len(g) for g in globals_)
    # three rows hold the whole of the ring class: a fourth is not admitted though global pages are left
    for uid in list(state._seqs):
        state.flush(uid)
    assert state.allocators[1].free_blocks == 12 and state.free_blocks == 40
    for uid in (0, 1):
        state.extend(uid, 40)
    assert state.allocators[1].free_blocks == 2 and not state.can_schedule([2], [40]) and state.can_schedule([2], [16])
    assert layout.overwritten(0, 40) == 0 and layout.overwritten(40, 1) == 1 and layout.overwritten(41, 7) == 0


def swa_controls(monkeypatch):
    """``tools/swa_controls.py`` as a module; what its plants replace is put back when the test ends."""
    import importlib.util
    import os

    from benchmarks.runners import serve
    from deepspeed_tpu.checkpoint import hf
    from deepspeed_tpu.inference import model, paged
    from deepspeed_tpu.models import transformer
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    root = os.path.dirname(os.path.dirname(harness.BENCH_DIR + "/"))
    spec = importlib.util.spec_from_file_location("swa_controls", os.path.join(root, "tools", "swa_controls.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    for module, name in ((hf, "config_from_hf"), (harness, "load_workload"), (attention, "first_live"),
                         (attention, "causal_attention"), (transformer, "sliding_kind"), (paged, "paged_attention"),
                         (model, "_mlp"), (fa, "_band_maps"), (fa, "_band_first"), (serve, "check"),
                         (InferenceEngineV2, "generate"), (InferenceEngineV2, "_log_picks")):
        monkeypatch.setattr(module, name, getattr(module, name))
    return tool


@pytest.mark.parametrize("control", ["window_wider", "window_narrower", "dead_slots", "rope_on_full", "rope_halves",
                                     "shared_summed", "e4m3_ring", "e4m3_swa_prefill", "e4m3_shared",
                                     "band_as_mask"])
def test_the_planted_faults_of_tools_swa_controls_are_seen_in_float32(files, control, monkeypatch):
    """Each fault ``tools/swa_controls.py`` plants, at the toy's window of 32 in
    float32, through ``put`` of two prompts past the window and four further
    tokens through the ring: every one moves the logits by hundreds of times
    the sound reading (on the chip, in bf16 at a window of 4,096, one key in
    4,096 does not: PERF.md, section 6); the band as a mask alone moves nothing."""
    tool = swa_controls(monkeypatch)
    from deepspeed_tpu.checkpoint import hf

    reference, architecture = files
    _, params = toy_params(TOY)  # the published model's weights, made before the plant
    tool.PLANTS[control]()
    harness.load_workload("command-a-plus-05-2026.serve.long-prompt-wave8")  # (where dead_slots learns the block: 16)
    eng = InferenceEngineV2(hf.config_from_hf(TOY), params, dict(ENGINE, kv_block_size=16, max_ragged_batch_size=112))
    seqs, lengths, steps = sequences(120, seed=4), (75, 104), 4
    got = []
    for step in range(steps + 1):
        fed = [seqs[i, (0 if step == 0 else n + step - 1):n + step] for i, n in enumerate(lengths)]
        got.append(np.asarray(eng.put_with_picks([1, 2], fed)[0]))
    want = np.asarray(reference.forward(architecture.reference_weights(params), program.published(TOY), seqs[:2]))
    errs = [rel(got[step], np.stack([want[i, n + step - 1] for i, n in enumerate(lengths)])) for step in range(steps + 1)]
    if control == "band_as_mask":
        assert max(errs) < 5e-5, errs
    else:
        assert max(errs) > 2e-3, errs


def test_a_prompt_of_one_token_beside_longer_ones_is_a_row_of_the_chunk_s_program(files, toy):
    """A call of fresh prompts has no one-token path: a prompt of ONE token
    attends itself inside the chunk and writes its page like any other, and
    the token fed after it reads that page through the ring and the table."""
    reference, architecture = files
    published, cfg, params = toy
    eng = engine(toy)
    seqs = sequences(64, seed=3)[:2]
    lengths = (1, 37)
    first = eng.put([5, 6], [seqs[i, :n] for i, n in enumerate(lengths)])
    assert eng.dispatch_count == 1
    second = eng.put([5, 6], [seqs[i, n:n + 1] for i, n in enumerate(lengths)])
    want = np.asarray(reference.forward(architecture.reference_weights(params), program.published(published), seqs))
    for step, got in enumerate((first, second)):
        assert rel(got, np.stack([want[i, n + step - 1] for i, n in enumerate(lengths)])) < 5e-5, step


def test_wave_parts_counts_a_wave_s_held_pairs_and_touched_experts_and_leaves_its_tokens_alone(toy, monkeypatch, capsys):
    """The diagnostic behind PERF.md's account of the seed's hold on the rate:
    a wave of ``max_seqs`` prompts is served as it always is, and one line says
    its seconds, the held experts a decode step touched and the pairs of its
    prefills that landed on a held expert, real tokens and pads apart."""
    published, cfg, params = toy
    seqs = sequences()
    prompts = [seqs[i, :n] for i, n in enumerate(LENGTHS)]
    want = engine(toy, max_ragged_batch_size=16 * 7).generate(prompts, max_new_tokens=12)
    swa_controls(monkeypatch).PLANTS["wave_parts"]()
    eng = engine(toy, max_ragged_batch_size=16 * 7)
    got = eng.generate(prompts, max_new_tokens=12)
    assert all(np.array_equal(a, b) for a, b in zip(got, want))
    said = dict(part.split("=") for part in capsys.readouterr().out.split() if "=" in part)
    padded = sum(-(-n // 16) * 16 for n in LENGTHS)
    k, layers = cfg.moe_top_k, cfg.routed_layers
    assert 1 <= int(said["prefill_calls"]) <= 4 and 0 <= float(said["touched"]) <= cfg.num_experts
    if cfg.expert_parallel is None:  # every expert is held: every pair lands, of tokens and of pads
        assert int(said["real_pairs"]) == sum(LENGTHS) * k * layers
        # (a call's rows are padded to its longest prompt's bucket: at least every prompt to its own)
        assert int(said["pad_pairs"]) >= (padded - sum(LENGTHS)) * k * layers and int(said["pad_pairs"]) % (k * layers) == 0
        assert float(said["touched"]) >= k
    else:
        assert 0 < int(said["real_pairs"]) < sum(LENGTHS) * k * layers


def blocks_of_8(monkeypatch):
    """The flash forward at blocks of 8 behind the registry's name, so that a toy bucket has blocks past a prompt."""
    import functools

    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setitem(registry._REGISTRY["causal_attention"], "pallas",
                        functools.partial(fa.flash_causal_attention, block_q=8, block_k=8))


@pytest.mark.parametrize("impl", ["xla", "flash"])
@pytest.mark.parametrize("length", [48, 41, 8], ids=lambda n: f"prompt-{n}")
def test_a_padded_prompt_s_logits_and_pages_are_the_unpadded_prompt_s(toy, impl, length, monkeypatch):
    """A fresh prompt through ``_windowed_attention`` in a bucket of 112 (pads after it, whole blocks of them
    that the flash forward, told ``new_lens``, neither fetches nor computes) against the same prompt in the
    tightest bucket that holds it: the same logits, and the same pages in both classes (a pad writes none)."""
    import dataclasses

    _, cfg, params = toy
    if impl == "flash":
        blocks_of_8(monkeypatch)
    cfg = dataclasses.replace(cfg, attn_impl=impl)
    prompt = sequences(64, seed=5)[0, :length]
    tight = InferenceEngineV2(cfg, params, dict(ENGINE, chunk_bucket=8))
    loose = InferenceEngineV2(cfg, params, dict(ENGINE, chunk_bucket=112))
    got, want = loose.put([3], [prompt]), tight.put([3], [prompt])
    assert rel(got, want) < 1e-5
    assert np.array_equal(loose.state.get(3).blocks, tight.state.get(3).blocks)
    for a, b in zip(jax.tree_util.tree_leaves(loose.pools), jax.tree_util.tree_leaves(tight.pools)):
        assert float(jnp.abs(a - b).max()) < 1e-5 * max(float(jnp.abs(b).max()), 1.0)


def test_a_prefill_s_span_counts_the_flash_forward_s_live_cells_by_the_kernels_own_maps(toy, monkeypatch):
    """``flash_cells_live`` / ``flash_cells_grid`` on a call of fresh prompts' ``serve:dispatch`` span: summed
    over the call's rows and the pattern's layers; absent where the chunks' attention is not the flash forward."""
    import dataclasses

    from deepspeed_tpu.ops.pallas import flash_attention as fa
    from deepspeed_tpu.telemetry import get_tracer

    _, cfg, params = toy
    seqs = sequences(64, seed=7)
    tracer = get_tracer()
    tracer.configure(enabled=True)
    tracer.reset()
    try:
        said = {}
        for impl in ("xla", "flash"):
            eng = InferenceEngineV2(dataclasses.replace(cfg, attn_impl=impl), params, dict(ENGINE, chunk_bucket=112))
            eng.put([1, 2], [seqs[0, :41], seqs[1, :8]])
            said[impl] = [e["args"] for e in tracer.events() if e["kind"] == "span" and e["name"] == "serve:dispatch"]
            tracer.reset()
        # a bucket past the kernel's block of 512: rows of 700, 1,300 and no tokens in a bucket of 2,048
        lengths, S = np.array([700, 1300, 0]), 2048
        args = eng._flash_args(lengths, S)
        assert eng._flash_args(lengths, 1) == {}  # (one token a row goes through the pages)
    finally:
        tracer.configure(enabled=False)
        tracer.reset()
    assert eng._flash_args(lengths, S) == {}  # nobody records: nothing is counted
    assert all("flash_cells_live" not in a for a in said["xla"])
    (call,) = said["flash"]
    assert (cfg.sliding_layers, cfg.attention_layers) == (3, 1)
    assert (call["flash_cells_live"], call["flash_cells_grid"]) == (2 * 4, 2 * 4)  # a bucket of 112 is one block a row
    maps = {None: np.asarray(fa._tri_maps(4)[0]), WINDOW: np.asarray(fa._band_maps(4, 512, WINDOW)[0])}
    live = {w: sum(int((qs * 512 < n).sum()) for n in lengths) for w, qs in maps.items()}
    assert (live[None], live[WINDOW]) == (3 + 6, 3 + 5) and (len(maps[None]), len(maps[WINDOW])) == (10, 7)
    assert args == {"flash_cells_live": live[None] + 3 * live[WINDOW], "flash_cells_grid": 3 * (10 + 3 * 7)}


def test_swa_controls_prints_the_live_share_of_the_flash_forwards_cells(monkeypatch, capsys):
    """``--control flash_cells``: one more metric on the traced line, from the spans' two counts; nothing
    where no span of the window says them (the parent of PR 58, a window of chains)."""
    import collections

    from benchmarks.lib import spans

    tool = swa_controls(monkeypatch)
    Span = collections.namedtuple("Span", "name args")
    window = (Span("serve:dispatch", {"kind": "prefill", "flash_cells_live": 1000, "flash_cells_grid": 1284}),
              Span("serve:dispatch", {"kind": "chain", "ring_tokens": 7}),
              Span("serve:fetch", {"kind": "prefill", "flash_cells_live": 5, "flash_cells_grid": 5}),
              Span("serve:dispatch", {"kind": "prefill", "flash_cells_live": 284, "flash_cells_grid": 1284}))
    monkeypatch.setattr(spans, "of_run", lambda run: window)
    assert tool.flash_cells_live_share({}) == pytest.approx(50.0)
    assert "flash_cells_calls=2 flash_cells_live=1284.0 flash_cells_grid=2568.0" in capsys.readouterr().out
    monkeypatch.setattr(spans, "of_run", lambda run: window[1:3])
    assert tool.flash_cells_live_share({}) is None
    for name in ("cell_metrics", "load_reader"):
        monkeypatch.setattr(harness, name, getattr(harness, name))
    tool.PLANTS["flash_cells"]()
    bench = harness.load_json(harness.BENCH_DIR + "/../BENCHMARK.json")
    cell = "command-a-plus-05-2026.serve.long-prompt-wave8"
    assert harness.cell_metrics(bench, "per_layer", cell)[-1] == {"name": "flash_cells_live_share.batch", "unit": "%"}
    assert all(m["name"] != "flash_cells_live_share.batch" for m in harness.cell_metrics(bench, "end_to_end", cell))
    assert harness.load_reader("flash_cells_live_share.batch")({}, None) is None
