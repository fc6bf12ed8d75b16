"""``qwen3_next`` at a toy size with the published structure (a ROUTED layer
pattern of two periods: three Gated DeltaNet layers to one gated
softmax-attention layer with per-head QK-norm and partial rotary, in every
layer a softmax router beside a shared expert behind a sigmoid gate, norms
that multiply by 1 + w) against the benchmark's plain reference
``benchmarks/reference/qwen3_next.py``, whose DeltaNet layers are the
sequential recurrence: the flax forward, and ``InferenceEngineV2`` through the
state pool beside the page pool (``put``, the fused prefill, ``generate`` with
a chain ahead), logits and not tokens; ONE CHIP'S SHARE of the routed layer
(``expert_parallel``) against the uncut layer; the names in a trace.

Tolerances. fp32: 1e-4 relative L2 of logits (read 3e-6 to 4e-5: eight layers,
the chunked form's other order of summation). bf16 at the program's own
picks: 0.5, a smoke test of the dtype's path alone (read 0.06-0.33 at hidden
64, 0.04-0.07 at 256, 0.018-0.019 on the chip at 2,048: the error falls with
the width; the chip's own check holds the bf16 program to its reference)."""

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
from deepspeed_tpu.models.transformer import ExpertParallel, GDNConfig, TransformerConfig

TOY = dict(
    model_type="qwen3_next", vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32, full_attention_interval=4,
    linear_conv_kernel_dim=4, linear_key_head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_value_head_dim=16, moe_intermediate_size=32, shared_expert_intermediate_size=32, num_experts=16,
    num_experts_per_tok=4, norm_topk_prob=True, partial_rotary_factor=0.25, rms_norm_eps=1e-6,
    rope_theta=10000000, max_position_embeddings=512, tie_word_embeddings=False, decoder_sparse_step=1,
    mlp_only_layers=[], hidden_act="silu", rope_scaling=None, use_sliding_window=False)
SHARE = dict(TOY, num_experts=4, expert_parallel={"size": 4, "rank": 2})  # experts 8..11 of the router's 16
DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}
ENGINE = {"kv_block_size": 16, "num_kv_blocks": 64, "chunk_bucket": 64, "row_bucket": 4, "max_seq_len": 256,
          "max_seqs": 8, "decode_chain": 4, "hbm_check": "off"}
K, L = TOY["num_experts_per_tok"], TOY["num_hidden_layers"]


def toy_params(published, dtype, seed=0):
    cfg = dataclasses.replace(config_from_hf(published), dtype=dtype)
    params = CausalLM(cfg).init({"params": jax.random.PRNGKey(seed)},
                                {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return cfg, jax.tree_util.tree_unflatten(  # every leaf perturbed: the gated norm's scale off one
        tree, [(a + 0.02 * jax.random.normal(k, a.shape)).astype(dtype) for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def files():
    return harness.load_reference("qwen3_next"), harness.load_architecture("qwen3_next")


@pytest.fixture(scope="module", params=["whole", "share"])
def toy(request):
    published = TOY if request.param == "whole" else SHARE
    return (published,) + toy_params(published, jnp.float32)


def engine(toy, dtype="fp32", **over):
    _, cfg, params = toy
    return InferenceEngineV2(dataclasses.replace(cfg, dtype=DTYPES[dtype]), params,
                             dict(ENGINE, dtype=dtype, kv_cache_dtype=dtype, **over))


def tokens(rows, length, seed=0):
    return np.random.default_rng(seed).integers(0, TOY["vocab_size"], (rows, length)).astype(np.int32)


def rel(got, want):
    return program.relative_error(got, want)


def pinned(files, toy, seqs, picks_by_row, params=None):
    """The reference's logits and shortfall for ``seqs`` [B, S] at the picks
    the program made for each row's first tokens (the rest keep 0..k-1: causal)."""
    reference, arch = files
    all_picks = np.broadcast_to(np.arange(K, dtype=np.int32), seqs.shape + (L, K)).copy()
    for i, p in enumerate(picks_by_row):
        all_picks[i, :len(p)] = p
    weights = arch.reference_weights(toy[2] if params is None else params)
    cfg = program.published(toy[0])
    return (np.asarray(reference.forward(weights, cfg, seqs, all_picks)),
            np.asarray(reference.route_shortfall(weights, cfg, seqs, all_picks)))


# ------------------------------------------------------------- the config
def test_the_config_is_read_from_the_published_keys():
    cfg = config_from_hf(SHARE)
    assert cfg.period == ("linear_attention",) * 3 + ("attention",) and cfg.layer_types == cfg.period * 2
    assert (cfg.attention_layers, cfg.gdn_layers, cfg.ssm_layers, cfg.state_layers, cfg.routed_layers) == (2, 6, 0, 6, 8)
    assert cfg.gdn == GDNConfig(n_k_heads=2, n_v_heads=4, head_k_dim=16, head_v_dim=16, d_conv=4, chunk_size=64)
    assert (cfg.gdn.key_dim, cfg.gdn.value_dim, cfg.gdn.conv_dim, cfg.gdn.proj_dim) == (32, 64, 128, 192)
    assert cfg.attn_output_gate and cfg.qk_norm and cfg.norm_unit_offset and cfg.rotary_dim == 8
    assert (cfg.moe_router, cfg.moe_renormalize, cfg.moe_shared_experts, cfg.moe_shared_gate) == ("softmax", True, 1, True)
    assert cfg.drop_free_moe and cfg.expert_parallel == ExpertParallel(4, 2)
    assert (cfg.num_experts, cfg.router_experts, cfg.first_expert) == (4, 16, 8)
    whole = config_from_hf(TOY)
    assert whole.expert_parallel is None and (whole.router_experts, whole.first_expert) == (16, 0)
    # layer_types written out, as the benchmark's file has it, is the interval's rule
    assert config_from_hf(dict(TOY, layer_types=["linear_attention"] * 3 + ["full_attention"]
                               + ["linear_attention"] * 3 + ["full_attention"])) == whole


def test_the_benchmark_s_configuration_counts_2_929_374_400_parameters(files):
    """Shapes alone: nothing of that size is made. ISSUE 48's arithmetic."""
    _, arch = files
    published = program.published(harness.load_config("qwen3-next-80b-a3b"))
    cfg = config_from_hf(published)
    shapes = jax.eval_shape(lambda: CausalLM(cfg).init(
        {"params": jax.random.PRNGKey(0)}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False))
    counted = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert counted == cfg.num_params() == arch.total_params(published) == 2_929_374_400
    assert arch.gdn_params(published) == 33_718_464 and arch.attention_params(published) == 27_263_488
    assert arch.expert_params(published) == 3_145_728 and arch.state_bytes(published) == 2_146_304
    assert (arch.routed_experts(published), arch.held_experts(published), cfg.router_experts) == (512, 64, 512)
    layer = shapes["params"]["layers"]["layer_0"]
    assert layer["gdn"]["gdn_in_proj"]["kernel"].shape == (3, 2048, 12288)
    assert layer["moe"]["gate"]["wg"]["kernel"].shape == (3, 2048, 512)
    assert layer["moe"]["experts"]["w_up"].shape == (3, 64, 2048, 512)
    assert shapes["params"]["layers"]["layer_3"]["attn"]["wq"]["kernel"].shape == (3, 2048, 16, 512)
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree_util.tree_leaves(shapes))


@pytest.mark.parametrize("field, bad, said", [
    ("mlp_only_layers", [1], "mlp_only_layers"), ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"), ("use_sliding_window", True, "use_sliding_window"),
    ("hidden_act", "gelu", "hidden_act"), ("layer_types", ["sliding_attention"] * 8, "layer_types")])
def test_what_the_mapping_does_not_build_is_refused_by_name(field, bad, said):
    with pytest.raises(ValueError, match="qwen3_next with.*" + said):
        config_from_hf(dict(TOY, **{field: bad}))


def test_a_share_of_size_one_is_no_share():
    """One chip that holds every expert runs the routed layer as it always
    was, to the instruction: the record of size 1 is dropped at construction."""
    from deepspeed_tpu.inference.model import _moe_with_picks

    one = config_from_hf(dict(TOY, expert_parallel={"size": 1, "rank": 0}))
    whole = config_from_hf(TOY)
    assert one.expert_parallel is None and one == whole
    _, params = toy_params(TOY, jnp.float32)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["layer_0"]["moe"])
    x = jnp.ones((2, 3, 64))
    assert str(jax.make_jaxpr(lambda p, x: _moe_with_picks(p, one, x))(lp, x)) == str(
        jax.make_jaxpr(lambda p, x: _moe_with_picks(p, whole, x))(lp, x))
    with pytest.raises(ValueError, match="0 <= rank < size"):
        ExpertParallel(4, 4)
    with pytest.raises(ValueError, match="drop-free routed layer"):
        TransformerConfig(num_experts=8, expert_parallel=ExpertParallel(2, 0))


def test_every_leaf_of_a_mixer_is_drawn_off_a_constant():
    _, params = toy_params(TOY, jnp.float32)
    mixer = params["layers"]["layer_0"]["gdn"]
    for name in ("A_log", "dt_bias", "gdn_conv"):
        assert float(jnp.std(mixer[name])) > 0.05, name
    assert float(jnp.std(params["layers"]["layer_3"]["attn"]["q_norm"]["scale"])) > 0.01


# ------------------------------------------------------------- the flax model
def test_the_flax_forward_is_the_reference_s(files, toy):
    reference, arch = files
    published, cfg, params = toy
    seqs = tokens(3, 150, seed=1)  # two chunks of the delta rule and a part
    _, logits = CausalLM(cfg).apply({"params": params}, {"input_ids": jnp.asarray(seqs)})
    want = reference.forward(arch.reference_weights(params), program.published(published), seqs)
    assert rel(logits, want) < 1e-4


def test_padded_rows_of_the_flax_forward_are_the_unpadded_ones(toy):
    _, cfg, params = toy
    seqs = tokens(2, 40, seed=2)
    mask = np.ones((2, 40), np.int32)
    mask[1, 23:] = 0
    _, padded = CausalLM(cfg).apply({"params": params}, {"input_ids": jnp.asarray(seqs), "attention_mask": jnp.asarray(mask)})
    _, short = CausalLM(cfg).apply({"params": params}, {"input_ids": jnp.asarray(seqs[1:, :23])})
    assert rel(padded[1, :23], short[0]) < 1e-5


# ------------------------------------------------------------- serving
def test_pools_are_sized_by_the_layers_that_use_them(toy):
    eng = engine(toy)
    cfg = eng.model_config
    assert eng.pool.k.shape == (2 * 64, 16, cfg.kv_heads * cfg.dims_per_head)  # the attention layers' pages
    assert eng.pools.state.ssm.shape == (6, 8, 4, 16, 16) and eng.pools.state.ssm.dtype == jnp.float32  # values on lanes
    assert eng.pools.state.conv.shape == (6, 8, 3 * 128)
    assert eng.state.state_slots == 8


@pytest.mark.parametrize("dtype, tol", [("fp32", 1e-4), ("bf16", 0.5)])
def test_put_through_the_slot_and_the_pages_is_the_reference_s_full_forward(files, toy, dtype, tol):
    """A prompt through the chunked form (padded to the call's shape), then
    tokens one at a time through the state slot and the pages, then a chunk
    that continues a sequence: logits against the reference's full forward at
    the program's own picks, and the picks against the reference's scores."""
    eng = engine(toy, dtype)
    seqs = tokens(3, 80, seed=4)
    lens = [40, 64, 33]
    fed = [[seqs[i, :n] for i, n in enumerate(lens)]]
    fed += [[seqs[i, n + s:n + s + 1] for i, n in enumerate(lens)] for s in range(3)]
    got, picks = [], [[] for _ in lens]
    for step in fed:
        logits, p = eng.put_with_picks([0, 1, 2], step)
        got.append(np.asarray(logits, np.float32))
        for i in range(3):
            assert p[i].shape == (len(step[i]), L, K) and p[i].min() >= 0 and p[i].max() < 16  # the router's numbering
            picks[i].append(p[i])
    logits, p = eng.put_with_picks([1], [seqs[1, 67:78]])  # eleven more tokens of a sequence that holds a state
    picks[1].append(p[0])
    want, shortfall = pinned(files, toy, seqs, [np.concatenate(p) for p in picks], eng.params)
    for s, step_logits in enumerate(got):
        for i, n in enumerate(lens):
            assert rel(step_logits[i], want[i, n - 1 + s]) < tol, (s, i)
    assert rel(np.asarray(logits, np.float32)[0], want[1, 77]) < tol
    fed_to = [n + 3 for n in lens]
    fed_to[1] = 78
    worst = max(shortfall[i, :n].max() for i, n in enumerate(fed_to))
    assert worst < (1e-3 if dtype == "fp32" else 3.0)


def test_a_prefill_s_rows_go_through_a_mixer_a_group_at_a_time_to_the_same_numbers(toy, monkeypatch):
    """Past ``ops/gdn.py::group_rows`` a call's rows go through a DeltaNet mixer
    (its float32 ``[q | k | v | z]`` included) a group at a time: the logits,
    the states and the tails are those of all rows at once, a dead row's slot
    included (three prompts in a call of four rows)."""
    from deepspeed_tpu.ops import gdn

    def fed(eng):
        seqs = tokens(3, 50, seed=21)
        lens = [40, 17, 33]
        logits = [eng.put([0, 1, 2], [seqs[i, :n] for i, n in enumerate(lens)])]
        logits.append(eng.put([0, 1, 2], [seqs[i, n:n + 1] for i, n in enumerate(lens)]))
        return [np.asarray(a, np.float32) for a in logits], jax.tree_util.tree_map(np.asarray, eng.pools.state)

    whole, whole_state = fed(engine(toy))
    gdn_cfg = toy[1].gdn
    monkeypatch.setattr(gdn, "_GROUP_ELEMENTS", 2 * 64 * gdn_cfg.chunk_size * gdn_cfg.n_v_heads)  # two rows a group
    assert gdn.group_rows(4, 64, gdn_cfg.chunk_size, gdn_cfg.n_v_heads) == 2
    grouped, grouped_state = fed(engine(toy))
    for a, b in zip(whole, grouped):
        assert rel(b, a) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(whole_state), jax.tree_util.tree_leaves(grouped_state)):
        np.testing.assert_allclose(b, a, atol=1e-5 * np.abs(a).max(), rtol=0)


def test_generate_keeps_a_chain_ahead_and_follows_the_reference(files, toy):
    eng = engine(toy)
    prompts = [tokens(1, n, seed=30 + n)[0] for n in (9, 40, 5, 33, 17)]  # five rows: slots 0..4, a bucket of 8
    outs, picks = eng.generate_with_picks(prompts, max_new_tokens=21)
    assert eng.chains_ahead >= 2
    seqs = np.zeros((5, 64), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        seqs[i, :len(p) + len(o)] = np.concatenate([p, o])
        assert picks[i].shape == (len(p) + len(o) - 1, L, K)
    want, shortfall = pinned(files, toy, seqs, picks)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        assert len(o) == 21
        for j, tok in enumerate(o):
            row = want[i, len(p) + j - 1]
            assert row.max() - row[tok] < 1e-3 * np.sqrt(np.mean(row ** 2)), (i, j)
        assert shortfall[i, :len(p) + 20].max() < 1e-3
    assert eng.state.state_slots_in_use == 0 and eng.state.n_active == 0


def test_a_slot_changes_hands_and_the_next_sequence_starts_from_zeros(files, toy):
    reference, arch = files
    eng = engine(toy)
    eng.put([0, 1], [tokens(1, 20, seed=50)[0], tokens(1, 9, seed=51)[0]])
    eng.flush(0)
    seq = tokens(1, 30, seed=52)
    logits, picks = eng.put_with_picks([7], [seq[0, :29]])  # takes slot 0, which holds another's state
    assert eng.state.get(7).slot == 0
    want, _ = pinned(files, toy, seq, picks)
    assert rel(logits[0], want[0, 28]) < 1e-4


def test_a_dead_row_s_slot_is_bitwise_what_it_was(toy):
    """Rows 0 and 2 decode, row 1 (a live sequence that is not fed) rides the
    program dead: state and tail of its slot come out as they went in."""
    eng = engine(toy)
    eng.put([0, 1, 2], [tokens(1, n, seed=60 + n)[0] for n in (12, 7, 20)])
    before = (np.asarray(eng.pools.state.ssm[:, 1]), np.asarray(eng.pools.state.conv[:, 1]))
    eng.put([0, 2], [np.asarray([3], np.int32), np.asarray([5], np.int32)])
    assert np.array_equal(before[0], np.asarray(eng.pools.state.ssm[:, 1]))
    assert np.array_equal(before[1], np.asarray(eng.pools.state.conv[:, 1]))
    eng.put([0, 2], [tokens(1, 9, seed=70)[0], tokens(1, 5, seed=71)[0]])  # and through the chunked form
    assert np.array_equal(before[0], np.asarray(eng.pools.state.ssm[:, 1]))
    assert np.array_equal(before[1], np.asarray(eng.pools.state.conv[:, 1]))


@pytest.mark.parametrize("over, said", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"spec_decode": 2}, "spec_decode"),
    ({"tp_size": 2}, "tp=2"),
])
def test_what_does_not_hold_with_recurrent_state_is_refused_by_name(over, said):
    with pytest.raises(ValueError, match="recurrent state.*" + said):
        engine((TOY,) + toy_params(TOY, jnp.float32), **over)


def test_migration_the_v1_engine_and_an_ep_mesh_are_refused_by_name():
    from deepspeed_tpu.inference.model import init_cache

    toy = (SHARE,) + toy_params(SHARE, jnp.float32)
    eng = engine(toy)
    eng.put([0], [tokens(1, 5)[0]])
    with pytest.raises(ValueError, match="migration of a model with recurrent state"):
        eng.export_request(0)
    with pytest.raises(NotImplementedError, match="layer pattern.*v1 engine"):
        init_cache(toy[1], 1, 32)
    with pytest.raises(ValueError, match="ONE chip's share"):
        engine(toy, ep_size=2)


def test_spans_say_whose_state_they_move_and_which_experts_they_read():
    from deepspeed_tpu.telemetry import get_tracer

    tracer = get_tracer()
    tracer.configure(enabled=True)
    tracer.reset()
    try:
        eng = engine((SHARE,) + toy_params(SHARE, jnp.float32))
        eng.generate([tokens(1, 6, seed=1)[0], tokens(1, 9, seed=2)[0]], max_new_tokens=11)
        events = [e for e in tracer.events() if e["kind"] == "span"]
    finally:
        tracer.configure(enabled=False)
        tracer.reset()
    dispatch = [e["args"] for e in events if e["name"] == "serve:dispatch"]
    assert [a["state_rows"] for a in dispatch if a.get("kind") == "prefill"] == [2]
    assert [a["state_rows"] for a in dispatch if a.get("kind") == "chain"] == [8, 8, 4]
    accept = [e["args"] for e in events if e["name"] == "serve:accept" and e["args"].get("kind") == "chain"]
    assert len(accept) == 3
    for a in accept:  # HELD experts a step reads (4 are here), and the visits they got: 2 rows x 4 picks / 4 chips
        assert 0 <= a["experts_touched"] <= 4 and 0 <= a["held_visits"] <= 8
        assert a["held_visits"] >= a["experts_touched"]
        assert a["experts_touched"] <= a["experts_read"] <= 4  # what the decode product read: pad rows' picks too
    assert any(a["held_visits"] > 0 for a in accept)


# ------------------------------------------------------- ONE CHIP'S SHARE
def _layer_and_input(rows, hidden=64, experts=32, width=32, seed=0):
    """One routed layer's parameters as the program keeps them, all ``experts`` of them, and tokens."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    n = lambda k, *shape: jax.random.normal(k, shape) * shape[-2] ** -0.5  # noqa: E731
    lp = {"gate": {"wg": {"kernel": n(keys[0], hidden, experts)}},
          "experts": {"w_gate": n(keys[1], experts, hidden, width), "w_up": n(keys[2], experts, hidden, width),
                      "w_down": n(keys[3], experts, width, hidden)},
          "shared": {"w_gate": {"kernel": n(keys[4], hidden, width)}, "w_up": {"kernel": n(keys[5], hidden, width)},
                     "w_down": {"kernel": n(keys[6], width, hidden)}},
          "shared_gate": {"kernel": n(keys[7], hidden, 1)}}
    return lp, jax.random.normal(keys[8], (1, rows, hidden))


@pytest.mark.parametrize("rows", [5, 80], ids=["every-expert-product", "sorted-dispatch"])
def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer(files, rows):
    """Over all ranks of an 8-way share of one routed layer (32 experts, 4
    held a chip, 6 a token), the parts of the result add up, with the shared
    expert (which every chip computes alike) counted once, to what the UNCUT
    reference gives for the whole layer; each part is the reference's own
    share of that rank; and the picks every rank hands out are the uncut
    router's, in its numbering. Both regimes of the program's dispatch."""
    from deepspeed_tpu.inference.model import _moe_with_picks

    reference, _ = files
    size, held, k = 8, 4, 6
    lp, x = _layer_and_input(rows)
    published = dict(TOY, num_experts=held, num_experts_per_tok=k)
    whole_cfg = dict(published, num_experts=size * held)
    ref_w = {"router": lp["gate"]["wg"]["kernel"], "shared_gate": lp["shared"]["w_gate"]["kernel"],
             "shared_up": lp["shared"]["w_up"]["kernel"], "shared_down": lp["shared"]["w_down"]["kernel"],
             "shared_w": lp["shared_gate"]["kernel"]}
    leaves = lambda lo, hi: tuple(lp["experts"][n][lo:hi] for n in reference.EXPERT_LEAVES)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.routed(x[0], ref_w, leaves(0, size * held), whole_cfg, None)
        shared = jax.nn.sigmoid(x[0] @ ref_w["shared_w"]) * reference.glu(
            x[0], ref_w["shared_gate"], ref_w["shared_up"], ref_w["shared_down"])
    uncut_picks = np.sort(np.asarray(jax.lax.top_k(x[0] @ ref_w["router"], k)[1]), axis=-1)
    total = np.zeros_like(np.asarray(uncut))
    for rank in range(size):
        cfg = config_from_hf(dict(published, expert_parallel={"size": size, "rank": rank}))
        assert (cfg.first_expert, cfg.router_experts, rows >= 2 * cfg.router_experts) == (rank * held, 32, rows == 80)
        mine = dict(lp, experts={n: a[rank * held:(rank + 1) * held] for n, a in lp["experts"].items()})
        part, picks = _moe_with_picks(mine, cfg, x)
        assert np.array_equal(np.sort(np.asarray(picks), axis=-1), uncut_picks)
        with jax.default_matmul_precision("highest"):
            ref_part, shortfall = reference.routed(
                x[0], ref_w, leaves(rank * held, (rank + 1) * held),
                dict(published, expert_parallel={"size": size, "rank": rank}), np.asarray(picks))
        assert rel(part[0], ref_part) < 1e-5 and float(shortfall.max()) <= 0
        total += np.asarray(part[0]) - np.asarray(shared)
    assert rel(total + np.asarray(shared), uncut) < 1e-5
    assert rel(total, np.asarray(uncut) - np.asarray(shared)) < 1e-5  # and it is not the shared expert that carries it


# ------------------------------------------------------- the names in a trace
def test_the_new_pieces_carry_their_names_in_serving_and_in_training():
    """Scope ``gdn`` around every DeltaNet mixer and under it the pieces, each
    the parameter key it reads or the form of the rule it runs; under ``attn``
    the head norms and the output gate; under ``moe`` the shared expert's gate:
    in the ``op_name``s of the toy's compiled ``step`` and ``chain`` and of the
    flax model's forward (HLO metadata: what the benchmark's readers match)."""
    import re

    cfg, params = toy_params(SHARE, jnp.float32)
    pools = jax.eval_shape(lambda: cache.Pools(cache.init_pool(cfg, 32, 4, jnp.float32), cache.init_state_pool(cfg, 4, jnp.float32)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    step = jax.jit(lambda p, pool, t, pos, n, bt: paged.ragged_forward(p, cfg, pool, t, pos, n, bt, 4)).lower(
        params, pools, i32(4, 16), i32(4, 16), i32(4), i32(4, 8)).compile().as_text()
    chain = jax.jit(lambda p, pool, t, pos, bt, a, b, r: paged.ragged_decode_chain(
        p, cfg, pool, t, pos, bt, 4, a, b, r, 4, None)).lower(
        params, pools, i32(4), i32(4), i32(4, 8), jax.ShapeDtypeStruct((4,), jnp.bool_), i32(4),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile().as_text()
    train = jax.jit(lambda p, t: CausalLM(cfg).apply({"params": p}, {"input_ids": t}, train=True)[0]).lower(
        params, i32(2, 16)).compile().as_text()

    def under(scope, text):
        return {name.split(f"/{scope}/", 1)[1].split("/")[0]
                for joined in re.findall(r'op_name="([^"]*)"', text) for name in joined.split(";")
                if f"/{scope}/" in name}

    shared = {"gdn_in_proj", "gdn_ba_proj", "gdn_conv", "gdn_norm", "gdn_out_proj"}
    assert under("gdn", chain) >= shared | {"gdn_update"} and "gdn_chunk" not in under("gdn", chain)
    assert under("gdn", step) >= shared | {"gdn_chunk"}
    assert under("gdn", train) >= shared | {"gdn_chunk"}
    assert re.search(r'op_name="jit\([^"]*/layer/gdn/gdn_in_proj/dot_general', chain)
    assert re.search(r'op_name="jit\([^"]*layers/layer_0/gdn/gdn_in_proj/dot_general', train)
    for text in (step, chain):
        assert under("attn", text) >= {"wq", "wk", "wv", "wo", "q_norm", "k_norm", "attn_gate", "kv_write", "rope"}
        assert under("moe", text) >= {"moe_router", "moe_experts", "moe_shared"}
        assert "/moe/moe_shared/moe_shared_gate/" in text
    # (what the backward makes again sits under ``checkpoint`` in training, as ``attn_norm`` does)
    assert "attn_gate" in under("attn", train) and all(
        f"/layer_3/attn/checkpoint/{name}/" in train for name in ("q_norm", "k_norm"))


# ------------------------------------------------------- the checkpoint's names
def test_the_traced_tool_counts_a_prefill_s_layer_calls_by_the_group():
    """``tools/traced_cell.py::gdn_parts`` on rows as ``hlo_stats`` gives them: a
    ``(128, 256)`` prefill makes its in-projection a group of 32 rows at a
    time, four a layer-call, so twelve occurrences of each of a period's three
    are nine layer-calls, and the chunked rule's share of its roofline is
    counted at nine whole shapes."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location("traced_cell", os.path.join(
        os.path.dirname(__file__), "..", "..", "..", "tools", "traced_cell.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    def row(name, scope, program, seconds, count):
        return {"hlo_op_name": name, "tf_op_name": f"jit({program})/pool_scan/while/body/layer/gdn/{scope}/dot_general:",
                "occurrences": str(float(count)), "total_self_time": str(1e6 * seconds), "category": "fusion",
                "hlo_op_expression": f"%{name} = f32[32,256,12288] fusion()"}

    rows = [row(f"fusion.{j}", "gdn_in_proj", "step", 0.03, 12) for j in range(3)]
    rows += [row("custom-call.1", "gdn_chunk", "step", 0.9, 36)]
    rows += [row(f"fusion.{9 + j}", "gdn_in_proj", "chain", 0.015, 210) for j in range(3)]
    lines = list(tool.gdn_parts(rows, "qwen3-next-80b-a3b.serve.long-output-wave128"))
    said = {line.split(" ")[0] + " " + line.split(" ")[1]: line for line in lines}
    assert "layer_calls=9 " in said["gdn_part=gdn_in_proj program=step"]
    assert "layer_calls=630 " in said["gdn_part=gdn_in_proj program=chain"]
    assert "device_s=0.8999" in said["gdn_part=gdn_chunk program=step"] and "layer_calls=9 " in said["gdn_part=gdn_chunk program=step"]
    roofline = next(line for line in lines if line.startswith("gdn_chunk_roofline="))
    assert abs(float(roofline.split(" ")[0].split("=")[1]) - 100 * 1.649e-3 * 9 / 0.9) < 0.01


def test_the_traced_tool_looks_for_the_conv_pool_and_a_layer_s_row_of_it():
    """``tools/traced_cell.py::pool_copies`` on rows as ``hlo_stats`` gives
    them: the kernels that alias a pool and a prompt's ``dynamic-update-slice``
    are its in-place updates; a copy of either pool's whole shape counts (the
    chip's compiler moving the 57 MB conv pool into its fast memory and back, a
    period: what ``conv_update`` pins its pool against), and in program
    ``chain`` so does any instruction of a layer's row of the conv pool, which
    a prompt's ``step`` makes by right."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location("traced_cell", os.path.join(
        os.path.dirname(__file__), "..", "..", "..", "tools", "traced_cell.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    def row(name, program, expression, category="fusion"):
        return {"hlo_op_name": name, "tf_op_name": f"jit({program})/pool_scan/while/body/layer/gdn/gdn_conv/x:",
                "occurrences": "8.0", "total_self_time": "100.0", "category": category,
                "hlo_op_expression": f"%{name} = {expression}"}

    sound = [row("conv_update.31", "chain", "(bf16[9,128,24576]{2,1,0}, f32[128,8192]{1,0}) custom-call()", "custom-call"),
             row("gdn_update.3", "chain", "(f32[9,128,32,128,128]{4,3,2,1,0}, f32[128,32,128]) custom-call()", "custom-call"),
             row("fusion.7", "step", "bf16[9,128,24576]{2,1,0} fusion(), calls=%dynamic-update-slice.3"),
             row("fusion.8", "step", "bf16[1,128,24576]{2,1,0} fusion()"),
             row("while.2", "chain", "(s32[], bf16[9,128,24576]{2,1,0}) while()", "while")]
    state, conv = r"f32\[9,128,32,128,128\]", (9, 128, 3, 8192)
    kernels = ("gdn_update", "conv_update")
    assert list(tool.pool_copies(sound, state, conv, kernels))[-1].startswith("state_pool_copies=0 ")
    for name, program, expression in (
            ("copy-start.63", "chain", "(bf16[9,128,24576]{2,1,0:S(1)}, bf16[9,128,24576]{2,1,0}, u32[]) copy-start()"),
            ("copy.5", "step", "f32[9,128,32,128,128]{4,3,2,1,0} copy()"),
            ("fusion.9", "chain", "bf16[1,128,24576]{2,1,0} fusion()"),
            ("select_convert_fusion", "chain", "f32[128,3,8192]{2,1,0} fusion()"),
            ("copy.17", "chain", "bf16[128,3,8192]{2,0,1} copy()")):
        lines = list(tool.pool_copies(sound + [row(name, program, expression)], state, conv, kernels))
        assert lines[-1].startswith("state_pool_copies=1 ") and lines[0].startswith(f"state_pool_copy={name} "), name


@pytest.mark.parametrize("published", [TOY, SHARE], ids=["whole", "share"])
def test_hf_names_there_and_back(published):
    """The key map on a toy checkpoint: our tree under the family's names (the
    in-projections interleaved by key-head group as the checkpoint has them,
    the convolution as torch's depthwise ``[channels, 1, taps]``, a share's
    experts under their numbers in the router) and back, leaf for leaf."""
    from deepspeed_tpu.checkpoint.hf import _ba_rows, _qkvz_rows, convert_hf_state, detect_family, qwen3_next_hf_state

    cfg, params = toy_params(published, jnp.float32)
    state = qwen3_next_hf_state(params, cfg)
    assert detect_family(state) == "qwen3_next"
    assert state["model.layers.0.linear_attn.in_proj_qkvz.weight"].shape == (192, 64)  # torch's [out, in]
    assert state["model.layers.0.linear_attn.conv1d.weight"].shape == (128, 1, 4)
    assert state["model.layers.3.self_attn.q_proj.weight"].shape == (4 * 2 * 32, 64)
    assert state["model.layers.7.mlp.gate.weight"].shape == (16, 64)
    assert state["model.layers.5.mlp.shared_expert_gate.weight"].shape == (1, 64)
    first = cfg.first_expert
    assert f"model.layers.2.mlp.experts.{first}.up_proj.weight" in state
    assert f"model.layers.2.mlp.experts.{first + cfg.num_experts}.up_proj.weight" not in state
    assert not any(k.startswith("mtp.") for k in state)
    # the interleaving, by hand: key head 1's q is the checkpoint's rows after group 0's [q | k | v v | z z]
    g = cfg.gdn
    group = 2 * g.head_k_dim + 4 * g.head_v_dim
    plain = np.asarray(params["layers"]["layer_0"]["gdn"]["gdn_in_proj"]["kernel"])[0]  # [hidden, q | k | v | z]
    stored = state["model.layers.0.linear_attn.in_proj_qkvz.weight"]
    np.testing.assert_array_equal(stored[group:group + g.head_k_dim], plain[:, g.head_k_dim:2 * g.head_k_dim].T)
    np.testing.assert_array_equal(stored[2 * g.head_k_dim:2 * g.head_k_dim + g.head_v_dim],
                                  plain[:, 2 * g.key_dim:2 * g.key_dim + g.head_v_dim].T)  # value head 0 of group 0
    assert sorted(_qkvz_rows(g)) == list(range(g.proj_dim)) and sorted(_ba_rows(g)) == list(range(2 * g.n_v_heads))
    back = convert_hf_state(state, cfg)
    flat = lambda t: jax.tree_util.tree_leaves_with_path(t)  # noqa: E731
    assert [p for p, _ in flat(back)] == [p for p, _ in flat(jax.tree_util.tree_map(np.asarray, params))]
    for (_, a), (_, b) in zip(flat(back), flat(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
