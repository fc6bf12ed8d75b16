"""The ROUTED ``granitemoehybrid`` at a toy size with the published structure (a
layer PATTERN of two periods, Mamba-2 state-space mixers around grouped-query
attention with no positional term, after EVERY mixer a softmax router over 12
experts, top-4 then softmax, beside one shared MLP of twice an expert's width
with no gate, four scalar multipliers, a tied head) against the benchmark's
plain reference ``benchmarks/reference/granitemoehybrid_routed.py``, whose
state-space layers are the sequential recurrence: the flax forward, and
``InferenceEngineV2`` through the state pool beside the page pool (``put``,
chains, a dead row, a pad token) at the program's own picks, logits and not
tokens; uncut and as EACH of the two shares of a two-way expert-parallel layer
(``expert_parallel``), whose parts add up to the uncut layer.

Tolerances. fp32: 2e-5 relative L2 of logits (read 2e-7: six layers, the
chunked form's other order of summation). bf16 at the program's own picks:
0.05 (read 0.005-0.008 at hidden 64; 0.019-0.020 on the chip at 4,096)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import harness, program
from deepspeed_tpu.checkpoint.hf import config_from_hf
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import ExpertParallel

PERIOD = ["mamba", "attention", "mamba"]
TOY = dict(
    model_type="granitemoehybrid", vocab_size=128, hidden_size=64, intermediate_size=32,
    shared_intermediate_size=64, num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=2,
    layer_types=PERIOD * 2, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1,
    mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=8, mamba_conv_bias=True, mamba_proj_bias=False,
    embedding_multiplier=12, attention_multiplier=0.0625, residual_multiplier=0.22, logits_scaling=8,
    rms_norm_eps=1e-5, tie_word_embeddings=True, position_embedding_type="nope", attention_bias=False,
    hidden_act="silu", normalization_function="rmsnorm", num_local_experts=12, num_experts_per_tok=4,
    max_position_embeddings=256, rope_scaling=None, rope_theta=10000)
SHARES = {"whole": TOY,
          "rank0": dict(TOY, num_local_experts=6, expert_parallel={"size": 2, "rank": 0}),
          "rank1": dict(TOY, num_local_experts=6, expert_parallel={"size": 2, "rank": 1})}
DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}
ENGINE = {"kv_block_size": 4, "num_kv_blocks": 96, "chunk_bucket": 8, "row_bucket": 4, "max_seq_len": 128,
          "max_seqs": 8, "decode_chain": 8, "hbm_check": "off"}
K, L = TOY["num_experts_per_tok"], TOY["num_hidden_layers"]


def toy_params(published, dtype, seed=0):
    """The flax initialiser's parameters with EVERY leaf perturbed (norm scales off one)."""
    cfg = dataclasses.replace(config_from_hf(published), dtype=dtype)
    params = CausalLM(cfg).init({"params": jax.random.PRNGKey(seed)},
                                {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return cfg, jax.tree_util.tree_unflatten(
        tree, [(a + 0.05 * jax.random.normal(k, a.shape)).astype(dtype) for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def files():
    return harness.load_reference("granitemoehybrid_routed"), harness.load_architecture("granitemoehybrid_routed")


@pytest.fixture(scope="module", params=list(SHARES))
def toy(request):
    published = SHARES[request.param]
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
    cfg = config_from_hf(SHARES["rank1"])
    assert cfg.layer_types == tuple(PERIOD * 2) and cfg.period == tuple(PERIOD)
    assert (cfg.attention_layers, cfg.ssm_layers, cfg.state_layers, cfg.routed_layers) == (2, 4, 4, 6)
    # intermediate_size is ONE expert's width, shared_intermediate_size the shared MLP's: two expert widths, no gate
    assert (cfg.expert_width, cfg.moe_shared_experts, cfg.moe_shared_gate, cfg.intermediate_size) == (32, 2, False, 64)
    assert (cfg.moe_router, cfg.moe_renormalize, cfg.moe_top_k, cfg.drop_free_moe) == ("softmax", True, 4, True)
    assert cfg.expert_parallel == ExpertParallel(2, 1)
    assert (cfg.num_experts, cfg.router_experts, cfg.first_expert) == (6, 12, 6)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.0625, 0.22, 8.0)
    whole = config_from_hf(TOY)
    assert whole.expert_parallel is None and (whole.num_experts, whole.router_experts, whole.first_expert) == (12, 12, 0)
    # the dense member is what it was: no routed field is set
    dense = config_from_hf(dict(TOY, num_local_experts=0, num_experts_per_tok=0, shared_intermediate_size=128))
    assert (dense.num_experts, dense.moe_shared_experts, dense.intermediate_size, dense.has_moe) == (0, 0, 128, False)


@pytest.mark.parametrize("cut", ["catalog-row", "the-cell-s-cut"])
def test_the_catalog_row_whole_and_cut_gives_issue_51_s_sizes(files, cut):
    """Shapes alone: nothing of that size is made. ISSUE 51's arithmetic."""
    _, arch = files
    held = program.published(harness.load_config("granite-4.0-h-small"))
    if cut == "catalog-row":  # the published row: every cut of the file taken back
        published = dict(held, **{r["key"]: r["published"] for r in harness.load_config("granite-4.0-h-small")["reduced"]})
        assert published["layer_types"] == held["layer_types"] * 4
        del published["expert_parallel"]
        want, router, first, periods = 32_207_337_984, 72, 0, 4
    else:
        published, want, router, first, periods = held, 4_757_211_776, 72, 0, 1
    cfg = config_from_hf(published)
    shapes = jax.eval_shape(lambda: CausalLM(cfg).init(
        {"params": jax.random.PRNGKey(0)}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False))
    counted = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert counted == cfg.num_params() == arch.total_params(published) == want
    assert (cfg.router_experts, cfg.first_expert, cfg.moe_top_k, cfg.expert_width) == (router, first, 10, 768)
    assert len(cfg.period) == 10 and cfg.period.count("attention") == 1 and cfg.period[5] == "attention"
    assert arch.ssm_params(published) == 102_286_976 and arch.attention_params(published) == 41_943_040
    assert arch.expert_params(published) == 9_437_184 and arch.shared_params(published) == 18_874_368
    assert arch.router_params(published) == 294_912 and arch.state_bytes(published) == 4_244_992
    layer = shapes["params"]["layers"]["layer_0"]
    E = published["num_local_experts"]
    assert layer["ssm"]["ssm_in_proj"]["kernel"].shape == (periods, 4096, 16768)
    assert layer["moe"]["gate"]["wg"]["kernel"].shape == (periods, 4096, 72)
    assert layer["moe"]["experts"]["w_up"].shape == (periods, E, 4096, 768)
    assert layer["moe"]["shared"]["w_down"]["kernel"].shape == (periods, 1536, 4096) and "shared_gate" not in layer["moe"]
    assert shapes["params"]["layers"]["layer_5"]["attn"]["wq"]["kernel"].shape == (periods, 4096, 32, 128)
    assert shapes["params"]["embed"]["embedding"].shape == (published["vocab_size"], 4096)
    # the file names no top-level dtype: the leaves are DRAWN in float32 and the harness rounds them to bf16 once
    # (normals drawn in bf16 carry a mean of -1.77% of a standard deviation in every matrix: ``assumed.dtype``)
    assert all(a.dtype == jnp.float32 for a in jax.tree_util.tree_leaves(shapes))


@pytest.mark.parametrize("field, bad, said", [
    ("num_experts_per_tok", 0, "num_experts_per_tok=0"), ("num_experts_per_tok", 13, "num_experts_per_tok=13"),
    ("shared_intermediate_size", 48, "shared_intermediate_size not a multiple"),
    ("position_embedding_type", "rope", "position_embedding_type"), ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("attention_bias", True, "attention_bias"), ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("mamba_conv_bias", False, "mamba_conv_bias"), ("hidden_act", "gelu", "hidden_act"),
    ("normalization_function", "layernorm", "normalization_function"), ("mamba_expand", 3, "mamba_expand")])
def test_what_the_mapping_does_not_build_is_refused_by_name(field, bad, said):
    with pytest.raises(ValueError, match="granitemoehybrid with.*" + said):
        config_from_hf(dict(TOY, **{field: bad}))


@pytest.mark.parametrize("over, said", [
    ({"prefix_cache": True}, "prefix_cache"), ({"spec_decode": 2}, "spec_decode"), ({"tp_size": 2}, "tp=2")])
def test_what_does_not_hold_with_recurrent_state_is_refused_by_name(over, said):
    with pytest.raises(ValueError, match="recurrent state.*" + said):
        engine((TOY,) + toy_params(TOY, jnp.float32), **over)


def test_the_v1_engine_and_an_ep_mesh_are_refused_by_name():
    from deepspeed_tpu.inference.model import init_cache

    toy = (SHARES["rank0"],) + toy_params(SHARES["rank0"], jnp.float32)
    with pytest.raises(NotImplementedError, match="layer pattern.*v1 engine"):
        init_cache(toy[1], 1, 32)
    with pytest.raises(ValueError, match="ONE chip's share"):
        engine(toy, ep_size=2)


# ------------------------------------------------------------- the router
def test_top_k_then_softmax_is_the_program_s_softmax_renormalised_over_the_picks(files):
    """The published code takes the ten largest LOGITS and a softmax over
    them; the program's ``route`` takes a softmax over all and divides the
    picks' by their sum. The same picks, the same weights."""
    from deepspeed_tpu.parallel.moe import route

    reference, _ = files
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(3), (50, 72))
    top_p, top_i = route(logits, 10, kind="softmax", renormalize=True)
    picked, picks = jax.lax.top_k(logits, 10)
    assert np.array_equal(np.sort(np.asarray(top_i), -1), np.sort(np.asarray(picks), -1))
    gate = np.zeros((50, 72), np.float32)
    np.put_along_axis(gate, np.asarray(top_i), np.asarray(top_p), axis=-1)
    np.testing.assert_allclose(gate, reference.gates(logits, picks), atol=1e-6)
    np.testing.assert_allclose(np.take_along_axis(gate, np.asarray(picks), -1), jax.nn.softmax(picked, -1), atol=1e-6)


# ------------------------------------------------------------- the flax model
def test_the_flax_forward_is_the_reference_s(files, toy):
    reference, arch = files
    published, cfg, params = toy
    seqs = tokens(3, 29, seed=1)  # three chunks of the scan and a part
    _, logits = CausalLM(cfg).apply({"params": params}, {"input_ids": jnp.asarray(seqs)})
    want = reference.forward(arch.reference_weights(params), program.published(published), seqs)
    assert rel(logits, want) < 2e-5


# ------------------------------------------------------------- serving
def test_pools_are_sized_by_the_layers_that_use_them(toy):
    eng = engine(toy)
    cfg = eng.model_config
    assert eng.pool.k.shape == (2 * 96, 4, cfg.kv_heads * cfg.dims_per_head)  # the attention layers' pages
    assert eng.pools.state.ssm.shape[:2] == (4, 8) and eng.pools.state.ssm.dtype == jnp.float32
    assert eng.pools.state.conv.shape == (4, 8, 3 * 160)
    assert eng.state.state_slots == 8


@pytest.mark.parametrize("dtype, tol", [("fp32", 2e-5), ("bf16", 0.05)])
def test_put_through_the_slot_and_the_pages_is_the_reference_s_full_forward(files, toy, dtype, tol):
    """A prompt through the chunked scan (padded to the call's shape: pad
    tokens ride every layer and its router without moving a state), then
    tokens one at a time through the state slot and the pages with ONE ROW
    DEAD (a live sequence that is not fed), then a chunk that continues a
    sequence: logits against the reference's full forward at the program's own
    picks, and the picks against the reference's scores."""
    eng = engine(toy, dtype)
    seqs = tokens(3, 60, seed=4)
    lens = [21, 40, 13]  # none a whole number of chunks of 8: every row is padded
    logits, p = eng.put_with_picks([0, 1, 2], [seqs[i, :n] for i, n in enumerate(lens)])
    got, picks = [np.asarray(logits, np.float32)], [[p[i]] for i in range(3)]
    for i in range(3):
        assert p[i].shape == (lens[i], L, K) and p[i].min() >= 0 and p[i].max() < 12  # the router's numbering
    before = (np.asarray(eng.pools.state.ssm[:, 1]), np.asarray(eng.pools.state.conv[:, 1]))
    for s in range(3):  # rows 0 and 2 decode, row 1 rides the program dead
        logits, p = eng.put_with_picks([0, 2], [seqs[i, lens[i] + s:lens[i] + s + 1] for i in (0, 2)])
        got.append(np.asarray(logits, np.float32))
        picks[0].append(p[0])
        picks[2].append(p[1])
    assert np.array_equal(before[0], np.asarray(eng.pools.state.ssm[:, 1]))  # bitwise what it was
    assert np.array_equal(before[1], np.asarray(eng.pools.state.conv[:, 1]))
    logits, p = eng.put_with_picks([1], [seqs[1, 40:51]])  # eleven more tokens of the sequence that sat still
    picks[1].append(p[0])
    want, shortfall = pinned(files, toy, seqs, [np.concatenate(p) for p in picks], eng.params)
    for i, n in enumerate(lens):
        assert rel(got[0][i], want[i, n - 1]) < tol, i
    for s in range(3):
        for j, i in enumerate((0, 2)):
            assert rel(got[1 + s][j], want[i, lens[i] + s]) < tol, (s, i)
    assert rel(np.asarray(logits, np.float32)[0], want[1, 50]) < tol
    fed_to = [lens[0] + 3, 51, lens[2] + 3]
    worst = max(shortfall[i, :n].max() for i, n in enumerate(fed_to))
    assert worst < (1e-3 if dtype == "fp32" else 1.0)


def test_a_pad_token_moves_no_state_and_no_logit(toy):
    """The same prompt in a call padded to 16 and in one padded to 24 (another
    row's length sets the chunk): the same logits, the same state and tail."""
    seq = tokens(1, 13, seed=9)[0]
    alone, beside = engine(toy), engine(toy)
    a = alone.put([0], [seq])
    b = beside.put([0, 1], [seq, tokens(1, 22, seed=10)[0]])
    assert rel(b[0], a[0]) < 1e-5
    for x, y in zip((alone.pools.state.ssm[:, 0], alone.pools.state.conv[:, 0]),
                    (beside.pools.state.ssm[:, 0], beside.pools.state.conv[:, 0])):
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-5 * float(jnp.abs(x).max()), rtol=0)


def test_generate_keeps_a_chain_ahead_and_follows_the_reference(files, toy):
    eng = engine(toy)
    prompts = [tokens(1, n, seed=30 + n)[0] for n in (9, 24, 5, 17, 12)]  # five rows: slots 0..4, a bucket of 8
    outs, picks = eng.generate_with_picks(prompts, max_new_tokens=21)
    assert eng.chains_ahead >= 2
    seqs = np.zeros((5, 48), np.int32)
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


def test_spans_carry_state_rows_and_the_three_counts_of_a_share_on_one_model():
    """``state_rows`` on ``serve:dispatch`` (a model with recurrent state) and
    ``experts_touched``, ``experts_read``, ``held_visits`` on ``serve:accept`` (a
    share of a routed layer), for the first time on ONE model: nothing new was
    built for it, and this says so."""
    from deepspeed_tpu.telemetry import get_tracer

    tracer = get_tracer()
    tracer.configure(enabled=True)
    tracer.reset()
    try:
        eng = engine((SHARES["rank0"],) + toy_params(SHARES["rank0"], jnp.float32))
        eng.generate([tokens(1, 6, seed=1)[0], tokens(1, 9, seed=2)[0]], max_new_tokens=11)
        events = [e for e in tracer.events() if e["kind"] == "span"]
    finally:
        tracer.configure(enabled=False)
        tracer.reset()
    dispatch = [e["args"] for e in events if e["name"] == "serve:dispatch"]
    assert [a["state_rows"] for a in dispatch if a.get("kind") == "prefill"] == [2]
    assert [a["state_rows"] for a in dispatch if a.get("kind") == "chain"] == [16, 4]
    accept = [e["args"] for e in events if e["name"] == "serve:accept" and e["args"].get("kind") == "chain"]
    assert len(accept) == 2
    for a in accept:  # HELD experts a step reads (6 are here), and the visits they got: 2 rows x 4 picks / 2 chips
        assert {"experts_touched", "experts_read", "held_visits"} <= set(a)
        assert 0 <= a["experts_touched"] <= 6 and 0 <= a["held_visits"] <= 8
        assert a["held_visits"] >= a["experts_touched"]
        assert a["experts_touched"] <= a["experts_read"] <= 6  # what the decode product read: pad rows' picks too
    assert any(a["held_visits"] > 0 for a in accept)


# ------------------------------------------------------- ONE CHIP'S SHARE
def _layer_and_input(rows, hidden=64, experts=12, width=32, seed=0):
    """One routed layer's parameters as the program keeps them, all ``experts`` of them, and tokens."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda k, *shape: jax.random.normal(k, shape) * shape[-2] ** -0.5  # noqa: E731
    lp = {"gate": {"wg": {"kernel": n(keys[0], hidden, experts)}},
          "experts": {"w_gate": n(keys[1], experts, hidden, width), "w_up": n(keys[2], experts, hidden, width),
                      "w_down": n(keys[3], experts, width, hidden)},
          "shared": {"w_gate": {"kernel": n(keys[4], hidden, 2 * width)}, "w_up": {"kernel": n(keys[5], hidden, 2 * width)},
                     "w_down": {"kernel": n(keys[6], 2 * width, hidden)}}}
    return lp, jax.random.normal(keys[7], (1, rows, hidden))


@pytest.mark.parametrize("rows", [5, 40], ids=["every-expert-product", "sorted-dispatch"])
def test_the_two_ranks_terms_and_the_shared_mlp_once_add_up_to_the_uncut_layer(files, rows):
    """Over both ranks of a two-way share of one routed layer (12 experts, 6
    held a chip, 4 a token), the routed terms add up, with the shared MLP
    (which both chips compute alike) counted once, to what the UNCUT reference
    gives for the whole layer; each part is the reference's own share of that
    rank; and the picks both ranks hand out are the uncut router's, in its
    numbering. Both regimes of the program's dispatch (``T >= 2 x 12`` or not)."""
    from deepspeed_tpu.inference.model import _moe_with_picks

    reference, _ = files
    size, held, k = 2, 6, 4
    lp, x = _layer_and_input(rows)
    ref_w = {"router": lp["gate"]["wg"]["kernel"], "shared_gate": lp["shared"]["w_gate"]["kernel"],
             "shared_up": lp["shared"]["w_up"]["kernel"], "shared_down": lp["shared"]["w_down"]["kernel"]}
    leaves = lambda lo, hi: tuple(lp["experts"][n][lo:hi] for n in reference.EXPERT_LEAVES)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.routed(x[0], ref_w, leaves(0, size * held), TOY, None)
        shared = reference.glu(x[0], ref_w["shared_gate"], ref_w["shared_up"], ref_w["shared_down"])
    uncut_picks = np.sort(np.asarray(jax.lax.top_k(x[0] @ ref_w["router"], k)[1]), axis=-1)
    total = np.zeros_like(np.asarray(uncut))
    for rank in range(size):
        published = dict(TOY, num_local_experts=held, expert_parallel={"size": size, "rank": rank})
        cfg = config_from_hf(published)
        assert (cfg.first_expert, cfg.router_experts, rows >= 2 * cfg.router_experts) == (rank * held, 12, rows == 40)
        mine = dict(lp, experts={n: a[rank * held:(rank + 1) * held] for n, a in lp["experts"].items()})
        part, picks = _moe_with_picks(mine, cfg, x)
        assert np.array_equal(np.sort(np.asarray(picks), axis=-1), uncut_picks)
        with jax.default_matmul_precision("highest"):
            ref_part, shortfall = reference.routed(x[0], ref_w, leaves(rank * held, (rank + 1) * held), published,
                                                   np.asarray(picks))
        assert rel(part[0], ref_part) < 1e-5 and float(shortfall.max()) <= 0
        total += np.asarray(part[0]) - np.asarray(shared)
    assert rel(total + np.asarray(shared), uncut) < 1e-5
    assert rel(total, np.asarray(uncut) - np.asarray(shared)) < 1e-5  # and it is not the shared MLP that carries it


def test_the_reference_imports_nothing_of_the_program_and_reads_the_dense_one_beside_it(files):
    import os

    reference, _ = files
    src = open(os.path.join(harness.BENCH_DIR, "reference", "granitemoehybrid_routed.py")).read()
    assert "deepspeed_tpu" not in src.split('"""', 2)[2] and "benchmarks." not in src.split('"""', 2)[2]
    assert reference.dense.__file__ == os.path.join(harness.BENCH_DIR, "reference", "granitemoehybrid.py")
