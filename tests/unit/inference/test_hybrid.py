"""``granitemoehybrid`` at a toy size with the published structure (a layer
PATTERN of two periods, Mamba-2 state-space mixers around grouped-query
attention with no positional term, four scalar multipliers, a tied head)
against the benchmark's plain reference ``benchmarks/reference/
granitemoehybrid.py``, whose state-space layers are the sequential recurrence:
the flax forward, ``train_batch``'s first loss and gradients, and
``InferenceEngineV2`` through the state pool beside the page pool (``put``, the
fused prefill, ``decode_chain`` with a chain ahead, rows that end inside a
chain, a slot that changes hands, preemption, the refusals).

Tolerances. fp32: 2e-5 relative L2 of logits (read 1e-7 to 4e-7: six layers,
the chunked form's other order of summation). bf16 through the cache: 0.05."""

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

PERIOD = ["mamba", "attention", "mamba"]
TOY = dict(
    model_type="granitemoehybrid", vocab_size=128, hidden_size=64, intermediate_size=128,
    shared_intermediate_size=128, num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=2,
    layer_types=PERIOD * 2, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1,
    mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=8, mamba_conv_bias=True, mamba_proj_bias=False,
    embedding_multiplier=12, attention_multiplier=0.0625, residual_multiplier=0.22, logits_scaling=8,
    rms_norm_eps=1e-5, tie_word_embeddings=True, position_embedding_type="nope", attention_bias=False,
    hidden_act="silu", normalization_function="rmsnorm", num_local_experts=0, num_experts_per_tok=0,
    max_position_embeddings=256, rope_scaling=None, rope_theta=10000)
DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}
ENGINE = {"kv_block_size": 4, "num_kv_blocks": 96, "chunk_bucket": 8, "row_bucket": 4, "max_seq_len": 128,
          "max_seqs": 8, "decode_chain": 8, "hbm_check": "off"}


def toy_params(dtype, seed=0):
    """The flax initialiser's parameters with EVERY leaf perturbed (norm scales off one)."""
    cfg = dataclasses.replace(config_from_hf(TOY), dtype=dtype)
    params = CausalLM(cfg).init({"params": jax.random.PRNGKey(seed)},
                                {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return cfg, jax.tree_util.tree_unflatten(
        tree, [(a + 0.05 * jax.random.normal(k, a.shape)).astype(dtype) for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def files():
    return harness.load_reference("granitemoehybrid"), harness.load_architecture("granitemoehybrid")


@pytest.fixture(scope="module")
def toy():
    return toy_params(jnp.float32)


@pytest.fixture(scope="module")
def want(files, toy):
    """The reference's logits for ``tokens`` [B, S], on the toy's weights."""
    reference, arch = files
    weights = arch.reference_weights(toy[1])
    run = jax.jit(lambda t: reference.forward(weights, program.published(TOY), t))
    return lambda tokens: np.asarray(run(jnp.asarray(tokens)))


def engine(toy, dtype="fp32", **over):
    cfg, params = toy
    return InferenceEngineV2(dataclasses.replace(cfg, dtype=DTYPES[dtype]), params,
                             dict(ENGINE, dtype=dtype, **over))


def tokens(rows, length, seed=0):
    return np.random.default_rng(seed).integers(0, TOY["vocab_size"], (rows, length)).astype(np.int32)


def rel(got, want):
    return program.relative_error(got, want)


# ------------------------------------------------------------- the flax model
def test_the_config_is_read_from_the_published_keys():
    cfg = config_from_hf(TOY)
    assert cfg.layer_types == tuple(PERIOD * 2) and cfg.period == tuple(PERIOD)
    assert (cfg.attention_layers, cfg.ssm_layers) == (2, 4)
    assert cfg.position == "none" and cfg.tie_embeddings
    assert (cfg.embedding_multiplier, cfg.attention_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.0625, 0.22, 8.0)
    s = cfg.ssm
    assert (s.n_heads, s.head_dim, s.d_state, s.n_groups, s.d_conv, s.chunk_size) == (8, 16, 16, 1, 4, 8)
    assert (s.d_inner, s.conv_dim, s.proj_dim) == (128, 160, 296)
    assert cfg.intermediate_size == 128


def test_the_catalog_row_counts_3_191_396_096_parameters(files):
    """Shapes alone: nothing of that size is made."""
    _, arch = files
    published = program.published(harness.load_config("granite-4.0-h-micro"))
    cfg = config_from_hf(published)
    shapes = jax.eval_shape(lambda: CausalLM(cfg).init(
        {"params": jax.random.PRNGKey(0)}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False))
    counted = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert counted == cfg.num_params() == arch.total_params(published) == 3_191_396_096
    assert len(cfg.period) == 10 and cfg.period.count("attention") == 1
    assert shapes["params"]["layers"]["layer_0"]["ssm"]["ssm_in_proj"]["kernel"].shape == (4, 2048, 8512)
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree_util.tree_leaves(shapes))


@pytest.mark.parametrize("field, bad", [
    ("num_local_experts", 8), ("position_embedding_type", "rope"), ("mamba_proj_bias", True),
    ("hidden_act", "gelu"), ("mamba_expand", 3)])
def test_what_the_mapping_does_not_build_is_refused_by_name(field, bad):
    with pytest.raises(ValueError, match=field.replace("_", ".")):
        config_from_hf(dict(TOY, **{field: bad}))


def test_every_leaf_of_a_mixer_is_drawn_off_a_constant():
    cfg = config_from_hf(TOY)
    params = CausalLM(cfg).init({"params": jax.random.PRNGKey(3)},
                                {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    mixer = params["layers"]["layer_0"]["ssm"]
    for name in ("A_log", "dt_bias", "D"):
        leaf = np.asarray(mixer[name])
        assert leaf.std() > 0.05 and np.abs(leaf).min() > 1e-3, name
    assert np.abs(np.asarray(mixer["ssm_conv"]["bias"])).min() > 0


def test_the_flax_forward_and_loss_are_the_reference_s(files, toy, want):
    reference, arch = files
    cfg, params = toy
    batch = tokens(3, 29, seed=1)  # three chunks and a part
    with jax.default_matmul_precision("highest"):
        loss, logits = CausalLM(cfg).apply({"params": params}, {"input_ids": jnp.asarray(batch)}, train=False)
    assert rel(logits, want(batch)) < 2e-5
    ref_loss = reference.loss(arch.reference_weights(params), program.published(TOY), batch)
    assert abs(float(loss) - float(ref_loss)) < 2e-5 * float(ref_loss)


def test_gradients_of_the_chunked_model_are_the_recurrence_s(files, toy):
    reference, arch = files
    cfg, params = toy
    batch = tokens(2, 19, seed=2)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: CausalLM(cfg).apply({"params": p}, {"input_ids": jnp.asarray(batch)},
                                                     train=True)[0])(params)
        ref = jax.grad(lambda w: reference.loss(w, program.published(TOY), batch))(arch.reference_weights(params))
    relabelled = arch.reference_weights(got)
    flat_got = jax.tree_util.tree_leaves_with_path(relabelled)
    flat_ref = dict(jax.tree_util.tree_leaves_with_path(ref))
    assert len(flat_got) == len(flat_ref)
    for path, g in flat_got:
        w = np.asarray(flat_ref[path])
        if jax.tree_util.keystr(path) == "['embed']":
            continue  # the same leaf as the head: compared below, as their sum
        np.testing.assert_allclose(np.asarray(g), w, atol=2e-4 * np.abs(w).max(), rtol=0,
                                   err_msg=jax.tree_util.keystr(path))
    np.testing.assert_allclose(np.asarray(got["embed"]["embedding"]), np.asarray(ref["embed"]),
                               atol=2e-4 * np.abs(np.asarray(ref["embed"])).max(), rtol=0)


def test_train_batch_s_first_loss_is_the_reference_s(files):
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import causal_lm_spec

    reference, arch = files
    cfg = config_from_hf(TOY)
    eng, *_ = deepspeed_tpu.initialize(
        model=causal_lm_spec(cfg),
        config={"train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    params = jax.tree_util.tree_map(np.asarray, eng.state.params)
    batch = tokens(8, 24, seed=3)
    loss = float(eng.train_batch({"input_ids": batch})["loss"])
    ref_loss = float(reference.loss(arch.reference_weights(params), program.published(TOY), batch))
    assert abs(loss - ref_loss) < 1e-4 * ref_loss
    assert float(eng.train_batch({"input_ids": batch})["loss"]) < loss  # and it learns


# ------------------------------------------------------------- the state pool
def test_pools_are_sized_by_the_layers_that_use_them(toy):
    eng = engine(toy)
    cfg = eng.model_config
    assert eng.pool.k.shape == (2 * 96, 4, cfg.kv_heads * cfg.dims_per_head)  # the attention layers' pages
    assert eng.pools.state.ssm.shape == (4, 8, 1, 16, 128) and eng.pools.state.ssm.dtype == jnp.float32  # channels on lanes
    assert eng.pools.state.conv.shape == (4, 8, 3 * 160)
    assert eng.kv_bytes_per_token == 2 * 2 * cfg.kv_heads * cfg.dims_per_head * 4


@pytest.mark.parametrize("dtype, tol", [("fp32", 2e-5), ("bf16", 0.05)])
def test_put_through_the_state_is_the_reference_s_full_forward(toy, want, dtype, tol):
    """A prompt of several chunks and a part, padded to the call's shape; one
    token at a time; a chunk that continues a sequence."""
    eng = engine(toy, dtype)
    seqs = tokens(3, 48, seed=4)
    lens = [13, 5, 22]
    ref = want(seqs)
    got = eng.put([0, 1, 2], [seqs[i, :n] for i, n in enumerate(lens)])
    assert max(rel(got[i], ref[i, n - 1]) for i, n in enumerate(lens)) < tol
    got = eng.put([2, 0], [seqs[2, 22:23], seqs[0, 13:14]])  # another order than the slots'
    assert max(rel(got[0], ref[2, 22]), rel(got[1], ref[0, 13])) < tol
    got = eng.put([1], [seqs[1, 5:16]])  # eleven more tokens of a sequence that holds a state
    assert rel(got[0], ref[1, 15]) < tol


def test_a_prefill_and_64_tokens_of_chains_follow_the_reference(toy, want):
    """The fused path's own tokens against the reference's full forward of
    the same sequence: every token a chain emits is the reference's pick (or
    within round-off of it), and the logits read back through the state after
    every chain are the reference's at that position."""
    eng = engine(toy)
    prompts = [tokens(1, n, seed=10 + n)[0] for n in (11, 17, 6)]
    uids = [0, 1, 2]
    logits = eng.put(uids, prompts)
    seqs = [list(p) + [int(np.argmax(row))] for p, row in zip(prompts, logits)]
    rng = jax.random.PRNGKey(0)
    for chain in range(7):
        out, emitted, rng = eng.decode_chain(uids, [s[-1] for s in seqs], [8] * 3, 8, rng)
        assert (emitted == 8).all()
        for s, row in zip(seqs, out):
            s.extend(int(t) for t in row)
        # the last token of a chain is fed by hand: its logits come back
        logits = eng.put(uids, [np.asarray(s[-1:], np.int32) for s in seqs])
        length = max(map(len, seqs))
        ref = want(np.stack([np.pad(s, (0, length - len(s))) for s in seqs]))
        for i, s in enumerate(seqs):
            assert rel(logits[i], ref[i, len(s) - 1]) < 2e-5, (chain, i)
            for pos in range(len(prompts[i]), len(s)):  # every token generated so far
                row = ref[i, pos - 1]
                assert row.max() - row[s[pos]] < 1e-4 * np.sqrt(np.mean(row ** 2)), (chain, i, pos)
            s.append(int(np.argmax(logits[i])))
    assert min(map(len, seqs)) - max(map(len, prompts)) >= 47 and len(seqs[2]) - 6 == 1 + 7 * 9


def test_generate_keeps_a_chain_ahead_and_matches_the_reference(toy, want):
    eng = engine(toy)
    prompts = [tokens(1, n, seed=30 + n)[0] for n in (9, 14, 5, 12, 7)]  # five rows: slots 0..4, a bucket of 8
    outs = eng.generate(prompts, max_new_tokens=25)
    assert eng.chains_ahead >= 2
    for p, o in zip(prompts, outs):
        full = np.concatenate([p, o])
        ref = want(full[None])[0]
        for j, tok in enumerate(o):
            row = ref[len(p) + j - 1]
            assert row.max() - row[tok] < 1e-4 * np.sqrt(np.mean(row ** 2))
    assert eng.state.state_slots_in_use == 0 and eng.state.n_active == 0


def _slot(eng, uid):
    slot = eng.state.get(uid).slot
    return np.asarray(eng.pools.state.ssm[:, slot]), np.asarray(eng.pools.state.conv[:, slot])


def test_rows_that_end_inside_a_chain_stop_moving_their_slot(toy):
    """Budgets 1, 3 and 8 in one chain of 8: a row's slot afterwards is what
    the same row alone reaches in as many steps; a row with no budget and a
    sequence that is not in the call keep theirs to the bit."""
    seqs = tokens(4, 12, seed=5)

    def prefilled():
        eng = engine(toy)
        logits = eng.put([0, 1, 2, 3], list(seqs))
        return eng, [int(np.argmax(row)) for row in logits]

    eng, first = prefilled()
    bystander = _slot(eng, 3)
    idle = _slot(eng, 2)
    eng.decode_chain([0, 1, 2], first[:3], [1, 8, 0], 8, jax.random.PRNGKey(0))
    for got, was in zip(_slot(eng, 3), bystander):
        assert np.array_equal(got, was)  # slot 3: a pad row of this program
    for got, was in zip(_slot(eng, 2), idle):
        assert np.array_equal(got, was)  # no budget: dead from the first step
    assert eng.state.get(0).seen_tokens == 13 and eng.state.get(1).seen_tokens == 20
    ended = _slot(eng, 0)

    alone, first = prefilled()
    alone.decode_chain([0], first[:1], [1], 1, jax.random.PRNGKey(0))  # one step, and no more
    for got, w in zip(ended, _slot(alone, 0)):
        np.testing.assert_allclose(got, w, atol=1e-6)


def test_a_slot_changes_hands_and_the_next_sequence_starts_from_zeros(toy, want):
    eng = engine(toy)
    a, b = tokens(2, 15, seed=6)
    eng.put([7], [a])
    assert eng.state.get(7).slot == 0 and eng.state.state_slots_in_use == 1
    assert np.abs(np.asarray(eng.pools.state.ssm[:, 0])).max() > 0
    eng.flush(7)
    assert eng.state.state_slots_in_use == 0
    got = eng.put([8], [b])  # the lowest free slot: the one just given back, as it was left
    assert eng.state.get(8).slot == 0
    assert rel(got[0], want(b[None])[0, -1]) < 2e-5


def test_a_full_house_refuses_a_ninth_sequence(toy):
    eng = engine(toy)
    for uid in range(8):
        eng.put([uid], [tokens(1, 3, seed=uid)[0]])
    assert eng.state.state_slots_in_use == 8
    assert not eng.can_schedule([99], [3])
    eng.flush(4)
    assert eng.can_schedule([99], [3])
    eng.put([99], [tokens(1, 3)[0]])
    assert eng.state.get(99).slot == 4


def test_a_preempted_row_is_resumed_from_an_empty_state(toy, want):
    """A pool too small for three requests at once: the youngest is flushed
    (slot and pages given back) and fed again whole; its tokens are those of an
    engine with room for all."""
    prompts = [tokens(1, n, seed=40 + n)[0] for n in (10, 9, 11)]
    roomy = engine(toy).generate(prompts, max_new_tokens=20)
    eng = engine(toy, num_kv_blocks=20, flight_recorder=True)  # 80 token slots for 93 tokens of context
    tight = eng.generate(prompts, max_new_tokens=20)
    assert sum(r.preemptions for r in eng.lifecycle.records().values()) > 0
    for r, t in zip(roomy, tight):
        assert np.array_equal(r, t)
    assert eng.state.state_slots_in_use == 0


@pytest.mark.parametrize("over, said", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"spec_decode": 2}, "spec_decode"),
    ({"tp_size": 2}, "tp=2"),
])
def test_what_does_not_hold_with_recurrent_state_is_refused_by_name(toy, over, said):
    with pytest.raises(ValueError, match="recurrent state.*" + said):
        engine(toy, **over)


def test_migration_and_the_v1_engine_are_refused_by_name(toy):
    from deepspeed_tpu.inference.model import init_cache

    eng = engine(toy)
    eng.put([0], [tokens(1, 5)[0]])
    with pytest.raises(ValueError, match="migration of a model with recurrent state"):
        eng.export_request(0)
    with pytest.raises(ValueError, match="migration into a model with recurrent state"):
        eng.import_request(1, {})
    with pytest.raises(NotImplementedError, match="layer pattern.*v1 engine"):
        init_cache(toy[0], 1, 32)


def test_dispatch_spans_say_whose_state_they_move(toy):
    from deepspeed_tpu.telemetry import get_tracer

    tracer = get_tracer()
    tracer.configure(enabled=True)
    tracer.reset()
    try:
        eng = engine(toy)
        eng.generate([tokens(1, 6, seed=1)[0], tokens(1, 9, seed=2)[0]], max_new_tokens=11)
        spans = [e for e in tracer.events() if e["kind"] == "span" and e["name"] == "serve:dispatch"]
    finally:
        tracer.configure(enabled=False)
        tracer.reset()
    prefill = [e["args"] for e in spans if e["args"].get("kind") == "prefill"]
    chains = [e["args"] for e in spans if e["args"].get("kind") == "chain"]
    assert [a["state_rows"] for a in prefill] == [2]
    assert [a["state_rows"] for a in chains] == [16, 4]  # ten tokens a row after the prefill's: 8 + 2


# ------------------------------------------------- what must NOT have moved
def test_a_model_without_a_pattern_keeps_its_tree_and_is_handed_one_pool():
    from .test_inference_v2 import make_model

    cfg, _, params = make_model()
    assert cfg.layer_types is None and cfg.period is None and cfg.attention_layers == cfg.num_layers
    assert "layer_0" not in params["layers"] and "attn" in params["layers"]
    eng = InferenceEngineV2(cfg, params, dict(ENGINE, dtype="fp32"))
    assert eng.pools.state is None and eng.pools.ring is None  # its programs are handed the page pool's arrays
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(eng.pools), jax.tree_util.tree_leaves(eng.pool)))
    assert len(jax.tree_util.tree_leaves(eng.pools)) == 2
    assert eng.state.state_slots is None and eng.state.get_or_create(0).slot is None


# ------------------------------------------------------- the names in a trace
def test_a_mixer_s_pieces_carry_the_same_names_in_serving_and_in_training(toy):
    """Scope ``ssm`` around every state-space mixer and under it the pieces,
    each the parameter key it reads or the form of the recurrence it runs: in
    the ``op_name``s of the toy's compiled ``step`` and ``chain`` and of the
    flax model's forward (HLO metadata: what the benchmark's readers match)."""
    import re

    cfg, params = toy
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

    def under_ssm(text):
        return {tuple(name.split("/ssm/", 1)[1].split("/")[:1])[0]
                for joined in re.findall(r'op_name="([^"]*)"', text) for name in joined.split(";")
                if "/ssm/" in name}

    shared = {"ssm_in_proj", "ssm_conv", "ssm_norm", "ssm_out_proj"}
    assert under_ssm(chain) >= shared | {"ssm_update"} and "ssm_scan" not in under_ssm(chain)
    assert under_ssm(step) >= shared | {"ssm_scan"}
    assert under_ssm(train) >= shared | {"ssm_scan"}
    assert re.search(r'op_name="jit\([^"]*/layer/ssm/ssm_in_proj/dot_general', chain)
    assert re.search(r'op_name="jit\([^"]*layers/layer_0/ssm/ssm_in_proj/dot_general', train)
    for text in (step, chain):  # the attention layers keep their own
        assert "/layer/attn/wq/" in text and "/layer/attn/kv_write/" in text and "/layer/mlp/w_down/" in text
