"""EvaByte at a toy size with the published structure (EVA attention: window
32, chunk 4; unit-offset norms, an fp32 residual stream, 8 output heads in one
kernel), the system against the benchmark's plain reference
``benchmarks/reference/evabyte.py`` on seeded weights, LOGITS not tokens: the
chunk path over several windows, ``put`` token by token through closings, a
decode chain in which rows close their window at different steps, preemption
and resume, the pages a row holds after each closing, the HF mapping, and the
admission by tokens."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import harness, program
from deepspeed_tpu.checkpoint.hf import (config_from_hf, convert_hf_state, detect_family,
                                         evabyte_hf_state)
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import CausalLM

TOY = dict(
    model_type="evabyte", attention_class="eva", vocab_size=64, hidden_size=64, intermediate_size=160,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4, max_position_embeddings=512,
    window_size=32, chunk_size=4, num_pred_heads=8, rms_norm_eps=1e-5, rope_theta=100000,
    norm_add_unit_offset=True, fp32_skip_add=True, fp32_logits=True, tie_word_embeddings=False,
    attention_bias=False, hidden_act="silu", rope_scaling=None, num_chunks=None)
W, CHUNK = TOY["window_size"], TOY["chunk_size"]
PER_CLOSED, WINDOW_PAGES = W // CHUNK // CHUNK, W // CHUNK  # pages: a closed window's summaries, an open window
TOL = 5e-6  # fp32 against fp32


@pytest.fixture(scope="module")
def files():
    return harness.load_reference("evabyte"), harness.load_architecture("evabyte")


@pytest.fixture(scope="module")
def model():
    cfg = config_from_hf(TOY)
    params = CausalLM(cfg).init({"params": jax.random.PRNGKey(0)},
                                {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"]
    return cfg, params


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, TOY["vocab_size"], (4, 200), dtype=np.int32)


@pytest.fixture(scope="module")
def wanted(files, model, tokens):
    """The reference's head-0 logits over the whole of ``tokens``: a row at
    position p sees positions up to p alone, so one pass serves every test."""
    reference, architecture = files
    return np.asarray(reference.forward(architecture.reference_weights(model[1]), TOY, jnp.asarray(tokens)))


def engine_of(model, **kw):
    conf = dict(dtype="fp32", kv_cache_dtype="fp32", max_seqs=8, decode_chain=8, kv_block_size=CHUNK,
                num_kv_blocks=256, row_bucket=4, chunk_bucket=32, hbm_check="off", max_seq_len=256)
    return InferenceEngineV2(model[0], model[1], dict(conf, **kw))


def pages_of(engine, uid):
    seq = engine.state.get(uid)
    return seq.n_summary, seq.n_window


def test_a_chunk_over_three_and_a_half_windows(model, tokens, wanted):
    """One prefill call: windows computed at once from the chunk's own keys,
    the summaries of the three closed ones and the open one's rows written."""
    engine = engine_of(model)
    lens = [3 * W + W // 2, W + 8, 2 * W, 7]
    got = engine.put([1, 2, 3, 4], [tokens[i, :n] for i, n in enumerate(lens)])
    for i, n in enumerate(lens):
        assert program.relative_error(got[i], wanted[i, n - 1]) < TOL
    # a closed window's exact rows never reach the pool: its summaries alone are held
    assert [pages_of(engine, u) for u in (1, 2, 3, 4)] == [
        (3 * PER_CLOSED, W // 2 // CHUNK), (PER_CLOSED, 2), (2 * PER_CLOSED, 0), (0, 2)]
    # and the next token of each reads them through the cache
    got = engine.put([1, 2, 3, 4], [tokens[i, n:n + 1] for i, n in enumerate(lens)])
    for i, n in enumerate(lens):
        assert program.relative_error(got[i], wanted[i, n]) < TOL


def test_put_token_by_token_through_two_closings(model, tokens, wanted):
    engine = engine_of(model)
    start = W - 5
    engine.put([1], [tokens[0, :start]])
    closings = 0
    for p in range(start, 2 * W + 6):
        before = sum(pages_of(engine, 1))
        got = engine.put([1], [tokens[0, p:p + 1]])
        assert program.relative_error(got[0], wanted[0, p]) < TOL, p
        summary, window = pages_of(engine, 1)
        if p % W == W - 1:  # the token that closed its window
            closings += 1
            # at most per_closed pages more than before; the window's own pages went back
            assert summary == closings * PER_CLOSED and window == 0
            assert summary + window <= before + PER_CLOSED
        assert window <= WINDOW_PAGES and summary == (p + 1) // W * PER_CLOSED
        assert engine.state.free_blocks == 256 - summary - window  # counted in the one allocator
    # 2W + 6 tokens in: two windows' summaries and two pages of the third's rows, not 70 rows' pages
    assert closings == 2 and engine.windows_closed == 2 and pages_of(engine, 1) == (2 * PER_CLOSED, 2)


def test_a_chain_ahead_through_window_boundaries_is_the_serial_drivers(model, tokens):
    """Chains dispatched ahead in which rows close their window at steps 0, 3
    and 7 (and a row that closed in the chain before, and one that does at
    the last step of all): the tables of chain N+1 are built from where chain N WILL leave
    each row, pages behind a closing given back and all, before chain N is
    fetched. Tokens, windows counted and pages held are a driver's that runs
    one chain at a time; ``generate`` makes the same tokens."""
    from .test_chain_ahead import serial_driver

    k = 8
    # the token fed at step 0, 3, 7 of chain 2 is a window's last; at step 3 of chain 1; at the last of chain 3
    lens = [2 * W - 1 - k, 2 * W - 4 - k, 2 * W - 8 - k, 2 * W - 4, W + 8]
    prompts = [tokens[i % 4, :n] for i, n in enumerate(lens)]
    n_new = 1 + 3 * k
    ahead, serial = engine_of(model), engine_of(model)
    got = serial_driver(ahead, prompts, n_new, ahead=True, flush=False)
    want = serial_driver(serial, prompts, n_new, flush=False)
    assert (ahead.chains_ahead, serial.chains_ahead) == (2, 0)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert ahead.windows_closed == serial.windows_closed == 5 + 5  # the prompts' own, and one a row
    assert ahead.state.free_blocks == serial.state.free_blocks
    uids = [100 + i for i in range(len(lens))]
    assert [pages_of(ahead, u) for u in uids] == [pages_of(serial, u) for u in uids]
    assert [ahead.state.get(u).seen_tokens for u in uids] == [n + n_new - 1 for n in lens]
    engine = engine_of(model)
    for g, w in zip(engine.generate(prompts, max_new_tokens=n_new), want):
        np.testing.assert_array_equal(g, w)
    assert engine.chains_ahead == 2 and engine.state.free_blocks == 256


def test_a_chain_in_which_rows_close_at_steps_0_3_and_7_and_one_does_not(files, model, tokens):
    reference, architecture = files
    engine = engine_of(model)
    k = 8
    lens = [2 * W - 1, 2 * W - 4, 2 * W - 8, W + 8]  # the token fed at step 0, 3, 7 is a window's last; never
    uids = [1, 2, 3, 4]
    logits = engine.put(uids, [tokens[i, :n] for i, n in enumerate(lens)])
    last = logits.argmax(-1)
    closed = engine.windows_closed  # the prompts' own
    out, emitted, _ = engine.decode_chain(uids, last, [k] * 4, k, jax.random.PRNGKey(0))
    assert list(emitted) == [k] * 4 and engine.windows_closed == closed + 3
    assert [pages_of(engine, u)[0] for u in uids] == [2 * PER_CLOSED] * 3 + [PER_CLOSED]
    # the sequences the chain made, and one token more through `put`, against the reference
    full = tokens[:, :2 * W + 16].copy()
    for i, n in enumerate(lens):
        full[i, n] = last[i]
        full[i, n + 1:n + 1 + k] = out[i]
    want = np.asarray(reference.forward(architecture.reference_weights(model[1]), TOY, jnp.asarray(full)))
    for i, n in enumerate(lens):
        for j in range(k):  # every token the chain picked is the reference's own best, to a rounding
            row = want[i, n + j]
            assert row.max() - row[out[i, j]] <= 1e-4 * np.sqrt(np.mean(row ** 2)), (i, j)
    got = engine.put(uids, [full[i, n + k:n + k + 1] for i, n in enumerate(lens)])
    for i, n in enumerate(lens):
        assert program.relative_error(got[i], want[i, n + k]) < TOL


def test_preempt_and_resume(files, model, tokens):
    """A pool too small for two long rows: one is flushed and prefilled again
    with its context, through the chunk path, and ends where the reference does."""
    reference, architecture = files
    prompts = [tokens[0, :W + 20], tokens[1, :W + 22]]
    new = 2 * W
    engine = engine_of(model, num_kv_blocks=2 * PER_CLOSED + WINDOW_PAGES + 10, flight_recorder=True)
    outs = engine.generate(prompts, max_new_tokens=new)
    assert sum(r.preemptions for r in engine.lifecycle.records().values()) >= 1
    assert engine.state.free_blocks == engine.num_kv_blocks  # every page came back
    full = np.zeros((2, W + 22 + new), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        full[i, :len(p)], full[i, len(p):len(p) + len(o)] = p, o
    want = np.asarray(reference.forward(architecture.reference_weights(model[1]), TOY, jnp.asarray(full)))
    for i, (p, out) in enumerate(zip(prompts, outs)):
        assert len(out) == new
        for j, tok in enumerate(out):  # every token is the reference's own best, to a rounding
            row = want[i, len(p) + j - 1]
            assert row.max() - row[tok] <= 1e-4 * np.sqrt(np.mean(row ** 2)), (i, j)


def test_all_eight_heads(files, model, tokens):
    """Head m is columns m*V ... of the one kernel: with the kernel rolled by
    m heads the program's head 0 is the reference's head m."""
    reference, architecture = files
    cfg, params = model
    ids = jnp.asarray(tokens[:2, :W + 9])
    want = np.asarray(reference.forward_all_heads(architecture.reference_weights(params), TOY, ids))
    assert want.shape == (2, W + 9, 8, TOY["vocab_size"])
    for m in range(TOY["num_pred_heads"]):
        rolled = dict(params, lm_head={"kernel": jnp.roll(params["lm_head"]["kernel"], -m * TOY["vocab_size"], 1)})
        _, logits = CausalLM(cfg).apply({"params": rolled}, {"input_ids": ids})
        assert logits.dtype == jnp.float32
        assert program.relative_error(np.asarray(logits), want[:, :, m]) < TOL


def test_config_from_hf_of_the_published_config_gives_the_published_shapes():
    published = program.published(harness.load_config("evabyte"))
    cfg = config_from_hf(dict(published, num_hidden_layers=32))
    assert (cfg.eva_window, cfg.eva_chunk, cfg.num_pred_heads, cfg.rope_theta) == (2048, 16, 8, 100000.0)
    assert cfg.norm_unit_offset and cfg.fp32_residual and not cfg.tie_embeddings
    shapes = jax.eval_shape(lambda k: CausalLM(cfg).init(
        {"params": k}, {"input_ids": jnp.zeros((1, 8), jnp.int32)}, train=False)["params"], jax.random.PRNGKey(0))
    attn = shapes["layers"]["attn"]
    assert attn["phi"].shape == attn["mu"].shape == (32, 32, 128)
    assert attn["wq"]["kernel"].shape == attn["wk"]["kernel"].shape == (32, 4096, 32, 128)
    assert shapes["layers"]["mlp"]["w_gate"]["kernel"].shape == (32, 4096, 11008)
    assert shapes["lm_head"]["kernel"].shape == (4096, 8 * 320) and shapes["embed"]["embedding"].shape == (320, 4096)
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == cfg.num_params() == harness.load_architecture("evabyte").total_params(dict(published, num_hidden_layers=32))
    assert 6.4e9 < n < 6.6e9  # "6.5B"


def test_hf_names_there_and_back(model):
    cfg, params = model
    state = evabyte_hf_state(params, cfg)
    assert detect_family(state) == "evabyte"
    assert state["model.layers.2.self_attn.adaptive_phi"].shape == (4, 16)
    assert state["model.layers.0.self_attn.q_proj.weight"].shape == (64, 64)  # torch's [out, in]
    assert state["lm_head.weight"].shape == (8 * 64, 64)
    back = convert_hf_state(state, cfg)
    flat = lambda t: jax.tree_util.tree_leaves_with_path(t)  # noqa: E731
    assert [p for p, _ in flat(back)] == [p for p, _ in flat(jax.tree_util.tree_map(np.asarray, params))]
    for (_, a), (_, b) in zip(flat(back), flat(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("budget,calls", [(4 * 32, [4] * 6), (None, [24])], ids=["budget", "none"])
def test_the_token_budget_splits_24_prompts_into_6_calls(model, tokens, budget, calls):
    engine = engine_of(model, max_seqs=24, max_ragged_batch_size=budget)
    seen = []
    inner = engine._put_sample
    engine._put_sample = lambda uids, *a, **kw: (seen.append(len(uids)), inner(uids, *a, **kw))[1]
    chains = []
    chain = engine.decode_chain
    engine.decode_chain = lambda uids, *a, **kw: (chains.append(len(uids)), chain(uids, *a, **kw))[1]
    rng = np.random.default_rng(1)
    prompts = [tokens[i % 4, : rng.integers(20, 33)] for i in range(24)]
    outs = engine.generate(prompts, max_new_tokens=3)
    assert seen == calls and all(len(o) == 3 for o in outs)
    assert chains == [24]  # the calls run back to back: decode starts at the whole wave


@pytest.mark.parametrize("conf,named", [
    (dict(spec_decode=2), "spec_decode"), (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(prefix_cache=True), "prefix_cache"), (dict(kv_block_size=8), "kv_block_size=4")])
def test_what_an_eva_model_does_not_serve_with_is_refused_by_name(model, conf, named):
    with pytest.raises(ValueError, match=named):
        engine_of(model, **conf)


def test_a_chunk_in_the_middle_of_a_sequence_is_refused(model, tokens):
    engine = engine_of(model)
    engine.put([1], [tokens[0, :W]])
    with pytest.raises(ValueError, match="starts a sequence"):
        engine.put([1], [tokens[0, W:W + 5]])


def test_bf16_through_the_runner_s_own_comparison(files, model, tokens, wanted):
    """The cell's dtype at the toy's size: a bf16 pool and activations stay inside a few per cent."""
    cfg = dataclasses.replace(model[0], dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), model[1])
    reference, architecture = files
    engine = InferenceEngineV2(cfg, params, dict(
        dtype="bf16", kv_cache_dtype="bf16", max_seqs=8, decode_chain=8, kv_block_size=CHUNK, num_kv_blocks=256,
        row_bucket=4, chunk_bucket=32, hbm_check="off", max_seq_len=256))
    want = np.asarray(reference.forward(architecture.reference_weights(engine.params), TOY, jnp.asarray(tokens[:2])))
    got = engine.put([1, 2], [tokens[0, :3 * W - 2], tokens[1, :W + 3]])
    assert max(program.relative_error(got[0], want[0, 3 * W - 3]), program.relative_error(got[1], want[1, W + 2])) < 0.04
    for step in range(4):  # through the closing at 3W - 1
        got = engine.put([1], [tokens[0, 3 * W - 2 + step:3 * W - 1 + step]])
        assert program.relative_error(got[0], want[0, 3 * W - 2 + step]) < 0.04
