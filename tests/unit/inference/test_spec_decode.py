"""Speculative decode chains (ISSUE 12): n-gram propose + greedy
verify-and-accept inside the jitted K-step chain.

Contract under test:
  - spec output is token-identical to the plain chain for ANY accept
    pattern (the verify forward compares against exactly the argmax tokens
    the plain chain would emit) — random and repetitive prompts, EOS
    mid-chain, budget tails, int8 quantized pool
  - accept-all shape: on self-repeating greedy output the proposer locks
    on and >1 token per model forward is emitted (the acceptance metric)
  - reject-all shape: acceptance can only add tokens — a spec chain never
    dispatches more programs than the plain chain at the same K (the K=1
    cost floor: one forward per token, same as the plain per-token loop)
  - one compiled program per (rows, K) — the jit-cache pin survives
  - greedy-only: do_sample + spec_decode raises
"""

import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngineV2

from .test_inference_v2 import make_model


def _engine(cfg, params, **over):
    base = {"dtype": "fp32", "kv_block_size": 4, "num_kv_blocks": 128,
            "chunk_bucket": 8, "decode_chain": 4, "hbm_check": "off"}
    base.update(over)
    return InferenceEngineV2(cfg, params, base)


# ------------------------------------------------------------------- parity
def test_spec_matches_plain_chain_random_prompts():
    cfg, _, params = make_model()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in (7, 3, 5)]
    plain = _engine(cfg, params).generate(prompts, max_new_tokens=12)
    spec = _engine(cfg, params, spec_decode=3).generate(prompts, max_new_tokens=12)
    for a, b in zip(spec, plain):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("n_new", [1, 5, 13])
def test_spec_budget_tail_parity(n_new):
    """max_new_tokens not aligned with the chain window: rows stop exactly
    at the cap, token-identical to the plain chain."""
    cfg, _, params = make_model(seed=1)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (4,)) for _ in range(2)]
    plain = _engine(cfg, params).generate(prompts, max_new_tokens=n_new)
    spec = _engine(cfg, params, spec_decode=2).generate(prompts, max_new_tokens=n_new)
    for a, b in zip(spec, plain):
        assert len(a) == n_new
        np.testing.assert_array_equal(a, b)


def test_spec_eos_mid_window_parity():
    """EOS landing inside a verify window truncates the acceptances there
    (tokens after the EOS are discarded even if accepted)."""
    cfg, _, params = make_model(seed=2)
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, cfg.vocab_size, (6,))
    free = _engine(cfg, params).generate([prompt], max_new_tokens=10)[0]
    eos = int(free[3])
    plain = _engine(cfg, params).generate(
        [prompt], max_new_tokens=10, eos_token_id=eos)[0]
    spec = _engine(cfg, params, spec_decode=3).generate(
        [prompt], max_new_tokens=10, eos_token_id=eos)[0]
    np.testing.assert_array_equal(spec, plain)
    assert spec[-1] == eos


def test_spec_with_int8_pool_parity():
    """Speculation composes with quantized KV storage: the verify forward
    reads/writes the int8 pool like any chain step."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, (5,)) for _ in range(2)]
    plain = _engine(cfg, params, kv_cache_dtype="int8").generate(
        prompts, max_new_tokens=10)
    spec = _engine(cfg, params, kv_cache_dtype="int8", spec_decode=3).generate(
        prompts, max_new_tokens=10)
    for a, b in zip(spec, plain):
        np.testing.assert_array_equal(a, b)


# -------------------------------------------------------------- cost shape
def test_spec_accepts_on_repetitive_text():
    """The acceptance benchmark shape: greedy output that self-repeats lets
    the n-gram proposer lock on — >= 1.3 accepted tokens per model forward
    (the bench corpus's acceptance bar) and fewer dispatches than plain.

    Model seed 5 on this prompt settles into one repeated token from its
    first step (3.75 tokens per forward) — an attractor with wide logit
    margins, not a near-tie a jax upgrade can flip. (Seed 1 emitted a
    barely-repeating sequence at 1.25-1.36: the bound then tested the
    numerics of a random 2-layer model, not the proposer.)"""
    cfg, _, params = make_model(seed=5)
    rng = np.random.RandomState(1)
    pat = rng.randint(0, cfg.vocab_size, (4,))
    prompts = [np.tile(pat, 6)[:20] for _ in range(2)]
    plain = _engine(cfg, params)
    o_plain = plain.generate(prompts, max_new_tokens=16)
    spec = _engine(cfg, params, spec_decode=3)
    o_spec = spec.generate(prompts, max_new_tokens=16)
    for a, b in zip(o_spec, o_plain):
        np.testing.assert_array_equal(a, b)
    assert spec.spec_model_steps > 0
    tokens_per_forward = spec.spec_tokens_emitted / spec.spec_model_steps
    assert tokens_per_forward >= 1.3
    assert spec.dispatch_count < plain.dispatch_count


def test_spec_never_more_dispatches_than_plain():
    """Reject-all floor: every verify forward emits >= 1 token, so a spec
    chain at K covers at least the plain chain's K tokens — the dispatch
    count can only shrink."""
    cfg, _, params = make_model(seed=4)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, cfg.vocab_size, (6,)) for _ in range(3)]
    plain = _engine(cfg, params)
    o_plain = plain.generate(prompts, max_new_tokens=12)
    spec = _engine(cfg, params, spec_decode=3)
    o_spec = spec.generate(prompts, max_new_tokens=12)
    for a, b in zip(o_spec, o_plain):
        np.testing.assert_array_equal(a, b)
    assert spec.dispatch_count <= plain.dispatch_count
    assert spec.host_sync_count <= plain.host_sync_count
    # >= 1 token per forward even if nothing was ever accepted
    assert spec.spec_tokens_emitted >= spec.spec_model_steps


def test_spec_one_program_per_rows_k():
    """The jit-cache pin: a full generate compiles ONE spec-chain program
    (plus the fused prefill), regardless of accept pattern."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, cfg.vocab_size, (5,)) for _ in range(3)]
    eng = _engine(cfg, params, spec_decode=3)
    eng.generate(prompts, max_new_tokens=14)
    assert eng.jit_cache_size("spec") == 1
    assert eng.jit_cache_size("chain") == 0  # plain chain never compiled
    assert eng.jit_cache_size("logits") == 0
    assert eng.host_sync_count == eng.dispatch_count  # 1 fetch per program


def test_spec_metrics_and_gauges():
    from deepspeed_tpu.telemetry import get_tracer

    cfg, _, params = make_model(seed=1)
    tr = get_tracer()
    was = tr.enabled
    tr.configure(enabled=True)
    tr.reset()
    try:
        rng = np.random.RandomState(1)
        pat = rng.randint(0, cfg.vocab_size, (4,))
        eng = _engine(cfg, params, spec_decode=3)
        eng.generate([np.tile(pat, 5)], max_new_tokens=12)
        gauges = tr.registry.gauges()
        assert gauges["serving/spec_tokens_per_forward"] >= 1.0
        assert 0.0 <= gauges["serving/spec_accept_rate"] <= 1.0
        # the two describe the same accounting
        assert gauges["serving/spec_tokens_per_forward"] == pytest.approx(
            1.0 + 3 * gauges["serving/spec_accept_rate"])
    finally:
        tr.configure(enabled=was)
        if not was:
            tr.reset()


def test_ngram_proposer_masks_past_history_tail():
    """A match whose continuation runs past the valid history must fall back
    to the current token for the out-of-range slots — NOT propose the
    buffer's zero fill (which would silently kill acceptance on exactly the
    repetitive tails the proposer exists for)."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.paged import _ngram_propose

    hist = jnp.asarray([[9, 4, 9, 4, 0, 0]], jnp.int32)  # zeros = buffer fill
    drafts = np.asarray(_ngram_propose(hist, jnp.asarray([4]), 3, 2))[0]
    # pattern [9,4] matched at t=0; continuation = hist[2]=9, hist[3]=4,
    # then position 4 >= hist_len -> current token (4), not the buffer 0
    assert drafts.tolist() == [9, 4, 4]
    # no previous occurrence at all -> pure current-token fallback
    hist2 = jnp.asarray([[7, 1, 2, 3, 0, 0]], jnp.int32)
    drafts2 = np.asarray(_ngram_propose(hist2, jnp.asarray([4]), 3, 2))[0]
    assert drafts2.tolist() == [3, 3, 3]


def test_spec_rejects_sampling():
    cfg, _, params = make_model()
    eng = _engine(cfg, params, spec_decode=2)
    with pytest.raises(ValueError, match="greedy-only"):
        eng.generate([np.arange(5) % cfg.vocab_size], max_new_tokens=4,
                     do_sample=True)


def test_spec_composes_with_prefix_cache():
    """The full serving tier in one engine: warm prefix + spec chains."""
    cfg, _, params = make_model(seed=1)
    rng = np.random.RandomState(6)
    shared = rng.randint(0, cfg.vocab_size, (8,))
    p1 = np.concatenate([shared, rng.randint(0, cfg.vocab_size, (3,))])
    p2 = np.concatenate([shared, rng.randint(0, cfg.vocab_size, (4,))])
    cold = _engine(cfg, params).generate([p2], max_new_tokens=10)[0]
    eng = _engine(cfg, params, spec_decode=3, prefix_cache=True)
    eng.generate([p1], max_new_tokens=10)
    out = eng.generate([p2], max_new_tokens=10)[0]
    np.testing.assert_array_equal(out, cold)
    assert eng.prefill_tokens_cached >= 8
