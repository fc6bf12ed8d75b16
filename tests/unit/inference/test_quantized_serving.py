"""Quantized-serving equivalence harness (ISSUE 10).

The tentpole's correctness contract, pinned three ways:

  1. accuracy — int8/fp8 KV-cache storage and WOQ weights vs the fp path:
     bounded logit error at the ``put`` API, greedy-token agreement over a
     K-step decode chain on the CPU mesh (int8 KV is token-identical here)
  2. kernel parity — the fused-dequant Pallas block loads (interpret mode)
     match the XLA per-gathered-block fallback bit-tightly
  3. structure — a jaxpr census of the decode-chain program proves the
     full-precision pool NEVER materializes, whole or one layer of it: every
     pool-sized tensor in the program is int8/fp8 (the PR-8 program-census
     pattern applied to storage instead of wires)

Plus the capacity plumbing: byte-budget pool sizing admits ~1.9x the
requests at identical bytes, and the new serving gauges land labelled.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngineV2
from deepspeed_tpu.inference.cache import init_pool
from deepspeed_tpu.inference.paged import (
    _kv_block_quant,
    paged_attention,
    ragged_decode_chain,
)

from .test_inference_v2 import make_model


def _engine(cfg, params, **over):
    base = {"dtype": "fp32", "kv_block_size": 4, "num_kv_blocks": 64,
            "chunk_bucket": 8, "hbm_check": "off"}
    base.update(over)
    return InferenceEngineV2(cfg, params, base)


# ------------------------------------------------------------------ accuracy
def test_kv_int8_greedy_token_identical():
    """int8 KV (per-head-vector blocks) is accurate enough that greedy decode
    through the chained fast path matches the fp32 pool token for token —
    except where fp32 itself is at a near-tie. A random-init 2-layer model
    HAS near-ties, and which side of one a run lands on moves with the jax
    version, so the first divergent token (if any) must be one the fp32
    logits rank within the int8 logit-error bound (3% of the logit range,
    ``test_kv_quant_logit_error_bounded``) of their own argmax."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in (7, 3, 5)]
    outs_fp = _engine(cfg, params).generate(prompts, max_new_tokens=12)
    outs_q = _engine(cfg, params, kv_cache_dtype="int8").generate(
        prompts, max_new_tokens=12)
    scorer = _engine(cfg, params)
    for uid, (p, a, b) in enumerate(zip(prompts, outs_q, outs_fp)):
        a, b = np.asarray(a)[-12:], np.asarray(b)[-12:]
        assert a.shape == b.shape == (12,)
        diverged = np.nonzero(a != b)[0]
        if diverged.size == 0:
            continue
        j = int(diverged[0])  # everything before j agrees: score that context
        logits = scorer.put([uid], [np.concatenate([p, b[:j]])])[0]
        assert int(np.argmax(logits)) == b[j]
        assert logits[b[j]] - logits[a[j]] < 0.03 * np.abs(logits).max(), (
            f"prompt {uid}: int8 token {a[j]} at step {j} is no near-tie of fp32's {b[j]}")


@pytest.mark.parametrize("kvd,bound", [("int8", 0.03), ("fp8", 0.15)])
def test_kv_quant_logit_error_bounded(kvd, bound):
    """Bounded logit drift at the ``put`` API, prefill AND decode reads
    (measured ~1% int8 / ~6% fp8 on this tiny random-init model — real
    checkpoints with structured activations sit well below)."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, (9,))
    base = _engine(cfg, params)
    l_fp = base.put([0], [prompt])
    l_fp_d = base.put([0], [[3]])
    q = _engine(cfg, params, kv_cache_dtype=kvd)
    l_q = q.put([0], [prompt])
    l_q_d = q.put([0], [[3]])
    denom = np.abs(l_fp).max()
    assert np.abs(l_q - l_fp).max() / denom < bound
    assert np.abs(l_q_d - l_fp_d).max() / denom < bound


def test_kv_quant_chain_equals_per_token_loop():
    """The fast-path invariant survives quantized storage: decode_chain=K
    and decode_chain=1 are the same program semantics (greedy, int8 pool)."""
    cfg, _, params = make_model(seed=2)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in (6, 4)]
    o1 = _engine(cfg, params, kv_cache_dtype="int8", decode_chain=1).generate(
        prompts, max_new_tokens=10)
    ok = _engine(cfg, params, kv_cache_dtype="int8", decode_chain=4).generate(
        prompts, max_new_tokens=10)
    for a, b in zip(o1, ok):
        np.testing.assert_array_equal(a, b)


def test_woq_v2_bounded_and_generates():
    """v2 WOQ (int8 weights + scales through the shared block math, dequant
    at the matmul boundary): bounded logit error vs dense and a working
    greedy chain decode."""
    from deepspeed_tpu.inference.woq import WOQTensor

    cfg, _, params = make_model()
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, (9,))
    base = _engine(cfg, params)
    woq = _engine(cfg, params,
                  quant={"enabled": True, "bits": 8, "min_leaf_size": 0})
    assert isinstance(woq.params["layers"]["attn"]["wq"]["kernel"], WOQTensor)
    l_fp = base.put([0], [prompt])
    l_q = woq.put([0], [prompt])
    assert np.abs(l_q - l_fp).max() / np.abs(l_fp).max() < 0.08
    outs = woq.generate([prompt], max_new_tokens=8)
    assert len(outs[0]) == 8


def test_woq_tensor_classes_select():
    """Per-tensor-class WOQ: only the selected families quantize."""
    from deepspeed_tpu.inference.woq import WOQTensor, quantize_params

    cfg, _, params = make_model()
    q = quantize_params(params, "int8", min_size=0, classes=["attn"])
    assert isinstance(q["layers"]["attn"]["wq"]["kernel"], WOQTensor)
    assert not isinstance(q["layers"]["mlp"]["w_up"]["kernel"], WOQTensor)
    q2 = quantize_params(params, "int8", min_size=0, classes=["mlp"])
    assert isinstance(q2["layers"]["mlp"]["w_up"]["kernel"], WOQTensor)
    assert not isinstance(q2["layers"]["attn"]["wq"]["kernel"], WOQTensor)
    with pytest.raises(ValueError, match="unknown WOQ tensor class"):
        quantize_params(params, "int8", min_size=0, classes=["bogus"])
    # the v2 engine plumbs the selection through
    eng = _engine(cfg, params, quant={"enabled": True, "bits": 8,
                                      "min_leaf_size": 0,
                                      "tensor_classes": ["attn"]})
    assert isinstance(eng.params["layers"]["attn"]["wq"]["kernel"], WOQTensor)
    assert not isinstance(eng.params["layers"]["mlp"]["w_up"]["kernel"], WOQTensor)


def test_woq_composes_with_quantized_kv():
    """The full quantized-serving stack: int8 weights AND int8 KV pool."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (5,))]
    outs = _engine(cfg, params, kv_cache_dtype="int8",
                   quant={"enabled": True, "bits": 8, "min_leaf_size": 0}
                   ).generate(prompts, max_new_tokens=6)
    assert len(outs[0]) == 6


# -------------------------------------------------------------- kernel parity
@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_fused_pallas_loads_match_xla_fallback(quant):
    """Interpret-mode parity of the fused-dequant Pallas block loads vs the
    XLA gather-then-dequant fallback on an identically quantized pool."""
    cfg, _, _ = make_model()
    NB, bs = 8, 4
    pool = init_pool(cfg, NB, bs, jnp.float32, kv_quant=quant)
    L, kvH, hd = cfg.num_layers, cfg.kv_heads, cfg.dims_per_head
    rng = np.random.RandomState(3)

    def filled(values, scales):
        """The whole pool, zeros but for the LAST layer's pages."""
        q, s_ = _kv_block_quant(jnp.asarray(rng.randn(NB * bs, kvH, hd), jnp.float32), quant)
        first = (L - 1) * NB
        return (values.at[first:].set(q.astype(values.dtype).reshape(NB, bs, -1)),
                scales.at[first:].set(s_.reshape(NB, -1)))

    pk, psk = filled(pool.k, pool.k_scale)
    pv, psv = filled(pool.v, pool.v_scale)
    N, C, H = 2, 1, cfg.num_heads
    q = jnp.asarray(rng.randn(N, C, H, hd), jnp.float32)
    bt = jnp.asarray(rng.randint(0, NB, (N, 4)), jnp.int32) + (L - 1) * NB
    qpos = jnp.asarray([[5], [9]], jnp.int32)
    o_x = paged_attention(q, pk, pv, bt, qpos, 4, impl="xla",
                          k_scale=psk, v_scale=psv)
    o_p = paged_attention(q, pk, pv, bt, qpos, 4, impl="pallas",
                          k_scale=psk, v_scale=psv)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_x),
                               atol=2e-6, rtol=2e-6)


# ------------------------------------------------------------ program census
def _all_avals(jaxpr, acc):
    for v in list(jaxpr.invars) + list(jaxpr.constvars) + list(jaxpr.outvars):
        if hasattr(v, "aval"):
            acc.append(v.aval)
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            if hasattr(v, "aval"):
                acc.append(v.aval)
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else (val,)
            for x in vals:
                if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                    _all_avals(x.jaxpr, acc)
                elif hasattr(x, "eqns"):
                    _all_avals(x, acc)
    return acc


def test_decode_program_never_materializes_fp_pool():
    """Jaxpr census of the quantized decode-chain program (the PR-8 pattern):
    no floating-dtype tensor anywhere in the program is as large as one
    layer's dense pool — dequant happens per gathered block (XLA path) or
    inside the kernel's VMEM loads (Pallas path), never on the pool."""
    cfg, _, params = make_model()
    eng = _engine(cfg, params, kv_cache_dtype="int8", max_seq_len=32)
    bs = eng.config.kv_block_size
    rows, k = 4, 4

    def chain(params, pool, tokens, start_pos, tables, active, budgets, rng):
        return ragged_decode_chain(params, cfg, pool, tokens, start_pos,
                                   tables, bs, active, budgets, rng, k, None)

    jaxpr = jax.make_jaxpr(chain)(
        eng.params, eng.pools,
        jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32),
        jnp.zeros((rows, eng.max_pages), jnp.int32),
        jnp.ones((rows,), bool), jnp.full((rows,), k, jnp.int32),
        jax.random.PRNGKey(0))
    L, D = cfg.num_layers, eng.pool.k.shape[-1]
    NB = eng.pool.k.shape[0] // L
    layer_elems = NB * bs * D
    # the batch's gathered view must be smaller than one layer's pool, or the
    # census couldn't tell "gathered block" from "a layer's pool"
    assert rows * eng.max_pages < NB
    avals = [a for a in _all_avals(jaxpr.jaxpr, []) if hasattr(a, "shape")]
    # offender = a floating tensor with a layer's pool of elements or more
    # whose rows are kvH*hd or hd wide: the dense pool, in either layout.
    # (The fp32 SCALES are 1/head_dim the elements, exactly what quantized
    # storage stores, and fall under the size.)
    assert eng.pool.k_scale.size < layer_elems
    offenders = [a for a in avals
                 if a.size >= layer_elems and a.shape[-1] in (D, cfg.dims_per_head)
                 and jnp.issubdtype(a.dtype, jnp.floating)]
    assert not offenders, [f"{a.dtype} {a.shape}" for a in offenders[:5]]
    # and the quantized pool IS in the program (the census has teeth)
    assert any(a.size == L * layer_elems and a.dtype == jnp.int8 for a in avals)


# ----------------------------------------------------------- capacity & gauges
def test_byte_budget_sizing_admits_more():
    """Fixed pool bytes, head_dim=64: the int8 pool's block count (and the
    admission capacity that follows it) is >=1.8x the bf16 pool's."""
    cfg, _, params = make_model(hidden_size=128, num_heads=2, num_kv_heads=2,
                                intermediate_size=128)
    from deepspeed_tpu.utils.hbm import kv_slot_bytes

    budget = 96 * 16 * kv_slot_bytes(cfg.num_layers, cfg.kv_heads,
                                     cfg.dims_per_head, 2, None)
    bf = _engine(cfg, params, kv_block_size=16, kv_pool_bytes=budget,
                 kv_cache_dtype="bf16", max_seqs=256)
    i8 = _engine(cfg, params, kv_block_size=16, kv_pool_bytes=budget,
                 kv_cache_dtype="int8", max_seqs=256)
    assert i8.num_kv_blocks / bf.num_kv_blocks >= 1.8
    # admission control actually admits more: the real can_schedule check
    def admitted(eng):
        n = 0
        while eng.can_schedule(list(range(n + 1)), [48] * (n + 1)):
            n += 1
        return n

    assert admitted(i8) / admitted(bf) >= 1.8


def test_kv_pool_gauges_and_labels():
    """serving/kv_pool_dtype + serving/kv_bytes_per_token gauges land, and
    serving/kv_pool_utilization carries the storage-dtype label."""
    from deepspeed_tpu.telemetry import get_tracer

    cfg, _, params = make_model()
    tr = get_tracer()
    was = tr.enabled
    tr.configure(enabled=True)
    tr.reset()
    try:
        eng = _engine(cfg, params, kv_cache_dtype="int8")
        eng.generate([np.arange(5) % cfg.vocab_size], max_new_tokens=4)
        gauges = tr.registry.gauges()
        assert gauges['serving/kv_pool_dtype{dtype="int8"}'] == 1.0
        assert gauges["serving/kv_bytes_per_token"] == eng.kv_bytes_per_token
        assert 'serving/kv_pool_utilization{dtype="int8"}' in gauges
    finally:
        tr.configure(enabled=was)
        if not was:
            tr.reset()


def test_kv_cache_dtype_rejects_unknown():
    cfg, _, params = make_model()
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        _engine(cfg, params, kv_cache_dtype="int3")
