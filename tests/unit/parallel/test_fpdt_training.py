"""FPDT as a TRAINING feature (round-5; reference ``sequence/fpdt_layer.py:510``
``_FPDTGPUOffloadingAttentionImpl_`` backward, ``:971 FPDT_Attention``).

The custom-VJP chunked attention must (a) match dense forward AND gradients,
(b) compose into the model as ``attn_impl='fpdt'`` including under Ulysses
sp>1, (c) keep compiled fwd+bwd memory linear in S at fixed chunk size, and
(d) support the pinned-host K/V offload remat policy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, TransformerConfig, causal_lm_spec
from deepspeed_tpu.ops.attention import causal_attention
from deepspeed_tpu.sequence import fpdt_attention


def _qkv(B=2, S=64, H=4, Hkv=2, D=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, D), jnp.float32),
            jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32),
            jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32))


def _fpdt_parity_combos(combos):
    q, k, v = _qkv()
    slopes = jnp.asarray(np.geomspace(0.25, 0.004, q.shape[2]), jnp.float32)
    for causal, use_alibi in combos:
        sl = slopes if use_alibi else None

        def ref(q, k, v):
            if causal:
                return causal_attention(q, k, v, impl="xla", alibi_slopes=sl)
            from deepspeed_tpu.sequence import chunked_attention
            return chunked_attention(q, k, v, chunk_size=q.shape[1],
                                     causal=False, alibi_slopes=sl)

        def new(q, k, v):
            return fpdt_attention(q, k, v, q_chunk=16, kv_chunk=16,
                                  causal=causal, alibi_slopes=sl)

        np.testing.assert_allclose(np.asarray(new(q, k, v)),
                                   np.asarray(ref(q, k, v)),
                                   rtol=2e-5, atol=2e-5)
        sum_ref = lambda *a: ref(*a).astype(jnp.float32).sum() * 0.01  # noqa: E731
        sum_new = lambda *a: new(*a).astype(jnp.float32).sum() * 0.01  # noqa: E731
        g_ref = jax.grad(sum_ref, argnums=(0, 1, 2))(q, k, v)
        g_new = jax.jit(jax.grad(sum_new, argnums=(0, 1, 2)))(q, k, v)
        for a, b, nm in zip(g_new, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-5,
                err_msg=f"d{nm} causal={causal} alibi={sl is not None}")


def test_fpdt_attention_fwd_and_grad_parity():
    """Forward + all three input grads vs dense, with GQA, causal and
    causal+ALiBi — the backward is the round-5 feature."""
    _fpdt_parity_combos([(True, False), (True, True)])


def test_fpdt_attention_noncausal_parity():
    """Non-causal chunked parity (the causal combos above exercise the same
    kernel with the strictly harder tile-skip logic)."""
    _fpdt_parity_combos([(False, False)])


# 1 layer + seq 32 (2x2 chunks of 16): the model-level test proves the
# attn_impl wiring; depth and longer scans add double-scan VJP compile time
# (the slowest test in the tier at 2 layers/4x4 chunks), not coverage —
# per-layer math is already pinned by the attention parity
_MODEL_KW = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                 num_layers=1, num_heads=4, num_kv_heads=2, max_seq_len=32,
                 fused_ce=False)


def _loss_and_grad(cfg, ids):
    m = CausalLM(cfg)
    params = m.init(jax.random.PRNGKey(0), {"input_ids": ids}, train=False)["params"]

    def f(p):
        return m.apply({"params": p}, {"input_ids": ids}, train=False)[0]

    # jit both: eager dispatch of the chunked double-scan VJP dominates the
    # tier's wall-clock otherwise
    return jax.jit(f)(params), jax.jit(jax.grad(f))(params)


def test_fpdt_model_parity():
    """attn_impl='fpdt' trains identically to the xla path."""
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 32)), jnp.int32)
    l_ref, g_ref = _loss_and_grad(TransformerConfig(**_MODEL_KW, attn_impl="xla"), ids)
    l_new, g_new = _loss_and_grad(
        TransformerConfig(**_MODEL_KW, attn_impl="fpdt",
                          fpdt_q_chunk=16, fpdt_kv_chunk=16), ids)
    np.testing.assert_allclose(l_new, l_ref, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-6),
        g_new, g_ref)


def test_fpdt_model_host_offload_parity():
    """With fpdt_offload the q/k/v/out residuals park in host memory between
    fwd and bwd (reference host-offloaded SequenceChunk) — same math.
    Nightly tier: same model-level compile as test_fpdt_model_parity plus the
    host-transfer program; default keeps the attention-level parity + the
    no-offload model parity."""
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 32)), jnp.int32)
    l_ref, g_ref = _loss_and_grad(TransformerConfig(**_MODEL_KW, attn_impl="xla"), ids)
    # single-device jit: the host-memory residual transfers compile and the
    # math is unchanged (multi-device is blocked upstream — see
    # test_fpdt_offload_multidevice_raises)
    l_off, g_off = _loss_and_grad(
        TransformerConfig(**_MODEL_KW, attn_impl="fpdt",
                          fpdt_offload=True, fpdt_q_chunk=16, fpdt_kv_chunk=16), ids)
    np.testing.assert_allclose(l_off, l_ref, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-6),
        g_off, g_ref)


def test_fpdt_offload_multidevice_raises(devices):
    """XLA's SPMD partitioner rejects host-memory placement annotations in
    this version; the engine must say so loudly instead of dying with a
    RET_CHECK mid-compile."""
    model = TransformerConfig(vocab_size=256, hidden_size=32, intermediate_size=64,
                              num_layers=2, num_heads=4, max_seq_len=64,
                              attn_impl="fpdt", fpdt_offload=True,
                              fpdt_q_chunk=16, fpdt_kv_chunk=16)
    with pytest.raises(NotImplementedError, match="fpdt_offload"):
        deepspeed_tpu.initialize(
            model=causal_lm_spec(model, example_seq_len=64),
            config={"train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "mesh": {"dp": 8}, "steps_per_print": 1000})


def test_fpdt_offload_requires_fpdt_impl():
    with pytest.raises(ValueError, match="fpdt_offload"):
        TransformerConfig(**_MODEL_KW, attn_impl="xla", fpdt_offload=True)


def test_fpdt_engine_sp2_trajectory(devices):
    """The FPDT training path under Ulysses sp=2 must reproduce the sp=1
    trajectory — long-context training composes with sequence parallelism
    (reference FPDT sits inside Ulysses; fpdt_layer.py:971)."""

    def run(mesh):
        model = TransformerConfig(vocab_size=256, hidden_size=32, intermediate_size=64,
                                  num_layers=2, num_heads=4, num_kv_heads=4,
                                  max_seq_len=64, attn_impl="fpdt",
                                  fpdt_q_chunk=16, fpdt_kv_chunk=16)
        cfg = {"train_micro_batch_size_per_gpu": 2,
               "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
               "mesh": mesh, "steps_per_print": 1000}
        eng, *_ = deepspeed_tpu.initialize(
            model=causal_lm_spec(model, example_seq_len=64), config=cfg, seed=11)
        rng = np.random.default_rng(3)
        losses = []
        for _ in range(3):
            batch = {"input_ids": rng.integers(
                0, 256, (eng.train_batch_size, 64), dtype=np.int32)}
            losses.append(float(eng.train_batch(batch)["loss"]))
        return losses

    # same dp (=> same global batch); the second mesh folds the spare factor
    # into sp (pp=2 in the baseline is inert without pipeline microbatches)
    base = run({"dp": 4, "pp": 2})
    sp = run({"dp": 4, "sp": 2})
    np.testing.assert_allclose(sp, base, rtol=2e-4)


def test_fpdt_memory_linear_in_seq():
    """Compiled fwd+bwd peak temp bytes at fixed chunk size must scale ~O(S),
    not O(S²): the per-tile score buffer is Cq x Ck regardless of S. The
    dense xla path is the positive control (its score matrix IS O(S²))."""
    B, H, D = 1, 4, 16

    def temp_bytes(S, fpdt):
        q = jnp.zeros((B, S, H, D), jnp.float32)

        def loss(q):
            if fpdt:
                o = fpdt_attention(q, q[:, :, :H, :], q, q_chunk=128,
                                   kv_chunk=128, causal=True)
            else:
                o = causal_attention(q, q, q, impl="xla")
            return o.astype(jnp.float32).sum()

        comp = jax.jit(jax.grad(loss)).lower(q).compile()
        return comp.memory_analysis().temp_size_in_bytes

    lo, hi = 512, 2048  # 4x sequence
    r_fpdt = temp_bytes(hi, True) / max(temp_bytes(lo, True), 1)
    r_dense = temp_bytes(hi, False) / max(temp_bytes(lo, False), 1)
    # linear would be 4x, quadratic 16x; leave headroom for constant terms
    assert r_fpdt < 7, f"fpdt temp grew {r_fpdt:.1f}x over a 4x seq increase"
    assert r_dense > 9, (
        f"positive control broken: dense temp grew only {r_dense:.1f}x")
