"""Pallas paged flash-decode kernel vs the dense-gather XLA fallback
(reference inference/v2/kernels/ragged_ops/blocked_flash/)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.registry import dispatch
import deepspeed_tpu.ops.pallas.paged_attention  # noqa: F401
import deepspeed_tpu.inference.paged  # noqa: F401  (registers the xla impl)


PAGES = 64  # pages of one layer's pool; the pool holds LAYERS of them
LAYERS = 3


def _setup(N=3, C=4, H=8, kvH=2, hd=32, P=6, bs=16, seed=0, layer=0):
    """The pool as ``inference/paged.py`` stores it and hands it to either
    implementation: every layer's pages in ONE rank-3 ``[L*pages, bs, kvH*hd]``
    array, and a block table that already points at ``layer``'s pages in it."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (N, C, H, hd), jnp.float32)
    pool_k = jax.random.normal(ks[1], (LAYERS * PAGES, bs, kvH * hd), jnp.float32)
    pool_v = jax.random.normal(ks[2], (LAYERS * PAGES, bs, kvH * hd), jnp.float32)
    # distinct random pages per row
    bt = jax.random.permutation(ks[3], PAGES)[: N * P].reshape(N, P).astype(jnp.int32)
    bt = bt + layer * PAGES
    # rows with different live lengths: row n ends at position end_n
    ends = jnp.asarray([5, 37, 90])[:N]
    positions = jnp.stack([jnp.arange(C) + e - C + 1 for e in ends]).astype(jnp.int32)
    new_lens = jnp.full((N,), C, jnp.int32)
    return q, pool_k, pool_v, bt, positions, new_lens, bs


def _plain_reference(q, pool_k, pool_v, bt, pos, bs, slopes=None):
    """Token by token in numpy, from the layout's definition alone: position j
    of row n lives in page ``bt[n, j // bs]`` at slot ``j % bs``, head ``kh``
    in lanes ``[kh*hd, (kh+1)*hd)`` of that slot's row."""
    q, pool_k, pool_v, bt, pos = map(np.asarray, (q, pool_k, pool_v, bt, pos))
    N, C, H, hd = q.shape
    G = H // (pool_k.shape[-1] // hd)
    out = np.zeros_like(q)
    for n in range(N):
        for c in range(C):
            js = np.arange(pos[n, c] + 1)
            for h in range(H):
                lanes = slice((h // G) * hd, (h // G + 1) * hd)
                k = pool_k[bt[n, js // bs], js % bs, lanes]
                v = pool_v[bt[n, js // bs], js % bs, lanes]
                s = k @ q[n, c, h] / np.sqrt(hd)
                if slopes is not None:
                    s = s + np.asarray(slopes)[h] * js
                w = np.exp(s - s.max())
                out[n, c, h] = (w / w.sum()) @ v
    return out


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("kvH,alibi", [(8, False), (2, False), (2, True), (8, True)],
                         ids=["mha", "gqa", "gqa-alibi", "mha-alibi"])
def test_whole_pool_with_layer_offset_matches_plain_reference(impl, kvH, alibi):
    """Both implementations read a layer's pages out of the whole rank-3 pool
    through an offset block table, and agree with a reference that knows only
    where the layout says a token's keys and values are."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    q, pk, pv, bt, pos, lens, bs = _setup(H=8, kvH=kvH, hd=16, layer=2)
    assert int(bt.min()) >= 2 * PAGES  # nothing of layer 0 or 1 is addressed
    slopes = alibi_slopes(8) if alibi else None
    got = dispatch("paged_attention", impl)(q, pk, pv, bt, pos, bs, new_lens=lens,
                                            alibi_slopes=slopes)
    want = _plain_reference(q, pk, pv, bt, pos, bs, slopes)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("ppcb", [1, 2, 8])
def test_paged_pallas_matches_xla(ppcb, layer):
    q, pk, pv, bt, pos, lens, bs = _setup(layer=layer)
    xla = dispatch("paged_attention", "xla")
    pallas = dispatch("paged_attention", "pallas")
    want = xla(q, pk, pv, bt, pos, bs)
    got = pallas(q, pk, pv, bt, pos, bs, new_lens=lens, pages_per_block=ppcb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_paged_pallas_decode_single_token():
    q, pk, pv, bt, pos, lens, bs = _setup(C=1, layer=1)
    xla = dispatch("paged_attention", "xla")
    pallas = dispatch("paged_attention", "pallas")
    want = xla(q, pk, pv, bt, pos, bs)
    got = pallas(q, pk, pv, bt, pos, bs, new_lens=lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 1])
def test_paged_pallas_gqa_grouping(layer):
    q, pk, pv, bt, pos, lens, bs = _setup(H=8, kvH=4, hd=16, layer=layer)
    want = dispatch("paged_attention", "xla")(q, pk, pv, bt, pos, bs)
    got = dispatch("paged_attention", "pallas")(q, pk, pv, bt, pos, bs, new_lens=lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_ragged_forward_uses_kernel_consistently():
    """v2 ragged_forward parity between forced impls (engine path sanity)."""
    from deepspeed_tpu.inference.paged import init_pool, ragged_forward
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, hidden_size=32, intermediate_size=48,
                            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=64,
                            dtype=jnp.float32)
    module = CausalLM(cfg)
    batch = {"input_ids": jnp.zeros((1, 8), jnp.int32)}
    params = module.init({"params": jax.random.PRNGKey(0)}, batch, train=False)["params"]
    pool = init_pool(cfg, num_blocks=8, block_size=16, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 8)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(8), (2, 8)).astype(jnp.int32)
    new_lens = jnp.asarray([8, 5], jnp.int32)
    bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)

    logits, _ = ragged_forward(params, cfg, pool, tokens, positions, new_lens, bt, 16)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("kvH,ppcb", [(2, 8), (8, 8), (2, 2)])  # GQA/MHA + multi-chunk
def test_paged_pallas_alibi_matches_xla(kvH, ppcb):
    """ALiBi fused into the decode kernel (slope * key-position on the
    existing position iota) — bloom keeps the Pallas fast path."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    q, pk, pv, bt, pos, lens, bs = _setup(H=8, kvH=kvH, hd=16, layer=1)
    slopes = alibi_slopes(8)
    xla = dispatch("paged_attention", "xla")
    pallas = dispatch("paged_attention", "pallas")
    want = xla(q, pk, pv, bt, pos, bs, alibi_slopes=slopes)
    got = pallas(q, pk, pv, bt, pos, bs, new_lens=lens, alibi_slopes=slopes,
                 pages_per_block=ppcb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
