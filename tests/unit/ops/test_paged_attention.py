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
    from deepspeed_tpu.inference.cache import Pools, init_pool
    from deepspeed_tpu.inference.paged import ragged_forward
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, hidden_size=32, intermediate_size=48,
                            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=64,
                            dtype=jnp.float32)
    module = CausalLM(cfg)
    batch = {"input_ids": jnp.zeros((1, 8), jnp.int32)}
    params = module.init({"params": jax.random.PRNGKey(0)}, batch, train=False)["params"]
    pool = Pools(init_pool(cfg, num_blocks=8, block_size=16, dtype=jnp.float32))
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


# ---------------------------------------------------------------------------
# The walk over a row's live pages (ISSUE 30). Everything no row may read is
# NaN: every page no row owns (the table's dead entries point at one), and the
# dead slots of every row's last page. The XLA fallback gathers those and is
# not proof against them (0 * NaN in p @ v), so the plain reference, which
# reads live positions only, is what the kernel is held to.


def _planted(ends, C=1, H=8, kvH=8, hd=16, bs=16, layer=1, wide=1, quant=None, seed=0):
    """Rows that end at positions ``ends`` (-1: a row with no page), their
    pages scattered over ``layer``'s share of the whole pool, the table
    ``wide`` times as wide as the longest row needs. Returns the kernel's
    arguments, its keywords, and the pool in floats for the plain reference."""
    from deepspeed_tpu.inference.paged import _kv_block_quant

    rng = np.random.default_rng(seed)
    N, D = len(ends), kvH * hd
    ends = np.asarray(ends)
    need = np.maximum(ends, 0) // bs + 1
    P = int(need.max()) * wide
    free = rng.permutation(PAGES) + layer * PAGES
    dead, free = free[0], free[1:]
    bt = np.full((N, P), dead, np.int32)
    live = np.zeros((LAYERS * PAGES, bs), bool)
    for n, e in enumerate(ends):
        bt[n, :need[n]], free = free[:need[n]], free[need[n]:]
        js = np.arange(e + 1)
        live[bt[n, js // bs], js % bs] = True
    q = jnp.asarray(rng.standard_normal((N, C, H, hd)), jnp.float32)
    pos = np.stack([np.arange(C) + e - C + 1 for e in ends]).astype(np.int32)
    pos = np.where(ends[:, None] < 0, -1, pos)
    kw, pools, floats = {"new_lens": jnp.full((N,), C, jnp.int32)}, [], []
    for name in ("k_scale", "v_scale"):
        x = jnp.asarray(rng.standard_normal((LAYERS * PAGES * bs, kvH, hd)), jnp.float32)
        if quant is None:
            values = np.asarray(x).reshape(-1, bs, D)
            floats.append(values)
            pools.append(jnp.asarray(np.where(live[:, :, None], values, np.nan)))
            continue
        vq, sc = _kv_block_quant(x, quant)
        vq, sc = np.asarray(vq.astype(jnp.float32)), np.asarray(sc)
        floats.append((vq.reshape(-1, kvH, hd) * sc[:, :, None]).reshape(-1, bs, D))
        # an int8 cannot be NaN: its dead slots hold the largest value there is
        vq = np.where(live.reshape(-1, 1), vq, np.nan if quant == "fp8" else 127.0)
        pools.append(jnp.asarray(vq.reshape(-1, bs, D)).astype(
            jnp.float8_e4m3fn if quant == "fp8" else jnp.int8))
        kw[name] = jnp.asarray(np.where(live.reshape(-1, 1), sc, np.nan).reshape(-1, bs * kvH))
    return (q, *pools, jnp.asarray(bt), jnp.asarray(pos), bs), kw, floats


def _held_to_plain_reference(args, kw, floats, slopes=None, **more):
    q, _, _, bt, pos, bs = args
    got = np.asarray(dispatch("paged_attention", "pallas")(*args, alibi_slopes=slopes, **kw, **more))
    rows = np.asarray(pos)[:, -1] >= 0
    assert not got[~rows].any()  # a row with no page writes zeros
    want = _plain_reference(q[rows], *floats, bt[rows], pos[rows], bs, slopes)
    np.testing.assert_allclose(got[rows], want, rtol=2e-5, atol=2e-5)
    return got


@pytest.mark.parametrize("ppcb", [1, 2, 8])
def test_lengths_at_every_edge(ppcb):
    """No page, one token, exactly a page, one over, exactly a chunk, one
    over, two chunks, one over: side by side, so each row's first fetch is
    started by the row before it."""
    bs, T = 16, 16 * ppcb
    ends = [-1, 0, bs - 1, bs, T - 1, T, 2 * T - 1, 2 * T, -1, 1]
    _held_to_plain_reference(*_planted(ends), pages_per_block=ppcb)


@pytest.mark.parametrize("C,H,kvH,alibi,quant", [
    (1, 8, 8, False, None), (1, 8, 2, False, None), (4, 8, 8, False, None),
    (3, 8, 2, False, None), (32, 8, 8, False, None), (1, 8, 8, True, None),
    (1, 8, 2, True, None), (4, 8, 8, True, None), (1, 8, 8, False, "int8"),
    (1, 8, 2, False, "fp8"), (4, 8, 4, False, "int8"), (16, 8, 2, False, "fp8"),
], ids=["decode-mha", "decode-gqa", "drafts-mha", "drafts-gqa", "chunk-mha", "decode-mha-alibi",
        "decode-gqa-alibi", "drafts-mha-alibi", "decode-mha-int8", "decode-gqa-e4m3",
        "drafts-gqa-int8", "chunk-gqa-e4m3"])
def test_rows_of_very_different_lengths_side_by_side(C, H, kvH, alibi, quant):
    """Both forms of the compute (a product a kv head; one block-diagonal
    query for every head, where a head has fewer than 8 query rows), with
    scales and ALiBi, at a nonzero layer offset, under a table twice as wide
    as the longest row."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    ends = [C - 1, 250, C + 4, 129, 31]
    slopes = alibi_slopes(H) if alibi else None
    args, kw, floats = _planted(ends, C=C, H=H, kvH=kvH, layer=2, wide=2, quant=quant)
    _held_to_plain_reference(args, kw, floats, slopes)


@pytest.mark.parametrize("C", [1, 4, 16])
def test_a_rows_output_does_not_depend_on_the_tables_width(C):
    ends = [C + 40, 200, C - 1]
    narrow = _held_to_plain_reference(*_planted(ends, C=C, wide=1))
    wide = _held_to_plain_reference(*_planted(ends, C=C, wide=8))
    np.testing.assert_array_equal(narrow, wide)


@pytest.mark.parametrize("C,kvH,quant", [(1, 8, None), (16, 2, None), (1, 2, "fp8")],
                         ids=["decode", "chunk", "decode-e4m3"])
def test_under_the_chips_own_rules_for_memory_and_dma(monkeypatch, C, kvH, quant):
    """Pallas' TPU interpreter, not the plain one: scratch memory starts as
    NaN (the plain one zeroes it), a DMA lands when it is waited for and not
    when it is started, and a read of a buffer that a copy still in flight
    writes is a race. So a slot read before its wait, or a page of a slot that
    was never fetched, shows."""
    from jax.experimental.pallas import tpu as pltpu

    import deepspeed_tpu.ops.pallas.paged_attention as pa

    monkeypatch.setattr(pa, "_interpret", lambda: pltpu.InterpretParams(
        detect_races=True, dma_execution_mode="on_wait", uninitialized_memory="nan"))
    ends = [C - 1, 250, -1, C + 4, 129]
    _held_to_plain_reference(*_planted(ends, C=C, kvH=kvH, layer=2, wide=2, quant=quant))
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as interpreter

    assert not interpreter.races.races_found
