"""``ops/dsa.py`` (a learned sparse-attention indexer's scores and its choice of
the kept tokens) and its two kernels in interpret mode against their plain XLA
forms: ``dsa_index`` (``ops/pallas/dsa.py``) and the latent kernel under a
per-query mask (``dsa_paged_attn``, ``ops/pallas/paged_attention.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import dsa


def _as_sets(scores, topk):
    """Each row's ``lax.top_k`` as a mask (the candidates alone; -0.0 is 0.0, the lower index first among equals)."""
    values, at = jax.lax.top_k(jnp.where(scores == 0, 0.0, scores), min(topk, scores.shape[-1]))
    want = np.zeros(scores.shape, bool)
    for row in np.ndindex(*scores.shape[:-1]):
        want[row][np.asarray(at[row])[np.asarray(values[row]) > -np.inf]] = True
    return want


@pytest.mark.parametrize("shape,topk,ties", [((3, 50, 300), 64, True), ((2, 40, 257), 32, False),
                                             ((1, 9, 64), 64, True), ((2, 5, 40), 100, False)],
                         ids=["ties", "distinct", "as-many-columns-as-kept", "fewer-columns-than-kept"])
def test_the_choice_of_the_kept_is_top_k_s_as_sets(shape, topk, ties):
    """The threshold by bisection against ``lax.top_k``: rows with fewer
    candidates than ``topk`` (all kept), with exactly as many, with ties AT the
    threshold (the lower positions win), with both zeros among the scores."""
    rng = np.random.default_rng(0)
    raw = rng.normal(size=shape) * 2
    scores = jnp.asarray(np.round(raw) / 2 if ties else raw, jnp.float32)  # halves: many equal scores, -0.0 too
    seen = jnp.arange(shape[-1])[None, None] <= (jnp.arange(shape[1]) * (shape[-1] // shape[1] + 1))[None, :, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    chosen = np.asarray(dsa.select_mask(scores, topk))
    want = _as_sets(scores, topk)
    assert (chosen == want).all()
    assert (chosen.sum(-1) == np.minimum(np.asarray(seen.sum(-1)), topk)).all()
    if ties and shape[-1] > topk:
        assert (np.asarray(scores)[..., None, :] == np.asarray(scores)[..., :, None]).sum() > scores.size  # it had ties
    at = np.asarray(dsa.select_positions(scores[:, -1], topk))
    for row in range(shape[0]):
        assert set(at[row][at[row] >= 0]) == set(np.nonzero(want[row, -1])[0])
    packed = np.asarray(dsa.pack_mask(jnp.asarray(chosen)))
    assert (np.unpackbits(packed.view(np.uint8), axis=-1, bitorder="little")[..., :shape[-1]] == chosen).all()


def test_the_index_key_s_norm_is_a_layer_norm_with_bias():
    rng = np.random.default_rng(1)
    x, scale, bias = rng.normal(size=(5, 16)), rng.normal(size=16), rng.normal(size=16)
    want = (x - x.mean(-1, keepdims=True)) / np.sqrt(x.var(-1, keepdims=True) + 1e-6) * scale + bias
    np.testing.assert_allclose(np.asarray(dsa.key_norm(jnp.asarray(x, jnp.float32), jnp.asarray(scale), jnp.asarray(bias))),
                               want, rtol=1e-5, atol=1e-5)


def test_the_rotary_turns_the_first_columns_alone():
    from deepspeed_tpu.models.transformer import rope_at

    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 6, 3, 16)), jnp.float32)
    pos = jnp.arange(6)[None] + jnp.asarray([[0], [7]])
    out = dsa.rotate(x, pos, 8, 1e6, True)
    np.testing.assert_array_equal(np.asarray(out[..., 8:]), np.asarray(x[..., 8:]))
    np.testing.assert_allclose(np.asarray(out[..., :8]), np.asarray(rope_at(x[..., :8], pos, 1e6, True)), rtol=1e-6)


@pytest.mark.parametrize("N,C,H,D,S", [(2, 192, 4, 32, 700), (1, 130, 32, 128, 1100)], ids=["toy", "the-cell-s-heads"])
def test_the_index_kernel_against_its_xla_form(N, C, H, D, S):
    """Interpret mode: queries not a whole tile (padded), keys not a whole tile (the result comes in whole tiles
    of columns, -inf past the row's keys), a row from position 300, a row with dead queries (position -1)."""
    from deepspeed_tpu.ops.pallas import dsa as kernel

    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in ((N, C, H, D), (N, S, D)))
    w = jnp.asarray(rng.normal(size=(N, C, H)), jnp.float32)
    pos = np.stack([np.arange(C) + 300, np.where(np.arange(C) < 100, np.arange(C), -1)])[:N]
    want = np.asarray(dsa.index_scores(q, k, w, jnp.asarray(pos, jnp.int32), impl="xla"))
    got = np.asarray(kernel.index_scores(q, k, w, jnp.asarray(pos, jnp.int32)))
    assert got.shape == (N, C, -(-S // kernel._TK) * kernel._TK) and np.isinf(got[..., S:]).all()
    live = np.isfinite(want)
    assert (np.isfinite(got[..., :S]) == live).all()
    np.testing.assert_allclose(got[..., :S][live], want[live], rtol=2e-5, atol=2e-4)
    assert (np.asarray(dsa.select_mask(jnp.asarray(got), 64))[..., :S] == np.asarray(dsa.select_mask(jnp.asarray(want), 64))).mean() > 0.999


def test_a_prompt_s_queries_go_through_the_xla_form_a_tile_at_a_time():
    rng = np.random.default_rng(3)
    q, k = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in ((1, 256, 2, 16), (1, 300, 16)))
    w, pos = jnp.asarray(rng.normal(size=(1, 256, 2)), jnp.float32), jnp.arange(256)[None] + 40
    assert q.shape[1] > dsa._XLA_QUERY_TILE
    tiled = np.asarray(dsa.index_scores(q, k, w, pos, impl="xla"))
    plain = np.where(np.arange(300)[None, None] <= np.asarray(pos)[..., None],
                     np.einsum("nch,nchs->ncs", np.asarray(w), np.maximum(np.einsum("nchd,nsd->nchs", q, k), 0)), -np.inf)
    np.testing.assert_allclose(tiled, plain, rtol=2e-5, atol=2e-5)


# C, H, live queries a row, each row's first position, pages a row, (tokens a tile, pages a chunk) or None for
# the form the shapes choose, whether the mask is every position at or before
_MASKED_CASES = {
    "toy": (48, 4, (48, 30), (100, 0), 12, None, False),
    "64-heads": (32, 64, (32, 17), (100, 0), 12, None, False),
    "a-tile-straddles-a-prompt-s-end": (64, 4, (64, 37), (0, 0), 8, (16, 2), False),
    "a-row-of-pad-queries-alone": (32, 4, (32, 0), (40, 0), 8, (16, 2), False),
    "under-index-topk-every-position-at-or-before": (48, 4, (48, 20), (0, 0), 8, (16, 2), True),
    "a-context-ends-in-a-chunk-s-first-page": (48, 4, (35, 48), (0, 16), 8, (16, 2), False),
    "64-heads-five-chunks-of-two-pages": (32, 64, (32, 17), (100, 0), 12, (16, 2), False),
    "6-heads-tiles-of-32": (70, 6, (70, 33), (57, 0), 8, (32, 3), False),
}


@pytest.mark.parametrize("case", list(_MASKED_CASES))
def test_the_latent_kernel_under_a_mask_against_the_gather(case, monkeypatch):
    """Interpret mode: ``dsa_paged_attn`` attends the positions a query's mask marks and no others (a row from
    position 100, a row with dead queries), against the dense-gather form under the same mask; and unmasked, the
    kernel's output moves: the mask is not decoration. The mask marks no position past its query's own, which
    the kernel's contract is since PR 56 (its step makes no causal compare). At forms with several chunks a
    row: a tile of 16 queries that straddles a prompt's end (37 live: 5 of its 16), a row with no live query
    beside a whole one, a mask that is every position at or before (a prompt under ``index_topk``), a context
    of 35 = 32 + 3 positions that ends in the first page of a 32-column chunk, 64 heads, and 6 heads (no
    multiple of 8) with 70 queries in tiles of 32 against chunks of 3 pages (the queries padded to 96)."""
    from deepspeed_tpu.inference.paged import _xla_latent_paged_attention
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    C, H, lens, first, P, form, whole = _MASKED_CASES[case]
    if form is not None:
        monkeypatch.setattr(pa, "_masked_latent_form", lambda *shapes: form)
    rng = np.random.default_rng(0)
    N, W, vw, bs = 2, 256, 128, 16
    pool = jnp.asarray(rng.normal(size=(40, bs, W)), jnp.float32)
    tables = jnp.asarray(rng.permutation(40)[:N * P].reshape(N, P), jnp.int32)
    q = jnp.asarray(rng.normal(size=(N, C, H, W)), jnp.float32)
    pos = jnp.asarray(np.stack([np.arange(C) + at for at in first]), jnp.int32)
    new_lens = jnp.asarray(lens, jnp.int32)
    # (every query keeps its own position, as a selection of index_topk >= 1 does; the kernel's columns come in tiles)
    at = jnp.arange(256)[None, None]
    seen = at <= pos[..., None]
    mask = seen if whole else (jnp.asarray(rng.random((N, C, 256)) < 0.3) & seen) | (at == pos[..., None])
    want = _xla_latent_paged_attention(q, pool, tables, pos, bs, 0.1, vw, new_lens=new_lens, mask=mask)
    got = pa.flash_decode_latent(q, pool, tables, pos, bs, 0.1, vw, new_lens=new_lens, mask=mask)
    live = np.arange(C)[None] < np.asarray(new_lens)[:, None]
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(want - got))[live].max() < 2e-5
    dense = pa.flash_decode_latent(q, pool, tables, pos, bs, 0.1, vw, new_lens=new_lens)
    assert whole or np.abs(np.asarray(dense - got))[live].max() > 1e-2
    assert not whole or np.abs(np.asarray(dense - got))[live].max() < 2e-5


@pytest.mark.parametrize("S,topk", [(40, 64), (300, 64)], ids=["under-index-topk", "over-index-topk"])
def test_the_mask_the_model_hands_over_marks_nothing_past_a_query_s_position(S, topk):
    """What the masked kernel's step rests on since PR 56 (it makes no causal compare of its own): the choice
    of ``index_scores``' scores marks no position past its query's and none for a pad query (position -1), at
    prompts under ``index_topk`` (every candidate is taken) and over it, through the XLA form and through the
    index kernel (whose columns past the keys are ``-inf`` too); a pad query that stands at position 0, as the
    serving engine's do, marks position 0 and no other."""
    from deepspeed_tpu.ops.pallas import dsa as kernel

    rng = np.random.default_rng(5)
    N, C, H, D = 2, 128, 4, 32
    q, k = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in ((N, C, H, D), (N, S, D)))
    w = jnp.asarray(np.abs(rng.normal(size=(N, C, H))), jnp.float32)
    live = np.arange(C)[None] < np.asarray([[min(C, S)], [min(C, S) - 9]])
    first = np.asarray([[S - min(C, S)], [0]])
    for pad_at in (-1, 0):
        pos = jnp.asarray(np.where(live, np.arange(C)[None] + first, pad_at), jnp.int32)
        for scores in (dsa.index_scores(q, k, w, pos, impl="xla"), kernel.index_scores(q, k, w, pos)):
            chosen = np.asarray(dsa.select_mask(scores, topk))
            past = np.arange(chosen.shape[-1])[None, None] > np.asarray(pos)[..., None]
            assert not (chosen & past).any()
            assert (chosen[live].sum(-1) == np.minimum(np.asarray(pos)[live] + 1, topk)).all()
            assert (chosen[~live].sum(-1) == (0 if pad_at < 0 else 1)).all()


def test_one_token_a_row_attends_its_kept_rows_gathered_by_position():
    from deepspeed_tpu.inference.paged import _xla_latent_paged_attention, latent_selected_attention

    rng = np.random.default_rng(4)
    N, H, W, vw, bs, P = 3, 4, 256, 128, 8, 6
    pool = jnp.asarray(rng.normal(size=(30, bs, W)), jnp.float32)
    tables = jnp.asarray(rng.permutation(30)[:N * P].reshape(N, P), jnp.int32)
    q = jnp.asarray(rng.normal(size=(N, 1, H, W)), jnp.float32)
    pos = jnp.asarray([[40], [9], [25]], jnp.int32)
    kept = np.full((N, 16), -1, np.int32)
    mask = np.zeros((N, 1, P * bs), bool)
    for n, t in enumerate([40, 9, 25]):
        at = np.sort(rng.permutation(t + 1)[:16])
        kept[n, :len(at)] = at
        mask[n, 0, at] = True
    got = latent_selected_attention(q, pool, tables, jnp.asarray(kept), bs, 0.1, vw)
    want = _xla_latent_paged_attention(q, pool, tables, pos, bs, 0.1, vw, mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
