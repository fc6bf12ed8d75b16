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


@pytest.mark.parametrize("C,H,lens", [(48, 4, (48, 30)), (32, 64, (32, 17))], ids=["toy", "64-heads"])
def test_the_latent_kernel_under_a_mask_against_the_gather(C, H, lens):
    """Interpret mode: ``dsa_paged_attn`` attends the positions a query's mask marks and no others (a row from
    position 100, a row with dead queries), against the dense-gather form under the same mask; and unmasked, the
    kernel's output moves: the mask is not decoration."""
    from deepspeed_tpu.inference.paged import _xla_latent_paged_attention
    from deepspeed_tpu.ops.pallas.paged_attention import flash_decode_latent

    rng = np.random.default_rng(0)
    N, W, vw, bs, P = 2, 256, 128, 16, 12
    pool = jnp.asarray(rng.normal(size=(40, bs, W)), jnp.float32)
    tables = jnp.asarray(rng.permutation(40)[:N * P].reshape(N, P), jnp.int32)
    q = jnp.asarray(rng.normal(size=(N, C, H, W)), jnp.float32)
    pos = jnp.asarray(np.stack([np.arange(C) + 100, np.arange(C)]), jnp.int32)
    new_lens = jnp.asarray(lens, jnp.int32)
    # (every query keeps its own position, as a selection of index_topk >= 1 does; the kernel's columns come in tiles)
    mask = jnp.asarray(rng.random((N, C, 256)) < 0.3) | (jnp.arange(256)[None, None] == pos[..., None])
    want = _xla_latent_paged_attention(q, pool, tables, pos, bs, 0.1, vw, new_lens=new_lens, mask=mask)
    got = flash_decode_latent(q, pool, tables, pos, bs, 0.1, vw, new_lens=new_lens, mask=mask)
    live = np.arange(C)[None] < np.asarray(new_lens)[:, None]
    assert np.abs(np.asarray(want - got))[live].max() < 2e-5
    dense = flash_decode_latent(q, pool, tables, pos, bs, 0.1, vw, new_lens=new_lens)
    assert np.abs(np.asarray(dense - got))[live].max() > 1e-2


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
