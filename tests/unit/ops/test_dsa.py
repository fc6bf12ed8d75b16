"""``ops/dsa.py`` (a learned sparse-attention indexer's scores and its choice of
the kept tokens) and its three kernels in interpret mode against their plain
XLA forms: ``dsa_index`` and ``dsa_select`` (``ops/pallas/dsa.py``) and the
latent kernel under a per-query mask (``dsa_paged_attn``,
``ops/pallas/paged_attention.py``)."""

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


def _halves(rng, shape):
    return np.round(rng.normal(size=shape) * 2) / 2  # many equal scores, 0.0 and -0.0 among them


def _normal(rng, shape):
    return rng.normal(size=shape) * 2


def _zeros_of_both_signs(rng, shape):
    return rng.choice(np.asarray([-0.0, 0.0, 1.0, -1.0], np.float32), size=shape, p=[0.4, 0.4, 0.1, 0.1])


_FROM_0 = np.arange(128)
# rows, columns, kept, the scores' draw, each query's position (one row's, or a row each): a tile is 64 queries
# at these shapes, a column chunk 512 columns where they divide the row, else 256 or 128
_SELECT_CASES = {
    "ties": (2, 640, 64, _halves, _FROM_0 * 5),
    "distinct": (2, 512, 32, _normal, _FROM_0 * 4),
    "as-many-columns-as-kept": (1, 128, 128, _halves, _FROM_0),
    "fewer-columns-than-kept": (1, 128, 200, _normal, _FROM_0),
    "pad-queries-at-minus-1-among-live-ones": (1, 1024, 64, _halves, np.where(_FROM_0 < 100, _FROM_0 + 300, -1)),
    "pad-queries-at-0-as-the-engine-has-them": (1, 1024, 64, _normal, np.where(_FROM_0 < 100, _FROM_0 + 300, 0)),
    "a-tile-wholly-under-topk": (2, 256, 64, _halves, _FROM_0),
    "a-tile-of-pads-alone": (2, 512, 32, _normal, np.stack([np.where(_FROM_0 < 64, _FROM_0 * 7, -1), _FROM_0 * 3])),
    "a-last-position-ends-inside-a-column-chunk": (1, 1024, 64, _halves, np.where(_FROM_0 < 64, _FROM_0 + 237, _FROM_0 + 573)),
    "columns-of-minus-inf-past-the-keys": (1, 1024, 64, _normal, np.minimum(_FROM_0 * 5, 599)),
    "zeros-of-both-signs-at-the-threshold": (2, 384, 64, _zeros_of_both_signs, _FROM_0 * 3),
    "queries-and-columns-not-in-whole-tiles": (1, 300, 32, _halves, np.arange(150) * 2),
}


@pytest.mark.parametrize("dtype", [jnp.bool_, jnp.bfloat16], ids=["bool", "bf16"])
@pytest.mark.parametrize("case", list(_SELECT_CASES))
def test_the_select_kernel_against_its_xla_form(case, dtype):
    """Interpret mode: ``dsa_select`` gives the mask ``select_mask``'s XLA form gives, equal as arrays, in the
    type asked for: the four cases of the test above at whole tiles of queries (ties AT the threshold, which the
    kernel breaks by a bisection on the column index where XLA counts along the row; distinct scores; as many
    columns as kept and fewer, where nothing is sought), pad queries at position -1 among live ones and at
    position 0 as the serving engine has them, a tile wholly under ``topk`` beside one over it (the first bisects
    nothing), a tile of pads alone (nothing fetched, a row's last tile before the next row's first), last
    positions 300 and 700 inside 512-column chunks (the second chunk of the first tile is neither fetched nor
    counted), columns of ``-inf`` past the row's keys as ``dsa_index`` leaves them, a threshold AT zero among
    ``0.0`` and ``-0.0`` (one value), and a call of 150 queries against 300 columns (padded to whole tiles and
    cut back)."""
    from deepspeed_tpu.ops.pallas import dsa as kernel

    N, S, topk, draw, positions = _SELECT_CASES[case]
    positions = np.broadcast_to(positions, (N,) + positions.shape[-1:])
    rng = np.random.default_rng(0)
    seen = np.arange(S)[None, None] <= positions[..., None]
    scores = jnp.asarray(np.where(seen, draw(rng, seen.shape), -np.inf), jnp.float32)
    want = dsa.select_mask(scores, topk, dtype, impl="xla")
    got = kernel.select_mask(scores, topk, jnp.asarray(positions, jnp.int32), dtype)
    assert got.dtype == want.dtype == dtype and got.shape == want.shape == scores.shape
    assert (np.asarray(got) == np.asarray(want)).all()
    assert (np.asarray(got).astype(bool) == _as_sets(scores, topk)).all()
    assert (np.asarray(got).astype(np.int32).sum(-1) == np.minimum(positions + 1, topk)).all()
    if case == "ties":  # without the positions every tile looks at every column: the same mask
        assert (np.asarray(kernel.select_mask(scores, topk, None, dtype)) == np.asarray(want)).all()


def test_which_form_chooses_is_read_from_the_call_s_shapes(monkeypatch):
    """On the chip a chunk of 128 queries or more with more columns than it keeps is the kernel's; fewer queries
    (a token and its drafts), ``S <= topk`` (every candidate) and every call off the chip are XLA's. And the
    type the model asks the mask in is the one its walk reads: the queries' own for the kernel's, bool for XLA's."""
    from deepspeed_tpu.inference import paged
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import dsa as kernel  # noqa: F401  (registers the kernel)

    taken = []
    monkeypatch.setitem(registry._REGISTRY["dsa_select"], "pallas",
                        lambda scores, *rest: taken.append(scores.shape) or "the kernel's")
    scores = lambda C, S: jnp.zeros((1, C, S), jnp.float32)  # noqa: E731
    assert dsa.select_mask(scores(128, 65), 64).dtype == jnp.bool_ and not taken  # off the chip
    assert paged.masked_walk_reads(8192, jnp.bfloat16) == jnp.bool_
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
    assert dsa.select_mask(scores(128, 65), 64) == "the kernel's" and taken == [(1, 128, 65)]
    for C, S in ((127, 300), (1, 300), (128, 64), (8192, 40)):
        assert dsa.select_mask(scores(C, S), 64).shape == (1, C, S)
    assert dsa.select_mask(jnp.zeros((300, 65), jnp.float32), 64).shape == (300, 65)  # no chunk of a row's queries
    assert taken == [(1, 128, 65)]
    assert paged.masked_walk_reads(8192, jnp.bfloat16) == jnp.bfloat16
    assert paged.masked_walk_reads(8, jnp.bfloat16) == jnp.bool_  # a token and its drafts: XLA's walk


def test_the_bench_tool_counts_the_cells_the_kernel_skips(monkeypatch):
    """``tools/latent_kernel_bench.py --shapes glm5-select`` at a toy shape: a line a prompt length and form, the
    two masks equal as arrays, the share of (query tile, column chunk) cells not fetched from the shapes (a
    prompt of 100 in a bucket of 256 queries against four chunks of 256 columns: tiles of 64 whose last
    positions are 63, 99, 0, 0 fetch one chunk each, 4 of 16), and no device time without a chip: the host's
    clock is never written under ``ms_per_call``."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "..", "tools", "latent_kernel_bench.py")
    spec = importlib.util.spec_from_file_location("latent_kernel_bench", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    from deepspeed_tpu.ops.pallas import dsa as kernel

    monkeypatch.setitem(tool.SELECT, "toy", (256, 1024, 64, (100, 256)))
    monkeypatch.setattr(kernel, "_select_form", kernel._select_form)  # (the tool stands in for it; put back here)
    lines = list(tool.measure_select("toy", (64, 256), seed=1, calls=2, repeats=1))
    assert [(line["prompt"], line["form"]) for line in lines] == [(100, "xla"), (100, "kernel"), (256, "xla"), (256, "kernel")]
    short, whole = lines[1], lines[3]
    assert short["equal_to_xla"] and whole["equal_to_xla"] and short["kept"] == lines[0]["kept"]
    assert short["cells_skipped_share"] == 0.75 and short["score_bytes_read"] == 4 * 64 * 256 * 4
    assert whole["cells_skipped_share"] == 1 - (1 + 1 + 1 + 1) / 16  # 256 columns hold every position of the bucket
    assert short["ms_per_call"] is None and short["host_ms_per_call"] > 0


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
