"""A sliding window in the three places it is computed: the banded flash
forward (``ops/pallas/flash_attention.py``, kernel ``swa_flash_fwd``) against
the dense masked product at forced block sizes, with the cells it does not run
counted; the paged kernel's ``first_live`` over a ring of pages rolled so its
oldest live page comes first (``swa_paged_attn``) and the XLA fallback's,
against a brute-force list of the live positions for every ``t`` of three
windows; both again at the benchmark cell's own sizes (window 4,096 at a flash
block of 512 and at pages of 16), where float32 still tells a window that is
off by one key; and the fallback on a ring whose oldest page is wholly dead
slots full of NaN."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.paged import _xla_paged_attention, ring_columns
from deepspeed_tpu.ops.attention import _xla_causal_attention, band_keep, causal_attention, first_live
from deepspeed_tpu.ops.pallas import flash_attention as fa
from deepspeed_tpu.ops.pallas.paged_attention import flash_decode_paged


def qkv(S, H=8, Hkv=2, D=16, B=2, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(B, S, h, D)), jnp.float32) for h in (H, Hkv, Hkv))


@pytest.mark.parametrize("S,window,block", [(64, 16, 16), (64, 20, 16), (100, 32, 8), (64, 7, 16), (128, 32, 32),
                                            (64, 64, 16), (64, 100, 16), (96, 17, 32)])
def test_the_banded_forward_is_the_dense_masked_product(S, window, block):
    q, k, v = qkv(S)
    want = _xla_causal_attention(q, k, v, window=window)
    got = fa.flash_causal_attention(q, k, v, block_q=block, block_k=block, window=window)
    assert float(jnp.abs(got - want).max()) < 2e-6
    # and the dense path is the statement itself
    t, j = np.arange(S)[:, None], np.arange(S)[None, :]
    keep = (j <= t) & np.asarray(band_keep(t, j, window))
    scores = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.repeat(np.asarray(k), 4, axis=2)) * 16 ** -0.5
    probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
    plain = np.einsum("bhqk,bkhd->bqhd", np.asarray(probs), np.repeat(np.asarray(v), 4, axis=2))
    assert float(np.abs(plain - np.asarray(want)).max()) < 2e-6


def test_the_band_s_edge_is_held_to_the_key_at_the_cell_s_own_window_and_block():
    """Window 4,096 at blocks of 512, the cell's own: ten blocks of queries, the
    last two past the window, so the band's lower edge crosses cells of the
    squashed grid as it does in a ``(1, 16384)`` prefill; and a window one key
    wider or narrower, which bf16 cannot tell at this width, reads apart."""
    S, window, block = 5120, 4096, 512
    q, k, v = qkv(S, H=2, Hkv=1, D=16, B=1)
    want = _xla_causal_attention(q, k, v, window=window)
    got = fa.flash_causal_attention(q, k, v, block_q=block, block_k=block, window=window)
    assert float(jnp.abs(got - want).max()) < 2e-6
    for by in (-1, 1):
        other = fa.flash_causal_attention(q, k, v, block_q=block, block_k=block, window=window + by)
        assert float(jnp.abs(other[:, :window - 1] - want[:, :window - 1]).max()) < 2e-6  # (no query there is past it)
        assert float(jnp.abs(other[:, window:] - want[:, window:]).max()) > 1e-4


@pytest.mark.parametrize("n,block,window", [(32, 512, 4096), (8, 16, 16), (13, 8, 32), (4, 16, 100), (6, 16, 1)])
def test_cells_wholly_under_the_band_are_no_part_of_the_grid(n, block, window):
    qs, ks = (np.asarray(a) for a in fa._band_maps(n, block, window))
    cells = set(zip(qs.tolist(), ks.tolist()))
    assert len(cells) == len(qs)  # each once
    # a cell runs exactly if some (query, key) pair of it is causal and inside the band: its nearest pair is
    qi, ki = np.arange(n)[:, None], np.arange(n)[None, :]
    seen = (ki <= qi) & (np.maximum((qi - ki) * block - (block - 1), 0) < window)
    if n * block <= 512:  # (and that is what every pair of a small grid says, one by one)
        t, j = np.arange(n * block)[:, None], np.arange(n * block)[None, :]
        assert np.array_equal(seen, ((j <= t) & (t - j < window)).reshape(n, block, n, block).any(axis=(1, 3)))
    assert cells == set(zip(*np.nonzero(seen)))
    # a query row's cells are consecutive from its first to the diagonal: the accumulator is reset and read out once
    for qi in range(n):
        mine = sorted(k for q, k in cells if q == qi)
        assert mine == list(range(mine[0], qi + 1)) and mine[0] == int(fa._band_first(qi, block, window))
    if (n, block, window) == (32, 512, 4096):  # the cell's shape: 252 of the triangle's 528 cells run
        assert len(cells) == 8 * 9 // 2 + 24 * 9 == 252


def test_the_band_goes_through_causal_attention_and_has_no_backward_on_the_kernel():
    q, k, v = qkv(32)
    want = _xla_causal_attention(q, k, v, window=8)
    assert float(jnp.abs(causal_attention(q, k, v, window=8) - want).max()) < 2e-6
    assert float(jnp.abs(causal_attention(q, k, v, window=8, impl="xla") - want).max()) == 0.0
    with pytest.raises(NotImplementedError, match="forward alone"):
        jax.grad(lambda q: fa.flash_causal_attention(q, k, v, block_q=8, block_k=8, window=8).sum())(q)
    with pytest.raises(NotImplementedError, match="no padding mask"):
        fa.flash_causal_attention(q, k, v, mask=jnp.ones((2, 32), jnp.int32), window=8)
    # the dense path is differentiable, which is what a sliding layer trains through
    assert jnp.isfinite(jax.grad(lambda q: _xla_causal_attention(q, k, v, window=8).sum())(q)).all()


def ring_of(keys, values, t, window, bs, pool_pages, seed):
    """A ring of pages as a sliding layer leaves it after position ``t``: block
    ``b`` in ring column ``b % R`` (a later block over an earlier one), the
    columns' pages drawn from ``pool_pages``; NaN in every page and slot that
    holds no live position. -> (pool_k, pool_v, ring columns)."""
    R = ring_columns(window, bs)
    X = keys.shape[-1]
    rng = np.random.default_rng(seed)
    cols = rng.permutation(pool_pages)[:R].astype(np.int32)
    pk = np.full((pool_pages, bs, X), np.nan, np.float32)
    pv = np.full((pool_pages, bs, X), np.nan, np.float32)
    low = max(t - window + 1, 0)
    for j in range(low, t + 1):
        pk[cols[(j // bs) % R], j % bs] = keys[j]
        pv[cols[(j // bs) % R], j % bs] = values[j]
    return pk, pv, cols


def ring_reads_the_live_positions(window, bs, impl, ts):
    """For every ``t`` of ``ts``: the ring rolled by ``first_live`` reads the
    brute-force list of live positions in order, and the kernel over it is
    the plain softmax over those positions."""
    H, Hkv, D = 4, 2, 8
    R = ring_columns(window, bs)
    total = max(ts) + 1
    rng = np.random.default_rng(1)
    keys = rng.normal(size=(total, Hkv * D)).astype(np.float32)
    values = rng.normal(size=(total, Hkv * D)).astype(np.float32)
    queries = rng.normal(size=(total, H, D)).astype(np.float32)
    kernel = _xla_paged_attention if impl == "xla" else flash_decode_paged
    attend = jax.jit(lambda q, pk, pv, table, at, low: kernel(  # one program for every t: the shapes do not change
        q, pk, pv, table, at, bs, new_lens=jnp.ones((1,), jnp.int32), first_live=low))
    for t in ts:
        pk, pv, cols = ring_of(keys, values, t, window, bs, 3 * R, seed=t)
        low = int(first_live(jnp.asarray(t), window))
        live = [j for j in range(t + 1) if t - j < window]  # the brute-force list
        assert live[0] == low and len(live) == min(t + 1, window)
        oldest = low // bs
        rolled = cols[(oldest + np.arange(R)) % R]
        # the rolled ring reads the live positions in order, from slot ``low - oldest * bs`` of its first page on
        flat = pk[rolled].reshape(R * bs, -1)
        at = low - oldest * bs
        assert np.array_equal(flat[at:at + len(live)], keys[live])
        got = attend(jnp.asarray(queries[t][None, None]), jnp.asarray(pk), jnp.asarray(pv), jnp.asarray(rolled[None]),
                     jnp.asarray([[t - oldest * bs]], jnp.int32), jnp.asarray([[at]], jnp.int32))
        k = keys[live].reshape(-1, Hkv, D).repeat(H // Hkv, axis=1)
        v = values[live].reshape(-1, Hkv, D).repeat(H // Hkv, axis=1)
        probs = jax.nn.softmax(jnp.einsum("hd,jhd->hj", queries[t], k) * D ** -0.5, axis=-1)
        want = np.einsum("hj,jhd->hd", np.asarray(probs), v)
        assert np.isfinite(np.asarray(got)).all(), t
        assert float(np.abs(np.asarray(got)[0, 0] - want).max()) < 3e-6, t


@pytest.mark.parametrize("window,bs", [(32, 8), (16, 16), (24, 8)])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_rings_roll_and_first_live_against_the_live_positions_for_every_t_of_three_windows(window, bs, impl):
    ring_reads_the_live_positions(window, bs, impl, range(3 * window + 5))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_cell_s_own_ring_of_257_pages_at_the_positions_about_its_edges(impl):
    """Window 4,096 at pages of 16: before the window fills, at the position that
    fills it, the first that leaves a slot behind, the last of the ring's first
    round and the first of its second, and two contexts of the cell's traffic."""
    ring_reads_the_live_positions(4096, 16, impl, (0, 17, 4095, 4096, 4097, 4111, 4112, 8191, 12345, 16400))


def test_the_fallback_takes_a_wholly_dead_page_of_nan():
    """``first_live`` past a whole page: every slot a query could see there is
    masked, the page is NaN, and the answer is the live slots' alone."""
    bs, Hkv, D = 8, 2, 8
    rng = np.random.default_rng(2)
    pk = rng.normal(size=(4, bs, Hkv * D)).astype(np.float32)
    pv = rng.normal(size=(4, bs, Hkv * D)).astype(np.float32)
    pk[0], pv[0] = np.nan, np.nan  # the table's first page: dead to the last slot
    pv[2, 5:] = np.nan  # and the slots past the query
    q = jnp.asarray(rng.normal(size=(1, 1, 4, D)), jnp.float32)
    table = jnp.asarray([[0, 1, 2]], jnp.int32)
    got = _xla_paged_attention(q, jnp.asarray(pk), jnp.asarray(pv), table, jnp.asarray([[20]], jnp.int32), bs,
                               first_live=jnp.asarray([[8]], jnp.int32))
    assert np.isfinite(np.asarray(got)).all()
    want = _xla_paged_attention(q, jnp.asarray(pk), jnp.nan_to_num(jnp.asarray(pv)), table[:, 1:],
                                jnp.asarray([[12]], jnp.int32), bs)
    assert float(jnp.abs(got - want).max()) < 2e-6
