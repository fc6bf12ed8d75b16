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


NINE = dict(window=128, bs=16, H=4, Hkv=2, Dk=24, Dv=16)  # the MiMo cell's ring of 9 columns at toy widths, a key wider than its value


@pytest.mark.parametrize("ts", [(142,), (143,), (127,), (128,), (15,), (0,), (142, None, 15, 143, 128, 0, None, 2000, 2014)],
                         ids=["T-1", "eight-of-nine-pages", "the-window-fills", "nine-pages", "one-page", "one-token",
                              "side-by-side"])
def test_a_ring_of_nine_columns_is_one_chunk_under_the_default_with_a_sink(ts):
    """The chunk the kernel takes when told none is all 9 columns of the ring (``T`` = 144): a query at 142 sees
    143 slots (``T - 1``, the most a ring of 9 holds: its first page's 15 dead slots, its last page's one), at 143
    eight pages of nine (the ninth is not fetched), at 15 one page, None no page (zeros). NaN lies in every slot
    no query sees. Against the brute-force list of live positions with the sink in the denominator, and against
    the XLA fallback."""
    window, bs, H, Hkv, Dk, Dv = (NINE[k] for k in ("window", "bs", "H", "Hkv", "Dk", "Dv"))
    R = ring_columns(window, bs)
    assert R == 9
    rng = np.random.default_rng(3)
    total = max(t for t in ts if t is not None) + 1
    keys = rng.normal(size=(total, Hkv * Dk)).astype(np.float32)
    values = rng.normal(size=(total, Hkv * Dv)).astype(np.float32)
    sink = rng.normal(size=(H,)).astype(np.float32)
    q = rng.normal(size=(len(ts), 1, H, Dk)).astype(np.float32)
    pools_k, pools_v, table, at, low, want = [], [], [], [], [], []
    for n, t in enumerate(ts):
        # a row's ring in its own 2 R pages of the pool; a row with no page points at pages of NaN
        pk, _, cols = ring_of(keys, keys, t or 0, window, bs, 2 * R, seed=n)
        _, pv, _ = ring_of(values, values, t or 0, window, bs, 2 * R, seed=n)
        if t is None:
            pk[:], pv[:] = np.nan, np.nan
        first = max((t or 0) - window + 1, 0)
        oldest = first // bs
        pools_k.append(pk), pools_v.append(pv)
        table.append(2 * R * n + cols[(oldest + np.arange(R)) % R])
        at.append(-1 if t is None else t - oldest * bs), low.append(first - oldest * bs)
        live = np.arange(first, (t or 0) + 1)
        k = keys[live].reshape(-1, Hkv, Dk).repeat(H // Hkv, axis=1)
        v = values[live].reshape(-1, Hkv, Dv).repeat(H // Hkv, axis=1)
        e = np.exp(np.einsum("hd,jhd->hj", q[n, 0], k).astype(np.float64) * Dk ** -0.5)
        want.append(np.einsum("hj,jhd->hd", e / (e.sum(-1, keepdims=True) + np.exp(sink)[:, None]), v)
                    * (t is not None))
    args = (jnp.asarray(q), jnp.asarray(np.concatenate(pools_k)), jnp.asarray(np.concatenate(pools_v)),
            jnp.asarray(np.stack(table), jnp.int32), jnp.asarray(at, jnp.int32)[:, None], bs)
    kw = dict(new_lens=jnp.asarray([t is not None for t in ts], jnp.int32), first_live=jnp.asarray(low, jnp.int32)[:, None],
              sink=jnp.asarray(sink))
    (call,) = [e for e in jax.make_jaxpr(lambda *a: flash_decode_paged(*a, bs, **kw))(*args[:-1]).jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "swa_paged_attn"
    assert [v.aval.shape for v in call.params["jaxpr"].invars if len(v.aval.shape) == 4][:2] == [
        (2, R, bs, Hkv * Dk), (2, R, bs, Hkv * Dv)]  # the slots of K and of V: all nine columns a chunk
    got = np.asarray(flash_decode_paged(*args, **kw))[:, 0]
    assert np.isfinite(got).all() and float(np.abs(got - np.stack(want)).max()) < 3e-6
    rows = [n for n, t in enumerate(ts) if t is not None]
    fallback = np.asarray(_xla_paged_attention(*args, **kw))[:, 0]
    assert float(np.abs(got[rows] - fallback[rows]).max()) < 3e-6


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


# --- a fresh prompt padded to its bucket: the forward told its rows' live lengths (``_flash_fwd(lengths=)``) ---

LIVE_S, LIVE_BLOCK = 64, 16
LIVE_LENGTHS = [(0,), (1,), (LIVE_BLOCK - 1,), (LIVE_BLOCK,), (LIVE_BLOCK + 1,), (37,), (LIVE_S,),
                (37, LIVE_S), (LIVE_BLOCK, 0), (1, 50), (48, 17)]


def flash_fwd_told(lengths, window, plant=None, S=LIVE_S, block=LIVE_BLOCK):
    """``_flash_fwd`` at GQA 4:1 on ``len(lengths)`` rows of ``S`` -> ((out, lse) told the lengths, (out, lse) of
    the same kernel not told); ``plant`` is written over q, k and v in every block that holds no live token."""
    q, k, v = (a.transpose(0, 2, 1, 3) for a in qkv(S, B=len(lengths), seed=len(lengths)))
    told = jnp.asarray(lengths, jnp.int32)
    mask, slopes = jnp.ones((len(lengths), 1, S), jnp.int32), jnp.zeros((q.shape[1], 1, fa._LANES), jnp.float32)
    whole = fa._flash_fwd(q, k, v, mask, slopes, block, block, True, False, False, 1, window)
    if plant is not None:
        dead = (jnp.arange(S) >= -(-told // block)[:, None] * block)[:, None, :, None]
        q, k, v = (jnp.where(dead, plant, a) for a in (q, k, v))
    return fa._flash_fwd(q, k, v, mask, slopes, block, block, True, False, False, 1, window, lengths=told), whole


def live_rows_are_the_kernel_s_own_and_pads_are_zeros(lengths, window, plant=None):
    (out, lse), (whole, whole_lse) = flash_fwd_told(lengths, window, plant)
    for b, n in enumerate(lengths):
        assert np.array_equal(out[b, :, :n], whole[b, :, :n]) and np.array_equal(lse[b, :, :, :n], whole_lse[b, :, :, :n])
        assert not np.asarray(out[b, :, n:]).any() and (np.asarray(lse[b, :, :, n:]) == fa._NEG_INF).all()
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("lengths", LIVE_LENGTHS, ids=lambda a: "-".join(map(str, a)))
def test_under_the_band_a_row_told_its_length_is_the_kernel_s_own_and_its_pads_are_zeros(lengths):
    live_rows_are_the_kernel_s_own_and_pads_are_zeros(lengths, window=24)


@pytest.mark.parametrize("lengths", [(1,), (LIVE_BLOCK,), (37,), (48, 17)], ids=lambda a: "-".join(map(str, a)))
def test_under_the_band_nan_in_the_blocks_past_a_row_s_last_token_is_never_read(lengths):
    """What a dead block of q, k and v holds is neither fetched nor computed (the pads BESIDE a row's last token,
    in its block, are: they have to be finite, as a pad token's projections are)."""
    live_rows_are_the_kernel_s_own_and_pads_are_zeros(lengths, window=24, plant=jnp.nan)


@pytest.mark.parametrize("window", [None, 24], ids=["causal", "band"])
def test_every_implementation_hands_back_zeros_for_a_pad_and_the_kernel_has_no_backward(window):
    q, k, v = qkv(LIVE_S)
    lengths = jnp.asarray([37, LIVE_BLOCK], jnp.int32)
    dense = causal_attention(q, k, v, window=window, lengths=lengths, impl="xla")
    flash = causal_attention(q, k, v, window=window, lengths=lengths, impl="pallas", block_q=LIVE_BLOCK, block_k=LIVE_BLOCK)
    assert float(jnp.abs(flash - dense).max()) < 2e-6
    whole = _xla_causal_attention(q, k, v, window=window)
    for b, n in enumerate((37, LIVE_BLOCK)):
        assert float(jnp.abs(dense[b, :n] - whole[b, :n]).max()) == 0.0
        assert not np.asarray(dense[b, n:]).any() and not np.asarray(flash[b, n:]).any()
    with pytest.raises(NotImplementedError, match=r"lengths=.*forward alone"):
        jax.grad(lambda q: causal_attention(q, k, v, window=window, lengths=lengths, impl="pallas",
                                            block_q=LIVE_BLOCK, block_k=LIVE_BLOCK).sum())(q)
    with pytest.raises(NotImplementedError, match="no padding mask"):
        fa.flash_causal_attention(q, k, v, mask=jnp.ones((2, LIVE_S), jnp.int32), lengths=lengths)
    # the dense path is differentiable, and a pad's row takes no gradient
    grad = jax.grad(lambda q: _xla_causal_attention(q, k, v, window=window, lengths=lengths).sum())(q)
    assert jnp.isfinite(grad).all() and not np.asarray(grad[1, LIVE_BLOCK:]).any()


@pytest.mark.parametrize("S,window,block", [(16384, 4096, 512), (16384, None, 512), (64, 24, 16), (100, None, 8)])
def test_the_cells_a_length_leaves_are_a_prefix_of_the_grid_s_enumeration(S, window, block, monkeypatch):
    """``forward_cells`` (what the engine's span says) against the maps themselves, cell by cell; at the
    benchmark cell's bucket the mean over its traffic's lengths is ISSUE 58's 323.0 of 528 and 184.5 of 252.
    It counts in NumPy: a device array made between two dispatches would be a compile in a traced window."""
    n = -(-S // block)
    qs = np.asarray((fa._tri_maps(n) if window is None else fa._band_maps(n, block, window))[0])
    monkeypatch.setattr(fa, "jnp", None)
    assert (np.diff(qs) >= 0).all()  # row-major by query block: the cells of the first blocks come first
    rng = np.random.default_rng(0)
    for lengths in ([0], [1], [S], [block, block + 1], rng.integers(0, S + 1, 5).tolist()):
        live = sum(sum(1 for q in qs.tolist() if q * block < length) for length in lengths)
        assert fa.forward_cells(lengths, S, window, block) == (live, len(lengths) * len(qs))
    if S == 16384:
        mean = np.mean([fa.forward_cells([length], S, window)[0] for length in range(8192, 16385, 7)])
        assert mean == pytest.approx(323.0 if window is None else 184.5, abs=0.6)
