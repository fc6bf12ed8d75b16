"""The sparse-attention indexer's scores as one TPU kernel (``ops/dsa.py``
has the mathematics and the plain XLA form):

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        float32, -inf at s > t

A grid step takes a tile of ``_TQ`` queries, ALL the indexer's heads, against a
tile of ``_TK`` keys: the heads are the rows of one product, head-major, ``[heads
* _TQ, D] x [D, _TK]``, so the keys are pushed to the MXU once for all heads;
the ``relu``, the heads' weights and the sum over heads are then adds and
multiplies of ``[_TQ, _TK]`` slabs in fast memory, and only the summed scores
leave. XLA's form writes every head's scores first: 32 times the result.

A key tile wholly past a query tile's last position computes nothing and
writes ``-inf`` (a prompt from position 0: half the tiles). The result comes
in whole key tiles, ``[N, C, ceil(S / _TK) * _TK]``, the columns past ``S``
``-inf``: cutting them off would copy it.

And the choice of the kept tokens from those scores, ``dsa_select`` (the
mathematics and the XLA form: ``ops/dsa.py::select_mask``): a grid step holds
a tile of queries' scores in fast memory, makes their ordered int32 keys
there, finds each query's ``topk``-th largest key by the same 31-step
bisection ON THE TILE, and writes the mask in the type its reader takes. HBM
sees the scores once and the mask once, where XLA's form reads a row's keys 31
times. It works only where a query can see: of a tile whose last position is
``t`` the column chunks past ``t`` are neither fetched nor counted (their mask
is zeros), a tile with no query past ``topk - 1`` (pads at -1 among them)
bisects nothing (every candidate is kept), and the tie at the threshold is
paid for by a tile that has one with an excess: a second bisection, on the
column index among the tied, in place of XLA's running count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.dsa import _INT_MIN, _ordered
from deepspeed_tpu.ops.registry import dispatch, register
from deepspeed_tpu.utils.compat import tpu_compiler_params

_TQ = 64   # queries a tile: with 32 heads, 2,048 rows of the product
_TK = 512  # keys a tile: a step's float32 scores are [2048, 512], 4 MiB


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _index_kernel(last_ref, q_ref, w_ref, pos_ref, k_ref, o_ref, *, heads):
    n, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tq, tk = o_ref.shape[1], o_ref.shape[2]

    @pl.when(j * tk <= last_ref[n, i])
    def _scores():
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [heads * tq, tk]
        s = jnp.maximum(s, 0.0) * w_ref[0, 0]
        total = s.reshape(heads, tq, tk).sum(axis=0)
        col = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        o_ref[0] = jnp.where(col <= pos_ref[0, 0], total, -jnp.inf)

    @pl.when(j * tk > last_ref[n, i])
    def _past():
        o_ref[0] = jnp.full((tq, tk), -jnp.inf, jnp.float32)


@register("dsa_index", "pallas")
def index_scores(q: jax.Array, k: jax.Array, w: jax.Array, q_positions: jax.Array) -> jax.Array:
    """``ops/dsa.py::index_scores``: q [N, C, H, D], k [N, S, D], w [N, C, H] float32, q_positions [N, C]
    -> float32 [N, C, S rounded up to whole key tiles]."""
    N, C, H, D = q.shape
    S = k.shape[1]
    tq, tk = _TQ, _TK
    Cp, Sp = -(-C // tq) * tq, -(-S // tk) * tk
    if Cp != C:
        q = jnp.pad(q, ((0, 0), (0, Cp - C), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, Cp - C), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, Cp - C)), constant_values=-1)
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0)))
    nt = Cp // tq
    # a tile's rows head-major: [N, tiles, H * tq, D], the weights a column beside them
    q2 = q.reshape(N, nt, tq, H, D).transpose(0, 1, 3, 2, 4).reshape(N, nt, H * tq, D)
    w2 = w.astype(jnp.float32).reshape(N, nt, tq, H).transpose(0, 1, 3, 2).reshape(N, nt, H * tq, 1)
    pos = q_positions.astype(jnp.int32).reshape(N, nt, tq, 1)
    last = pos[..., 0].max(axis=-1)  # [N, nt]: the last position a tile's queries may see

    out = pl.pallas_call(
        functools.partial(_index_kernel, heads=H),
        name="dsa_index",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N, nt, Sp // tk),
            in_specs=[
                pl.BlockSpec((1, 1, H * tq, D), lambda n, i, j, last: (n, i, 0, 0)),
                pl.BlockSpec((1, 1, H * tq, 1), lambda n, i, j, last: (n, i, 0, 0)),
                pl.BlockSpec((1, 1, tq, 1), lambda n, i, j, last: (n, i, 0, 0)),
                # past a tile's last position the block stays where it was: no fetch for a step that computes nothing
                pl.BlockSpec((1, tk, D), lambda n, i, j, last: (n, jnp.minimum(j, jnp.maximum(last[n, i], 0) // tk), 0)),
            ],
            out_specs=pl.BlockSpec((1, tq, tk), lambda n, i, j, last: (n, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((N, Cp, Sp), jnp.float32),
        compiler_params=tpu_compiler_params(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(last, q2, w2, pos, k)
    return out if Cp == C else out[:, :C]


# ---------------------------------------------------------------- the choice of the kept tokens
_NO_CANDIDATE = np.int32(-0x7F800001)  # the ordered key of -inf: every candidate's is over it
_LANES = 128
# what a grid step may hold, of Mosaic's default 16 MiB scope (``paged_attention._LATENT_VMEM_BUDGET`` says why no
# kernel here asks for more): two slots of a tile's scores, its keys, and the mask's block twice
_SELECT_VMEM_BUDGET = 12 << 20


def _select_form(C: int, Sp: int, itemsize: int):
    """(queries a tile, columns a chunk) of ``dsa_select`` from the call's shapes, or None where no tile fits:
    the most queries, by halving from 64, whose scores (two slots: the next tile's are in flight), keys and mask
    (two blocks: the last tile's is on its way out) fit the budget (a bisection step is one lane reduce a row
    whatever the tile, so the larger tile pays fewer); 64 queries x 8,704 columns with a bf16 mask: 8.9 MiB.
    A chunk is the widest of 512, 256 and 128 columns that divides the row (the index kernel's rows: 512)."""
    chunk = next(t for t in (512, 256, 128) if Sp % t == 0)
    for tq in (64, 32, 16):
        if tq <= max(C, 16) and tq * Sp * (2 * 4 + 4 + 2 * itemsize) <= _SELECT_VMEM_BUDGET:
            return tq, chunk
    return None


def _select_kernel(last_ref, s_hbm, o_ref, buf, keys, sems, *, topk, T, tiles):
    g, steps = pl.program_id(0), pl.num_programs(0)
    tq, Sp = keys.shape
    slot = g % 2
    i32 = jnp.int32

    def live(step):  # the column chunks a tile's queries can see: up to its last position's (-1: none)
        return (last_ref[step] + T) // T

    def chunk_dma(step, c, slot):
        cols = pl.ds(pl.multiple_of(c * T, T), T)
        return pltpu.make_async_copy(s_hbm.at[step // tiles, pl.ds((step % tiles) * tq, tq), cols],
                                     buf.at[slot, :, cols], sems.at[slot])

    def fetch(step, slot):
        pl.loop(0, live(step))(lambda c: chunk_dma(step, c, slot).start())

    @pl.when(g == 0)
    def _first():
        fetch(0, 0)

    @pl.when(g + 1 < steps)  # the next tile's scores fly while this one's are bisected
    def _next():
        fetch(g + 1, 1 - slot)

    n = live(g)

    @pl.loop(0, n)
    def _keys(c):  # a chunk as it lands
        chunk_dma(g, c, slot).wait()
        cols = pl.ds(pl.multiple_of(c * T, T), T)
        keys[:, cols] = _ordered(buf[slot, :, cols])

    lane = jax.lax.broadcasted_iota(i32, (tq, _LANES), 1)
    wide = lambda column: jnp.broadcast_to(column, (tq, _LANES))  # noqa: E731  ([tq, 1]: once a pass, not a vreg)

    def count(holds):
        """int32 [tq, 1]: a row's live columns where ``holds(keys [tq, 128], their first column)``: compares
        and adds across the column vregs, one lane reduce a row."""
        def chunk(c, acc):
            for u in range(T // _LANES):
                first = pl.multiple_of(c * T + u * _LANES, _LANES)
                acc = acc + holds(keys[:, pl.ds(first, _LANES)], first).astype(i32)
            return acc

        return jax.lax.fori_loop(0, n, chunk, jnp.zeros((tq, _LANES), i32)).sum(axis=1, keepdims=True)

    def search():
        """(a row's ``topk``-th largest key, the column its ties stop before), the keys bisected a bit at a
        time from the sign down as ``select_mask`` has it; a row with fewer candidates keeps them all."""
        def enough(at):  # (rows with topk keys or more at or over ``at``, how many)
            at = wide(at)
            over = count(lambda k, _: k >= at)
            return over >= topk, over

        ok, over = enough(jnp.zeros((tq, 1), i32))
        start = jnp.where(ok, np.int32(0), _INT_MIN), jnp.where(ok, over, n * T)

        def bit(i, carry):
            kth, at_or_over = carry
            at = kth | jnp.left_shift(np.int32(1), 30 - i)
            ok, over = enough(at)
            return jnp.where(ok, at, kth), jnp.where(ok, over, at_or_over)

        kth, at_or_over = jax.lax.fori_loop(0, 31, bit, start)
        found = kth > _NO_CANDIDATE  # (under it: fewer candidates than topk, all kept)
        bits = Sp.bit_length()  # of a column's index; all of them set is over every column

        def lower_first():
            """Ties at the threshold go to the lower positions: the largest ``m`` with no more of a row's tied
            columns before it than the row may still take, a bit at a time (a row without excess: every bit)."""
            at = wide(kth)
            left = topk - count(lambda k, _: k > at)

            def bit(i, m):
                to = m | jnp.left_shift(np.int32(1), bits - 1 - i)
                before = wide(to)
                return jnp.where(count(lambda k, first: (k == at) & (first + lane < before)) <= left, to, m)

            return jax.lax.fori_loop(0, bits, bit, jnp.zeros((tq, 1), i32))

        excess = (found & (at_or_over > topk)).astype(i32).max() > 0
        stop = jax.lax.cond(excess, lower_first, lambda: jnp.full((tq, 1), 2 ** bits - 1, i32))
        return jnp.where(found, kth, _NO_CANDIDATE), jnp.where(found, stop, 0)

    # a tile with no query past topk - 1 (pads at -1 among them) seeks no threshold: every candidate is kept
    kth, stop = jax.lax.cond(last_ref[g] >= topk, search,
                             lambda: (jnp.full((tq, 1), _NO_CANDIDATE, i32), jnp.zeros((tq, 1), i32)))
    kth, stop = wide(kth), wide(stop)

    @pl.loop(0, n)
    def _seen(c):
        for u in range(T // _LANES):
            first = pl.multiple_of(c * T + u * _LANES, _LANES)
            k = keys[:, pl.ds(first, _LANES)]
            kept = (k > kth) | ((k == kth) & (first + lane < stop))
            o_ref[0, :, pl.ds(first, _LANES)] = kept.astype(jnp.float32).astype(o_ref.dtype)

    @pl.loop(n, Sp // T)
    def _past(c):
        o_ref[0, :, pl.ds(pl.multiple_of(c * T, T), T)] = jnp.zeros((tq, T), o_ref.dtype)


@register("dsa_select", "pallas")
def select_mask(scores: jax.Array, topk: int, q_positions: jax.Array = None, dtype=jnp.bool_) -> jax.Array:
    """``ops/dsa.py::select_mask``: float32 scores [N, C, S], ``-inf`` past a query's position ``q_positions``
    [N, C] (None: a query may see every column) -> 0/1 in ``dtype`` [N, C, S]."""
    N, C, S = scores.shape
    out = jnp.dtype(jnp.bfloat16 if dtype == jnp.bool_ else dtype)  # (a bool block is an int32 one to Mosaic)
    Sp = -(-S // _LANES) * _LANES
    form = _select_form(C, Sp, out.itemsize)
    if form is None:  # a row too wide for a tile of 16
        return dispatch("dsa_select", "xla")(scores, topk, q_positions, dtype)
    tq, T = form
    Cp = -(-C // tq) * tq
    if q_positions is None:
        q_positions = jnp.full((N, C), S - 1, jnp.int32)
    if (Cp, Sp) != (C, S):  # a padded query sees nothing, a padded column is no candidate
        scores = jnp.pad(scores, ((0, 0), (0, Cp - C), (0, Sp - S)), constant_values=-jnp.inf)
        q_positions = jnp.pad(q_positions, ((0, 0), (0, Cp - C)), constant_values=-1)
    tiles = Cp // tq
    last = jnp.clip(q_positions.astype(jnp.int32).reshape(N * tiles, tq).max(axis=-1), -1, Sp - 1)

    mask = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, T=T, tiles=tiles),
        name="dsa_select",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the last position a tile's queries may see
            grid=(N * tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, tq, Sp), lambda g, last: (g // tiles, g % tiles, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, tq, Sp), jnp.float32),  # a tile's scores, two slots: one bisected, one filling
                pltpu.VMEM((tq, Sp), jnp.int32),  # its ordered keys
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N, Cp, Sp), out),
        # steps in order: each starts the next one's fetch
        compiler_params=tpu_compiler_params(dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(last, scores)
    if (Cp, Sp) != (C, S):
        mask = mask[:, :C, :S]
    return mask != 0 if dtype == jnp.bool_ else mask
