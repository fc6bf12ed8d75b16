"""Flash attention as a Pallas TPU kernel (fwd + custom-VJP bwd).

TPU-native replacement for the reference's attention kernel families:
``csrc/transformer/inference/csrc/softmax.cu`` (masked/alibi softmax),
``csrc/transformer/softmax_kernels.cu`` and the Triton flash variants
(``deepspeed/ops/transformer/inference/triton/attention.py``). Design is
blockwise online-softmax (Flash-Attention-2 style): the score matrix is never
materialized in HBM; K/V stream through VMEM in (block_k x head_dim) tiles
while running max/denominator/accumulator live in VMEM scratch.

Layout: inputs are [B, S, H, D] (framework-native); the kernel works on
[B, H, S, D]. GQA/MQA is handled in the index maps (kv head = q head // G),
so grouped heads re-read the same KV tile — no KV replication in HBM.

Backward: a score block costs what its [block_q, block_k] passes cost,
whatever the head size, so the backward visits each block ONCE while it can.
``flash_bwd_dkv`` walks key columns (outer) over query rows (inner), forms
s, p, dp and ds once a block, accumulates dv and dk in scratch and adds
ds @ k into the head's whole fp32 dq, which stays in VMEM scratch over the
walk and is written out once a head: five products and one exp2 a block. That
dq slab is S x max(D, 128) x 4 bytes, and its output block as much again in
bf16 (double-buffered); past ``_ONE_PASS_DQ_BYTES`` of it (S over 8,192 at
head_dim up to 128) ``flash_bwd_dq`` makes dq on the row-major walk and
``flash_bwd_dkv`` only dk and dv, each forming the scores for itself (seven
products, two exp2). ``_flash_bwd`` chooses from S, D and the dtype's size
alone; there is no option. The gradients leave the kernels in their final
form: the softmax scale (dq) and the base-2 softmax's ln2 (dk) are applied in
fp32 inside, then the one rounding to the model's dtype; XLA only transposes
them (with grouped heads dk and dv stay fp32 per query head for the sum over
the group).

Row statistics: lse (out of the forward) and delta (into the backward) cross
the kernels' boundary as ``[B, H, 1, S]`` fp32 rows, the sequence on the
lanes, in ``T(1,128)`` tiles: their numbers and no padding (a ``[B, H, S, 8]``
column is stored 128 lanes wide, and a layer scan stacks, holds and slices
the residual: 805 MB a micro-step for 50 MB of numbers at the 410M train
cell). The forward turns its column into a row once a query block; the dkv
kernel, whose query block changes every step, forms its scores transposed
(``k q^T``) so the rows broadcast down sublanes with no relayout; the dq
kernel turns a query block's rows into columns once for all its key blocks.

Performance notes (measured on v5e):
  - transposed scores in the dkv kernel, a key block taken whole, take 16%
    off the one-pass backward at head_dim 64 and 8% at 128 (0.846 -> 0.710 ms
    at [2, 2048, 16, 64], 0.387 -> 0.356 at [1, 2048, 16, 128]: one
    transposed-lhs product where there were two, no lane reductions of the
    statistics, gradients rounded inside; in chunks of 256 columns, which the
    old form gained 4.5% from, 0.750 and 0.375); turning the rows into columns
    every step instead costs 4-10% MORE than the padded columns did (0.877,
    0.424 ms); the forward's once-a-block transpose costs 0.9% (0.391 ->
    0.395, 0.190 -> 0.192), and reading m / l as lane 0 of their scratch
    instead of reducing over its lanes DOUBLES the forward (0.78, 0.39)
    (tools/flash_kernel_bench.py; PERF.md, PR 44)
  - the one-pass backward takes 30% less than the pair at the train cells'
    shapes (0.847 against 1.213 ms at [2, 2048, 16, 64], 0.386 against 0.549
    at [1, 2048, 16, 128]: tools/flash_kernel_bench.py; PERF.md, PR 32), the
    slab's read-add-write costing nothing measurable beside the fifth product
  - every matmul is input-dtype (bf16) with fp32 accumulation; fp32 operands
    run the MXU at ~1/4 rate
  - blocks that sit strictly below the causal diagonal skip ALL mask work
    (iota/compare/select are VPU passes over [block_q, block_k] and dominate
    the kernel when applied to every block); only diagonal-crossing blocks
    mask, and the padding keep-mask is applied only when the caller passed one
  - grid dims (b, h, q) are declared parallel so Mosaic double-buffers the
    next block's DMA across grid steps
  - for causal + no user mask, tail padding introduced by the wrapper needs no
    masking at all: padded key columns are only visible to padded query rows,
    whose outputs are sliced off (and whose incoming gradients are zero)

Causality and padding are one combined mask on the diagonal path, so in-kernel
there is a single masking code path per block class.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.compat import tpu_compiler_params

from deepspeed_tpu.ops.registry import register

_NEG_INF = float(jnp.finfo(jnp.float32).min)
# The kernels run the softmax in BASE 2: XLA/Mosaic lower exp(x) as
# exp2(x * log2(e)), so folding log2(e) into the query pre-scale removes one
# full [block_q, block_k] VPU multiply per exp site (fwd + both backwards).
# The ln2 factor that base-2 softmax gradients pick up is applied exactly on
# the wrapper side: dq's ln2*log2e cancels to 1, dk gets one fp32 multiply
# (see _flash_vjp_bwd) — no extra in-kernel passes, no bf16 rounding bias.
_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_LANES = 8  # lane width of the forward's m / l scratch columns and of the alibi slopes' [H, 1, _LANES] (1 KB)
_VREG_LANES = 128  # a column becomes a row (and back) through a [n, 128] <-> [128, n] transpose


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# out_shape structs carry the inputs' varying-manual-axes (check_vma)
from deepspeed_tpu.utils.compat import shape_dtype_struct as _sds


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _block_classes(qi, ki, block_q, block_k):
    """(full_below, crosses_diag) for causal attention.

    full_below: every (row, col) in the block satisfies col <= row — no mask.
    crosses_diag: block intersects the diagonal — needs the iota mask.
    Blocks strictly above the diagonal are skipped entirely.
    """
    full_below = ki * block_k + block_k - 1 <= qi * block_q
    touches = ki * block_k <= qi * block_q + block_q - 1
    return full_below, touches & ~full_below


def _causal_keep(qi, ki, shape, block_q, block_k, col_off=0, key_axis=1):
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - key_axis)
    cols = ki * block_k + col_off + jax.lax.broadcasted_iota(jnp.int32, shape, key_axis)
    return cols <= rows


def _band_keep(qi, ki, shape, block_q, block_k, window, col_off=0, key_axis=1):
    """``ops/attention.py::band_keep`` on a cell's own rows and columns."""
    from deepspeed_tpu.ops.attention import band_keep

    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - key_axis)
    cols = ki * block_k + col_off + jax.lax.broadcasted_iota(jnp.int32, shape, key_axis)
    return band_keep(rows, cols, window)


def _band_first(qi, block: int, window: int):
    """The first key block a query block still sees under a band of ``window`` keys."""
    return jnp.maximum(qi * block - (window - 1), 0) // block


def _band_cells(n: int, block: int, window: int):
    """``_tri_cells`` with a lower bound: for each query row qi the key columns
    from the first one any of its queries still sees (``_band_first``) to qi.
    Cells wholly under the band are no part of the grid: no DMA, no compute."""
    import numpy as np

    first = np.maximum(np.arange(n) * block - (window - 1), 0) // block
    counts = np.arange(n) - first + 1
    qs = np.repeat(np.arange(n), counts)
    starts = np.repeat(np.cumsum(counts) - counts, counts)
    ks = np.arange(qs.size) - starts + np.repeat(first, counts)
    return qs.astype(np.int32), ks.astype(np.int32)


def _band_maps(n: int, block: int, window: int):
    """``_band_cells`` as the kernel's scalar operands."""
    qs, ks = _band_cells(n, block, window)
    return jnp.asarray(qs), jnp.asarray(ks)


def _row_of(col):
    """[n, 1] -> [1, n], the values unchanged: the column across a vreg row's
    lanes, transposed, its first sublane."""
    return jnp.broadcast_to(col, (col.shape[0], _VREG_LANES)).T[:1]


def _cols_of(row):
    """[1, n] -> [n, 128], every lane the row's value: ``[:, :1]`` is the column."""
    return jnp.broadcast_to(row, (_VREG_LANES, row.shape[1])).T


# The squashed grids ship their (qi, ki) enumeration as scalar-prefetch SMEM
# arrays of n(n+1)/2 entries. Past this cap the SMEM cost outweighs the
# skipped above-diagonal DMAs and the wrappers fall back to the dense causal
# grid (which skips the same compute via block classes, just not the DMAs).
# At block 512 this covers sequences up to ~90k tokens per device.
_MAX_SQUASHED_CELLS = 16384


def _squash_ok(nq: int, nk: int, block_q: int, block_k: int, causal: bool) -> bool:
    return (causal and block_q == block_k and nq == nk
            and nq * (nq + 1) // 2 <= _MAX_SQUASHED_CELLS)


def _tri_cells(n: int):
    """Row-major lower-triangle enumeration: for each query row qi, the active
    key columns ki in [0, qi]. The causal grid runs ONLY these n(n+1)/2 cells
    (vs n^2): above-diagonal cells would DMA K/V and then skip all compute.
    Pure arange arithmetic — no O(n^2) Python pair list at trace time."""
    import numpy as np

    counts = np.arange(1, n + 1)
    qs = np.repeat(np.arange(n), counts)
    starts = np.repeat(np.cumsum(counts) - counts, counts)
    ks = np.arange(qs.size) - starts
    return qs.astype(np.int32), ks.astype(np.int32)


def _tri_maps(n: int):
    """``_tri_cells`` as the kernel's scalar operands."""
    qs, ks = _tri_cells(n)
    return jnp.asarray(qs), jnp.asarray(ks)


def _wedge_maps(n: int):
    """Column-major enumeration of the same triangle: for each key column ki,
    the query rows qi in [ki, n-1] contiguously (dk/dv accumulate per column)."""
    import numpy as np

    counts = np.arange(n, 0, -1)
    ks = np.repeat(np.arange(n), counts)
    starts = np.repeat(np.cumsum(counts) - counts, counts)
    qs = (np.arange(ks.size) - starts) + ks
    return jnp.asarray(qs, jnp.int32), jnp.asarray(ks, jnp.int32)


# Grid-argument decoders: every BlockSpec index map below is written against
# canonical (b, h, qi, ki) and composed with the decoder for the grid in use,
# so the squashed (scalar-prefetch) and dense variants share one spec list.
_DEC_SQUASHED = lambda b, h, t, qm, km, *_: (b, h, qm[t], km[t])  # noqa: E731
_DEC_DENSE = lambda b, h, qi, ki: (b, h, qi, ki)  # noqa: E731
_DEC_DENSE_KQ = lambda b, h, ki, qi: (b, h, qi, ki)  # noqa: E731  (dkv grid order)


def _live_blocks(lengths, block: int):
    """The query blocks of each row that hold a live token, where a row's live tokens come first."""
    return (lengths + block - 1) // block


def forward_cells(lengths, S: int, window: Optional[int] = None, block: int = DEFAULT_BLOCK_Q):
    """(live, grid): the cells a head that ``flash_causal_attention(lengths=)`` runs over rows of ``S`` tokens
    with these live lengths, and the cells of the rows' whole grids (what it ran before it was told), by the
    kernels' own enumerations; NumPy alone, on the host, for a counter."""
    import numpy as np

    block = min(block, max(S, 8))
    n = _cdiv(S, block)
    qm = (_tri_cells(n) if window is None else _band_cells(n, block, window))[0]
    live = _live_blocks(np.asarray(lengths), block)
    return int((qm[None, :] < live[:, None]).sum()), int(qm.size * live.size)


def _live_grid(lengths, qm, km, nq: int, block: int):
    """A squashed forward's grid for rows whose live ``lengths`` it is told ->
    (its third extent, a traced scalar; its five scalar-prefetch operands).

    Both enumerations are row-major by query block, so the cells of the
    LONGEST row's live blocks are a prefix of ``(qm, km)``: the grid runs them
    and then ONE step for each query block past them (the tail: ``ki = qi``,
    so it is its block's first step and its last), which writes the block as
    zeros. ``qo, ko`` is that enumeration, what a step writes. What a step
    FETCHES (q, k, v) is row by row ``qf, kf`` (``[B * cells]``): the same,
    clamped to the row's last live cell (the diagonal one of its last live
    block), so a run of dead steps fetches nothing after its first. ``rows``
    is each row's live blocks, then each row's live tokens. All of it is made
    here, once a call, so that an index map is one read of SMEM as it was."""
    live = _live_blocks(lengths, block)
    longest = jnp.max(live)
    cells = jnp.sum(qm < longest)
    t = jnp.arange(qm.shape[0])
    qo = jnp.where(t >= cells, jnp.minimum(longest + t - cells, nq - 1), qm)
    ko = jnp.where(t >= cells, qo, km)
    last = jnp.maximum(live - 1, 0)[:, None]
    return cells + nq - longest, (qo, ko, jnp.minimum(qo, last).reshape(-1), jnp.minimum(ko, last).reshape(-1),
                                  jnp.concatenate([live, lengths]))


# ``_DEC_SQUASHED`` for what a step of ``_live_grid`` fetches
_DEC_LIVE_FETCH = lambda b, h, t, qo, ko, qf, kf, rows: (  # noqa: E731
    b, h, qf[b * qo.shape[0] + t], kf[b * qo.shape[0] + t])


def _spec(shape, f, dec):
    return pl.BlockSpec(shape, lambda *a: f(*dec(*a)))


def _qkv_in_specs(dec, block_q, block_k, D, G, alibi=False):
    """mask, [slopes], q, k, v input specs (shared by fwd and both backward
    kernels). The alibi slopes ride as a tiny [H, 1, _LANES] fp32 array blocked
    per query head (as [H, _LANES] Mosaic refuses the block of one head: its
    last two dimensions have to be the array's own or multiples of (8, 128))."""
    specs = [_spec((1, 1, block_k), lambda b, h, qi, ki: (b, 0, ki), dec)]
    if alibi:
        specs.append(_spec((1, 1, _LANES), lambda b, h, qi, ki: (h, 0, 0), dec))
    specs += [
        _spec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0), dec),
        _spec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // G, ki, 0), dec),
        _spec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // G, ki, 0), dec),
    ]
    return specs


def _alibi_add(s, slopes_ref, ki, block_k, col_off=0, key_axis=1):
    """s += slope[h] * key-position, in the caller's softmax scale (the
    wrapper pre-folds log2e into the slopes for the base-2 kernels). The HF
    bloom convention (slopes * j); softmax cancels the per-row shift vs
    slopes * (j - i)."""
    cols = ki * block_k + col_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, key_axis)
    return s + slopes_ref[0, :, :1] * cols.astype(jnp.float32)


def _qrow_specs(dec, block_q, D):
    """do, lse, delta input specs (backward) / o, lse output specs (forward)
    — everything blocked along the query row. The statistics (lse, delta) are
    ``[B, H, 1, S]`` in HBM, the sequence on the lanes: a ``[.., S, n]`` column
    is stored n -> 128 lanes wide, which the train scan then stacks and slices."""
    return {
        "qD": _spec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0), dec),
        "qL": _spec((1, 1, 1, block_q), lambda b, h, qi, ki: (b, h, 0, qi), dec),
    }


def _kcol_spec(dec, block_k, D):
    """dk/dv output spec — blocked along the key column."""
    return _spec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, 0), dec)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _sub_slices(block_k: int, k_splits: int):
    """Static row/col ranges splitting a block_k tile into k_splits chunks."""
    c = block_k // k_splits
    return [(i * c, c) for i in range(k_splits)]


def _sub_score(q, k, mask_ref, slopes_ref, qi, ki, off, c, *, block_q, block_k,
               masked, mask_block, alibi, keys_first=False, band=None):
    """Masked scores for one sub-chunk: s = q @ k[off:off+c]^T (+alibi, +mask),
    ``[block_q, c]``; with ``keys_first`` the same scores transposed, s^T =
    k[off:off+c] @ q^T, ``[c, block_q]``, so that a ROW of per-query statistics
    broadcasts down its sublanes (the dkv kernel).

    The one scoring implementation shared by the forward and both backward
    kernels — the mask/bias math must never diverge between passes."""
    key_axis = 0 if keys_first else 1
    a, b = (k[off:off + c], q) if keys_first else (q, k[off:off + c])
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    if alibi:
        s = _alibi_add(s, slopes_ref, ki, block_k, col_off=off, key_axis=key_axis)
    if mask_block or masked or band is not None:
        keep = None
        if band is not None:  # a cell on the band's lower edge (forward alone)
            keep = _band_keep(qi, ki, s.shape, block_q, block_k, band, col_off=off, key_axis=key_axis)
        if masked:
            kept = mask_ref[0, :, off:off + c]  # [1, c]: keys on the lanes
            user = jnp.broadcast_to((_cols_of(kept)[:, :1] if keys_first else kept) > 0, s.shape)
            keep = user if keep is None else keep & user
        if mask_block:
            ck = _causal_keep(qi, ki, s.shape, block_q, block_k, col_off=off, key_axis=key_axis)
            keep = ck if keep is None else keep & ck
        s = jnp.where(keep, s, _NEG_INF)
    return s


def _fwd_kernel(*refs, block_q, block_k, causal, masked, squashed, alibi=False,
                k_splits=1, window=None, lengths=False, sink=False):
    alive = None  # with ``lengths``: whether the step's query block holds a live token of its row
    sink_ref = None  # with ``sink``: the head's logit in the softmax's denominator, [1, 1, _LANES] (log2e-scaled)
    if squashed:
        (qm_ref, km_ref, *rest) = refs
        if lengths:  # (``_live_grid``: what a step fetches is the index maps' alone)
            _, _, rows_ref, *rest = rest
        mask_ref = rest.pop(0)
        slopes_ref = rest.pop(0) if alibi else None
        sink_ref = rest.pop(0) if sink else None
        (q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref) = rest
        t = pl.program_id(2)
        qi, ki = qm_ref[t], km_ref[t]
        first, last = ki == (0 if window is None else _band_first(qi, block_q, window)), ki == qi
        if lengths:
            b = pl.program_id(0)
            alive = qi < rows_ref[b]
            fed = rows_ref[rows_ref.shape[0] // 2 + b] - qi * block_q  # the block's live rows come first: this many
            first |= last & ~alive  # (a step of the tail is its block's first and last)
    else:
        (mask_ref, *rest) = refs
        slopes_ref = rest.pop(0) if alibi else None
        (q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref) = rest
        qi, ki = pl.program_id(2), pl.program_id(3)
        first, last = ki == 0, ki == pl.num_programs(3) - 1

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _compute(mask_block, band=None):
        q = q_ref[0, 0]  # [block_q, D]  (pre-scaled by 1/sqrt(D))
        k = k_ref[0, 0]  # [block_k, D]
        v = v_ref[0, 0]
        sub = _sub_slices(block_k, k_splits)

        def _score(off, c):
            return _sub_score(q, k, mask_ref, slopes_ref, qi, ki, off, c,
                              block_q=block_q, block_k=block_k, masked=masked,
                              mask_block=mask_block, alibi=alibi, band=band)

        s_next = _score(*sub[0])
        for idx, (off, c) in enumerate(sub):
            s = s_next
            if idx + 1 < k_splits:
                # Hoisted ahead of this chunk's softmax: the next QK^T reads
                # nothing from m/l/acc, so the MXU can run it while the VPU
                # does the exp2/renormalize passes below.
                s_next = _score(*sub[idx + 1])

            m_prev = jnp.max(m_ref[:], axis=-1, keepdims=True)  # [block_q, 1] (lanes equal)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # All-masked rows keep m at -inf; guard exp against (-inf) - (-inf).
            m_safe = jnp.where(m_cur == _NEG_INF, 0.0, m_cur)
            p = jnp.exp2(s - m_safe)  # masked entries: exp2(NEG_INF - finite) == 0

            alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp2(m_prev - m_safe))
            l_prev = jnp.max(l_ref[:], axis=-1, keepdims=True)
            l_cur = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            l_ref[:] = jnp.broadcast_to(l_cur, l_ref.shape)
            m_ref[:] = jnp.broadcast_to(m_cur, m_ref.shape)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v[off:off + c], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    def live(cell):
        """A dead cell (``lengths``) computes nothing: ``_init`` and ``_finalize`` alone run for its query
        block, which is written as zeros (``acc`` 0 over ``l_safe`` 1) with an lse of -inf."""
        return cell if alive is None else cell & alive

    if window is not None:
        # the grid enumerates the band's cells alone (``_band_maps``): the diagonal
        # cell masks causally, a cell that reaches under the band masks its lower
        # edge (one cell in eight where the window is eight blocks), the rest nothing
        edge = qi * block_q + block_q - 1 - ki * block_k >= window
        pl.when(live((ki < qi) & ~edge))(lambda: _compute(False))
        pl.when(live((ki == qi) & ~edge))(lambda: _compute(True))
        pl.when(live((ki < qi) & edge))(lambda: _compute(False, window))
        pl.when(live((ki == qi) & edge))(lambda: _compute(True, window))
    elif causal and squashed:
        # the grid enumerates only ki <= qi; the diagonal cell masks in-block
        pl.when(live(ki < qi))(lambda: _compute(False))
        pl.when(live(ki == qi))(lambda: _compute(True))
    elif causal:
        full_below, diag = _block_classes(qi, ki, block_q, block_k)
        pl.when(full_below)(lambda: _compute(False))
        pl.when(diag)(lambda: _compute(True))
    else:
        _compute(False)

    @pl.when(last)
    def _finalize():
        l = jnp.max(l_ref[:], axis=-1, keepdims=True)
        if alive is not None:  # the pads beside a row's last token: zeros, as the dead blocks after them
            l = jnp.where(jax.lax.broadcasted_iota(jnp.int32, l.shape, 0) < fed, l, 0.0)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        row_max = lambda: jnp.max(m_ref[:], axis=-1, keepdims=True)  # noqa: E731
        acc, l_out, m = acc_ref[:], l_safe, None
        if sink_ref is not None:
            # the sink joins the sum and weighs no value: the output over l + 2^(sink - m), both terms taken
            # against the larger of m and the sink so that neither power overflows; a row that saw no key (l 0)
            # stays zeros. The lse handed out is the keys' alone, as without a sink.
            m, s2 = row_max(), sink_ref[0, :, :1]  # [block_q, 1], [1, 1]
            top = jnp.maximum(jnp.where(m == _NEG_INF, s2, m), s2)
            shrink = jnp.where(m == _NEG_INF, 0.0, jnp.exp2(m - top))
            acc, l_out = acc * shrink, l * shrink + jnp.exp2(s2 - top)
        out = acc / l_out
        o_ref[0, 0] = (out if alive is None else jnp.where(l == 0.0, 0.0, out)).astype(o_ref.dtype)
        if m is None:  # (with no sink the kernel reads its refs in the order it always did: the program it was)
            m = row_max()
        # base-2 logsumexp per row; fully-masked rows get -inf. The column
        # becomes a lane-dense row here, once a query block.
        lse = jnp.where(l == 0.0, _NEG_INF, m + jnp.log2(l_safe))
        lse_ref[0, 0] = _row_of(lse)


_PARALLEL_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")


def _flash_fwd(q, k, v, mask, slopes, block_q: int, block_k: int, causal: bool,
               masked: bool, alibi: bool, k_splits: int = 1, window: Optional[int] = None, lengths=None,
               sinks=None):
    """q,k,v: [B, H(q/kv), S, D] (q pre-scaled); ``v`` may be narrower than
    ``k`` (``[.., Dv]``: the output is then ``Dv`` wide, the key's ``D`` lies in
    its blocks as it comes, whole 128-lane tiles or not). mask: [B, S] int32.
    slopes: [H, 1, _LANES] fp32 (log2e-scaled; ignored unless alibi).
    Returns (out, lse): lse fp32 ``[B, H, 1, S]``, base 2. ``window`` (a band
    under the causal mask, ``_band_maps``) names the kernel ``swa_flash_fwd``.
    ``sinks`` ([H, 1, _LANES] fp32, log2e-scaled, the squashed grids alone): a
    logit a query head that joins the softmax's denominator in ``_finalize``
    and nothing else; None builds the kernel as it was.

    ``lengths`` (int32 ``[B]``): a row's live tokens, which come FIRST in the
    row: a fresh prompt padded to its bucket. That says more than ``mask``
    does, which may have holes and only ever masks elements: every row at or
    past ``lengths[b]`` is a pad whose output nobody reads, and under the
    causal mask no live query sees a pad's key. The pads come back as zeros
    with an lse of -inf (not whatever the buffer held: a NaN in a pad's value
    would reach live rows through the next layer's ``p @ v`` at weight 0), and
    on the squashed grids the kernel neither fetches nor computes a cell whose
    query block lies wholly past ``lengths[b]`` (``_live_grid``): the grid's
    third extent is a traced scalar, the longest row's live cells and one step
    for each query block past them, a dead step's q, k and v maps point at the
    row's last live cell, and the rows' lengths ride beside the maps as scalar
    operands. A shorter row's dead cells run ``_init`` and ``_finalize``
    alone. The block that holds a row's last token and pads beside it runs
    whole. The choice is the kernel's from its operands: ``lengths=None``
    builds the kernel as it was; the dense grid, and a call whose rows' maps
    would pass ``_MAX_SQUASHED_CELLS`` entries of SMEM, compute the pads'
    cells as ever and zero their rows after."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    Dv = v.shape[-1]
    G = H // Hkv
    nq, nk = _cdiv(S, block_q), _cdiv(S, block_k)
    squashed = _squash_ok(nq, nk, block_q, block_k, causal)
    if (window is not None or sinks is not None) and not (squashed and not masked and not alibi):
        raise NotImplementedError(
            f"flash attention under a band (window={window}) or with a sink runs the squashed causal grid alone: "
            f"equal blocks (got {block_q}, {block_k}), no padding mask, no ALiBi; the dense path takes the rest")

    out_shape = [
        _sds((B, H, S, Dv), q.dtype, q, k, v, mask),
        _sds((B, H, 1, S), jnp.float32, q, k, v, mask),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, Dv), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
    ]
    if lengths is not None:
        lengths = jnp.clip(lengths.astype(jnp.int32), 0, S)
    cells = nq * (nq + 1) // 2  # (the band's are fewer)
    skips = squashed and lengths is not None and B * cells <= _MAX_SQUASHED_CELLS
    kernel = functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                               causal=causal, masked=masked, squashed=squashed,
                               alibi=alibi, k_splits=k_splits, window=window, lengths=skips,
                               sink=sinks is not None)
    dec = _DEC_SQUASHED if squashed else _DEC_DENSE
    fetch = _DEC_LIVE_FETCH if skips else dec
    in_specs = _qkv_in_specs(fetch, block_q, block_k, D, G, alibi=alibi)
    if Dv != D:  # the value's blocks at its own width
        in_specs[-1] = _spec((1, 1, block_k, Dv), lambda b, h, qi, ki: (b, h // G, ki, 0), fetch)
    qrow = _qrow_specs(dec, block_q, Dv)
    out_specs = [qrow["qD"], qrow["qL"]]
    extra = (slopes,) if alibi else ()
    if sinks is not None:  # a head's, as the slopes ride: after them, before q
        in_specs.insert(len(in_specs) - 3, _spec((1, 1, _LANES), lambda b, h, qi, ki: (h, 0, 0), fetch))
        extra += (sinks,)

    if squashed:
        qm, km = _tri_maps(nq) if window is None else _band_maps(nq, block_q, window)
        prefetch, cells = (qm, km), qm.shape[0]
        if skips:
            cells, prefetch = _live_grid(lengths, qm, km, nq, block_q)
        out, lse = pl.pallas_call(
            kernel,
            name="flash_fwd" if window is None else "swa_flash_fwd",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),  # qmap, kmap[, what a step fetches, the rows' lengths]
                grid=(B, H, cells),
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=scratch_shapes,
            ),
            out_shape=out_shape,
            compiler_params=tpu_compiler_params(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
        )(*prefetch, mask, *extra, q, k, v)
    else:
        out, lse = pl.pallas_call(
            kernel,
            name="flash_fwd",
            grid=(B, H, nq, nk),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            compiler_params=tpu_compiler_params(dimension_semantics=_PARALLEL_SEMANTICS),
            interpret=_interpret(),
        )(mask, *extra, q, k, v)
    if lengths is not None and not skips:
        fed = jnp.arange(S) < lengths[:, None]
        out = jnp.where(fed[:, None, :, None], out, jnp.zeros((), out.dtype))
        lse = jnp.where(fed[:, None, None, :], lse, _NEG_INF)
    return out, lse


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _bwd_dq_kernel(*refs, block_q, block_k, causal, masked, squashed, dq_scale,
                   alibi=False, k_splits=1):
    if squashed:
        (qm_ref, km_ref, mask_ref, *rest) = refs
        slopes_ref = rest.pop(0) if alibi else None
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref, lse_col, delta_col) = rest
        t = pl.program_id(2)
        qi, ki = qm_ref[t], km_ref[t]
        first, last = ki == 0, ki == qi
    else:
        (mask_ref, *rest) = refs
        slopes_ref = rest.pop(0) if alibi else None
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref, lse_col, delta_col) = rest
        qi, ki = pl.program_id(2), pl.program_id(3)
        first, last = ki == 0, ki == pl.num_programs(3) - 1

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # a query block's rows of statistics, turned into columns ONCE for all
        # of its key blocks (this walk keeps qi while ki runs)
        lse_col[:] = _cols_of(lse_ref[0, 0])
        delta_col[:] = _cols_of(delta_ref[0, 0])

    def _compute(mask_block):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_col[:, :1]  # [block_q, 1]
        lse_safe = jnp.where(lse == _NEG_INF, 0.0, lse)
        delta = delta_col[:, :1]
        sub = _sub_slices(block_k, k_splits)

        def _score(off, c):
            return _sub_score(q, k, mask_ref, slopes_ref, qi, ki, off, c,
                              block_q=block_q, block_k=block_k, masked=masked,
                              mask_block=mask_block, alibi=alibi)

        s_next = _score(*sub[0])
        for idx, (off, c) in enumerate(sub):
            s = s_next
            if idx + 1 < k_splits:
                s_next = _score(*sub[idx + 1])  # MXU overlaps the VPU passes below
            p = jnp.exp2(s - lse_safe)
            # bf16 x bf16 matmul with fp32 accumulation: fp32 operands would run
            # the MXU at a fraction of its bf16 rate (measured 4x slower on v5e).
            dp = jax.lax.dot_general(
                do, v[off:off + c], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta)
            acc_ref[:] += jax.lax.dot_general(
                ds.astype(k.dtype), k[off:off + c], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    if causal and squashed:
        pl.when(ki < qi)(lambda: _compute(False))
        pl.when(ki == qi)(lambda: _compute(True))
    elif causal:
        full_below, diag = _block_classes(qi, ki, block_q, block_k)
        pl.when(full_below)(lambda: _compute(False))
        pl.when(diag)(lambda: _compute(True))
    else:
        _compute(False)

    @pl.when(last)
    def _finalize():
        dq_ref[0, 0] = (acc_ref[:] * dq_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, block_q, block_k, causal, masked, squashed, nq_total,
                    dq_scale, dk_scale, alibi=False, k_splits=1, one_pass=False):
    """dk and dv of a key column, accumulated over its query rows (the grid
    runs key column outer, query rows inner). The query block changes every
    step here, so the scores are formed TRANSPOSED, ``s^T = k q^T``
    ``[keys, queries]``: lse and delta arrive as rows and broadcast down the
    sublanes with no relayout, ``dv += p^T @ do`` and ``dk += ds^T @ q`` are
    plain products. With ``one_pass`` the same ``ds^T`` also makes dq
    (``(ds^T)^T @ k``, the one transposed-lhs product): ``dq_acc`` is a whole
    head's fp32 ``[S, D]`` slab in VMEM scratch over both inner grid axes,
    each block adds into the slab's rows, and the head's last step writes it
    out once. A row block's terms still arrive in the order ki = 0, 1, ...,
    qi, as in ``_bwd_dq_kernel``. The gradients leave in their final form
    (``dq_scale``, ``dk_scale`` applied in fp32, then the output's dtype): no
    fp32 copy of them crosses HBM."""
    if squashed:
        (qm_ref, km_ref, mask_ref, *rest) = refs
        t = pl.program_id(2)
        qi, ki = qm_ref[t], km_ref[t]
        first, last, first_of_head = qi == ki, qi == nq_total - 1, t == 0
        last_of_head = t == pl.num_programs(2) - 1
    else:
        (mask_ref, *rest) = refs
        ki, qi = pl.program_id(2), pl.program_id(3)
        first, last = qi == 0, qi == pl.num_programs(3) - 1
        first_of_head = first & (ki == 0)
        last_of_head = last & (ki == pl.num_programs(2) - 1)
    slopes_ref = rest.pop(0) if alibi else None
    dq_acc = rest.pop() if one_pass else None
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *outs, dk_acc, dv_acc) = rest
    dq_ref = outs.pop(0) if one_pass else None
    dk_ref, dv_ref = outs

    if one_pass:
        @pl.when(first_of_head)
        def _init_dq():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute(mask_block):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # [1, block_q]
        lse_safe = jnp.where(lse == _NEG_INF, 0.0, lse)
        delta = delta_ref[0, 0]
        sub = _sub_slices(block_k, k_splits)

        def _score(off, c):
            return _sub_score(q, k, mask_ref, slopes_ref, qi, ki, off, c,
                              block_q=block_q, block_k=block_k, masked=masked,
                              mask_block=mask_block, alibi=alibi, keys_first=True)

        s_next = _score(*sub[0])
        for idx, (off, c) in enumerate(sub):
            s = s_next  # [c, block_q]
            if idx + 1 < k_splits:
                s_next = _score(*sub[idx + 1])  # MXU overlaps the VPU passes below
            p = jnp.exp2(s - lse_safe)
            # keep every matmul in the input dtype (bf16) with fp32 accumulation —
            # fp32 operands would cut the MXU rate ~4x (see _bwd_dq_kernel note)
            dv_acc[off:off + c] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(v[off:off + c], do, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(q.dtype)
            dk_acc[off:off + c] += jax.lax.dot_general(
                ds, q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if one_pass:
                rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
                dq_acc[rows, :] += jax.lax.dot_general(
                    ds, k[off:off + c], (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )

    if causal and squashed:
        pl.when(qi > ki)(lambda: _compute(False))
        pl.when(qi == ki)(lambda: _compute(True))
    elif causal:
        full_below, diag = _block_classes(qi, ki, block_q, block_k)
        pl.when(full_below)(lambda: _compute(False))
        pl.when(diag)(lambda: _compute(True))
    else:
        _compute(False)

    @pl.when(last)
    def _finalize():
        dk_ref[0, 0] = (dk_acc[:] * dk_scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)

    if one_pass:
        @pl.when(last_of_head)
        def _finalize_dq():
            dq_ref[0, 0] = (dq_acc[...] * dq_scale).astype(dq_ref.dtype)


# The one-pass backward keeps a whole head's dq in VMEM, [S, D] with D padded
# to the 128 lanes of a vreg row: once in fp32 (the scratch it adds into) and
# twice in the output's dtype (the pipeline double-buffers an output block).
# Twice this much in all (a bf16 dq of S = 8,192 at head_dim 64 and 128, 4,096
# at 256) compiles for a v5e beside the [512, 512] temporaries in 12 MiB of
# the 16 MiB a kernel may scope by default. Longer sequences run the dq kernel
# and the dkv kernel.
_ONE_PASS_DQ_BYTES = 4 * 1024 * 1024

def _one_pass_fits(S: int, D: int, itemsize: int = 2) -> bool:
    return S * _cdiv(D, 128) * 128 * (4 + 2 * itemsize) <= 2 * _ONE_PASS_DQ_BYTES


def _flash_bwd(q, k, v, mask, slopes, out, lse, do, block_q: int, block_k: int,
               causal: bool, masked: bool, alibi: bool, k_splits: int = 1,
               softmax_scale: Optional[float] = None):
    """The gradients of the caller's q, k, v (``[B, H(q/kv), S, D]``, q's
    dtype) from the kernels' base-2 quantities: dq times the softmax scale
    (the log2e of the pre-scale and the ln2 of the base-2 softmax cancel to
    1, exactly), dk times ln2 (it accumulates against the log2e-pre-scaled
    q), both applied in fp32 INSIDE the kernels before the one rounding; with
    grouped heads dk and dv leave the kernel in fp32 per QUERY head and are
    summed over the group, scaled and rounded here.
    One kernel, ``flash_bwd_dkv``, makes all three from one set of scores
    while a head's dq fits VMEM (``_one_pass_fits``: a choice from S, D and
    the dtype's size alone); past that ``flash_bwd_dq`` makes dq and
    ``flash_bwd_dkv`` dk and dv, each forming the scores for itself."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    nq, nk = _cdiv(S, block_q), _cdiv(S, block_k)
    squashed = _squash_ok(nq, nk, block_q, block_k, causal)
    one_pass = _one_pass_fits(S, D, q.dtype.itemsize)

    # [B, H, 1, S], as lse
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, :, None]

    static = dict(block_q=block_q, block_k=block_k, causal=causal, masked=masked,
                  squashed=squashed, alibi=alibi, k_splits=k_splits,
                  dq_scale=_scale(D, softmax_scale))
    extra = (slopes,) if alibi else ()
    final = _sds((B, H, S, D), q.dtype, q, k, v, mask, do)
    per_query_head = final if G == 1 else _sds((B, H, S, D), jnp.float32, q, k, v, mask, do)

    def call(kernel, name, walk, dense_semantics, out_specs, out_shape, scratch):
        """One backward kernel over ``walk`` = (the squashed grid's (qi, ki)
        enumeration, the dense grid's decoder to canonical (qi, ki) for the
        shared specs, the dense grid)."""
        maps, dense_dec, dense_grid = walk
        dec = _DEC_SQUASHED if squashed else dense_dec
        qrow = _qrow_specs(dec, block_q, D)
        in_specs = (_qkv_in_specs(dec, block_q, block_k, D, G, alibi=alibi)
                    + [qrow["qD"], qrow["qL"], qrow["qL"]])
        out_specs = out_specs(dec)
        args = (mask, *extra, q, k, v, do, lse, delta)
        common = dict(name=name, out_shape=out_shape, interpret=_interpret())
        if squashed:
            qm, km = maps(nq)
            return pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=2,
                    grid=(B, H, qm.shape[0]),
                    in_specs=in_specs,
                    out_specs=out_specs,
                    scratch_shapes=scratch,
                ),
                compiler_params=tpu_compiler_params(
                    dimension_semantics=("parallel", "parallel", "arbitrary")),
                **common,
            )(qm, km, *args)
        return pl.pallas_call(
            kernel,
            grid=dense_grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
            compiler_params=tpu_compiler_params(dimension_semantics=dense_semantics),
            **common,
        )(*args)

    if not one_pass:
        (dq,) = call(
            functools.partial(_bwd_dq_kernel, **static), "flash_bwd_dq",
            (_tri_maps, _DEC_DENSE, (B, H, nq, nk)), _PARALLEL_SEMANTICS,
            lambda dec: [_qrow_specs(dec, block_q, D)["qD"]], [final],
            [pltpu.VMEM((block_q, D), jnp.float32)]
            + [pltpu.VMEM((block_q, _VREG_LANES), jnp.float32)] * 2)

    def dkv_out_specs(dec):
        slab = [_spec((1, 1, S, D), lambda b, h, qi, ki: (b, h, 0, 0), dec)] if one_pass else []
        return slab + [_kcol_spec(dec, block_k, D)] * 2

    # The dense dkv grid iterates (ki outer, qi inner), as the wedge does; a
    # dq slab gathers over the key columns too, so only b and h stay parallel.
    *dq_slab, dk, dv = call(
        functools.partial(_bwd_dkv_kernel, nq_total=nq, one_pass=one_pass,
                          dk_scale=_LN2 if G == 1 else 1.0, **static),
        "flash_bwd_dkv", (_wedge_maps, _DEC_DENSE_KQ, (B, H, nk, nq)),
        ("parallel", "parallel", "arbitrary", "arbitrary") if one_pass else _PARALLEL_SEMANTICS,
        dkv_out_specs, [final] * one_pass + [per_query_head] * 2,
        [pltpu.VMEM((block_k, D), jnp.float32)] * 2 + [pltpu.VMEM((S, D), jnp.float32)] * one_pass)
    if one_pass:
        (dq,) = dq_slab

    if G > 1:
        dk = (dk.reshape(B, Hkv, G, S, D).sum(axis=2) * _LN2).astype(k.dtype)
        dv = dv.reshape(B, Hkv, G, S, D).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public op: [B, S, H, D] layout, custom VJP, padding + causal handling
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_attention(q, k, v, mask, slopes, block_q, block_k, causal, masked, alibi,
                     k_splits=1, softmax_scale=None):
    out, _ = _flash_core(q, k, v, mask, slopes, block_q, block_k, causal, masked,
                         alibi, k_splits, softmax_scale)
    return out


def _scale(d: int, softmax_scale: Optional[float]) -> float:
    return d ** -0.5 if softmax_scale is None else softmax_scale


def _flash_core(q, k, v, mask, slopes, block_q, block_k, causal, masked, alibi,
                k_splits=1, softmax_scale=None):
    scale = _scale(q.shape[-1], softmax_scale) * _LOG2E  # base-2 softmax (see module header)
    qs = (q * jnp.asarray(scale, q.dtype)).transpose(0, 2, 1, 3)  # [B,H,S,D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out, lse = _flash_fwd(qs, kt, vt, mask, slopes, block_q, block_k, causal, masked,
                          alibi, k_splits)
    return out.transpose(0, 2, 1, 3), (qs, kt, vt, lse, out)


def _flash_vjp_fwd(q, k, v, mask, slopes, block_q, block_k, causal, masked, alibi,
                   k_splits=1, softmax_scale=None):
    out, (qs, kt, vt, lse, out_bhsd) = _flash_core(q, k, v, mask, slopes, block_q,
                                                   block_k, causal, masked, alibi,
                                                   k_splits, softmax_scale)
    return out, (qs, kt, vt, mask, slopes, lse, out_bhsd)


def _flash_vjp_bwd(block_q, block_k, causal, masked, alibi, k_splits, softmax_scale, res, g):
    qs, kt, vt, mask, slopes, lse, out_bhsd = res
    do = g.transpose(0, 2, 1, 3)
    dq, dk, dv = _flash_bwd(qs, kt, vt, mask, slopes, out_bhsd, lse, do,
                            block_q, block_k, causal, masked, alibi, k_splits, softmax_scale)
    return (*(x.transpose(0, 2, 1, 3) for x in (dq, dk, dv)), None, None)


_flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_banded_forward(q, k, v, window: Optional[int], block: int, softmax_scale: Optional[float] = None,
                         lengths=None, sink=None):
    """Causal attention under a band of ``window`` keys (``ops/attention.py::
    band_keep``), the FORWARD alone: q ``[B, S, H, D]``, k ``[B, S, Hkv, D]``, v
    ``[B, S, Hkv, Dv]`` (as wide as the key or not), ``S`` whole blocks of
    ``block``. The kernel is ``_fwd_kernel`` on a grid of
    the band's cells (``_band_maps``), named ``swa_flash_fwd``. With ``lengths``
    (int32 ``[B]``: each row's live tokens, which come first in it; ``_flash_fwd``)
    no cell past a row's last token runs and the pads' rows are zeros; with
    no ``window`` the grid is the causal triangle's, ``flash_fwd``. ``sink``
    ([H]): a logit a query head in the softmax's denominator alone."""
    scale = _scale(q.shape[-1], softmax_scale) * _LOG2E
    B, S, H, _ = q.shape
    sinks = None
    if sink is not None:  # in the base-2 scale of the pre-scaled scores, as the slopes ride
        sinks = jnp.broadcast_to((sink.astype(jnp.float32) * _LOG2E)[:, None, None], (H, 1, _LANES))
    out, _ = _flash_fwd((q * jnp.asarray(scale, q.dtype)).transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), jnp.ones((B, 1, S), jnp.int32),
                        jnp.zeros((H, 1, _LANES), jnp.float32), block, block, True, False, False, 1, window, lengths,
                        sinks)
    return out.transpose(0, 2, 1, 3)


def _banded_fwd(q, k, v, window, block, softmax_scale, lengths=None, sink=None):
    return (flash_banded_forward(q, k, v, window, block, softmax_scale, lengths, sink),
            (lengths is not None, sink is not None, v.shape[-1] != k.shape[-1]))


def _banded_bwd(window, block, softmax_scale, given, g):
    with_lengths, with_sink, narrower = given
    raise NotImplementedError(
        "flash attention " + " and ".join([f"under a band (window={window})"] * (window is not None)
                                          + ["over rows' live lengths (lengths=)"] * with_lengths
                                          + ["with a sink in the softmax's sum"] * with_sink
                                          + ["with a value narrower than its key"] * narrower)
        + " has a forward alone: no backward kernel skips the cells under the band or past a row's last token, adds "
        "a sink or takes two widths yet; train a sliding layer with attn_impl='xla', and a padded batch with a mask")


flash_banded_forward.defvjp(_banded_fwd, _banded_bwd)


@register("causal_attention", "pallas")
def flash_causal_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, D]
    mask: Optional[jax.Array] = None,  # [B, S] 1=keep
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    alibi_slopes: Optional[jax.Array] = None,  # [H] fp32 (bloom ALiBi)
    k_splits: int = 1,
    softmax_scale: Optional[float] = None,  # None: D^-0.5
    window: Optional[int] = None,  # a band under the causal mask: the forward alone (``flash_banded_forward``)
    lengths: Optional[jax.Array] = None,  # [B] int32: the rows' live tokens, which come first: the forward alone
    sink: Optional[jax.Array] = None,  # [H]: a logit a query head in the softmax's denominator: the forward alone
) -> jax.Array:
    B, S, H, D = q.shape
    block_q = min(block_q, max(S, 8))
    block_k = min(block_k, max(S, 8))
    if window is not None or lengths is not None or sink is not None or v.shape[-1] != D:
        if mask is not None or alibi_slopes is not None:
            raise NotImplementedError(
                "flash attention under a band, over rows' live lengths, with a sink or with a value narrower than "
                "its key takes no padding mask and no ALiBi slopes")
        block = min(block_q, block_k)
        pad = _cdiv(S, block) * block - S  # padded keys reach padded queries alone (module header)
        if pad:
            q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        return flash_banded_forward(q, k, v, window, block, softmax_scale, lengths, sink)[:, :S]
    # k_splits > 1 processes each block_k tile as k_splits sub-chunks with the
    # next sub-chunk's QK^T hoisted ahead of the previous one's softmax, so the
    # MXU matmul can overlap the VPU exp2/renormalize passes. Pure
    # instruction-level restructuring: identical math. Measured on a v5e it is
    # not known to pay anywhere: the forward and the two-pass backward lose
    # to any split (PERF.md, PR 32), and since the dkv kernel forms its scores
    # transposed (PR 44) the one-pass backward loses 5% to chunks of 256 too,
    # where the old form gained 4.5% and took them by itself. A fixed k_splits
    # must stay valid when short sequences clamp block_k, so degrade to the
    # largest compatible divisor (sub-chunks divide block_k; >=128 lanes on
    # hardware).
    while k_splits > 1 and (block_k % k_splits != 0
                            or (not _interpret() and (block_k // k_splits) % 128 != 0)):
        k_splits -= 1
    Sp = _cdiv(S, max(block_q, block_k)) * max(block_q, block_k)

    # masked=False avoids every padding-mask VPU pass in-kernel. Wrapper tail
    # padding is invisible under a causal mask (padded keys only reach padded
    # queries, which are sliced off and receive zero cotangents), so the
    # synthesized all-ones mask never needs to be applied.
    masked = mask is not None
    keep = jnp.ones((B, S), jnp.int32) if mask is None else mask.astype(jnp.int32)
    if Sp != S:
        pad = Sp - S
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        keep = jnp.pad(keep, ((0, 0), (0, pad)))

    alibi = alibi_slopes is not None
    if alibi:
        # The kernels run base-2 softmax: fold log2e into the slopes so the
        # in-kernel bias lands in the same scale as the pre-scaled scores.
        # Slopes are NON-DIFFERENTIABLE on this path (stop_gradient makes it
        # explicit): they are positional constants in ALiBi models; to train
        # learned per-head slopes, use causal_attention(..., impl='xla').
        slopes = jnp.broadcast_to(
            (jax.lax.stop_gradient(alibi_slopes).astype(jnp.float32)
             * _LOG2E)[:, None, None], (H, 1, _LANES))
    else:
        slopes = jnp.zeros((H, 1, _LANES), jnp.float32)

    out = _flash_attention(q, k, v, keep[:, None, :], slopes,
                           block_q, block_k, True, masked, alibi, k_splits, softmax_scale)
    return out[:, :S]


@register("causal_attention_lse", "pallas")
def flash_causal_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                               block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K):
    """The forward kernel alone, with what it already computes beside the
    output: the log-sum-exp a (query, head), here in natural units, so that a
    caller can merge this attention with attention over further keys
    (``ops/eva.py``). q ``[B, S, H, D]``, k, v ``[B, S, Hkv, D]`` -> (out
    ``[B, S, H, D]``, lse fp32 ``[B, S, H]``). No gradient: serving only."""
    B, S, H, D = q.shape
    block_q = min(block_q, max(S, 8))
    block_k = min(block_k, max(S, 8))
    Sp = _cdiv(S, max(block_q, block_k)) * max(block_q, block_k)
    if Sp != S:  # padded keys reach padded queries alone (module header)
        q, k, v = (jnp.pad(a, ((0, 0), (0, Sp - S), (0, 0), (0, 0))) for a in (q, k, v))
    out, (_, _, _, lse, _) = _flash_core(
        q, k, v, jnp.ones((B, 1, Sp), jnp.int32), jnp.zeros((H, 1, _LANES), jnp.float32),
        block_q, block_k, True, False, False)
    return out[:, :S], (lse[:, :, 0] * _LN2).transpose(0, 2, 1)[:, :S]
