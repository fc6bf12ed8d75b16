"""Block-sparse attention Pallas kernel: dead (qblk, kblk) tiles are SKIPPED.

Reference analog: ``deepspeed/ops/sparse_attention/matmul.py:196`` — the
Triton sdd/dsd block-skipping matmuls that make BigBird/Longformer layouts a
real compute/memory win rather than a mask.

Design: the (static numpy) block layout compiles into per-(head, qblock)
active-column lists. The grid's last axis runs only to ``max_active`` columns
(not n_blocks), the column index rides scalar prefetch into the K/V BlockSpec
index maps, and rows with fewer active columns guard the tail — so both the
DMA and the MXU work scale with ``layout.sum()`` instead of ``n^2``. Online
softmax accumulates across a row's active tiles exactly as in the dense flash
kernel.

Backward (reference ``matmul.py:196`` / ``softmax.py:123`` — the Triton
sdd/dsd kernels have backward passes, so BigBird/Longformer layouts TRAIN
sparse): two tile-skipping kernels sharing the forward's layout-list
contract. ``dq`` re-walks each query row's active columns (same ``cols``/
``ncols`` lists, p recomputed from the forward's saved logsumexp); ``dk/dv``
walk the TRANSPOSED lists (per key column, its active query rows) so each
key tile's gradients accumulate over exactly the live tiles that touched it.
Scores are never materialized beyond one [block, block] VMEM tile — the
backward's HBM residency is O(S*D + S) (dq/dk/dv + lse/delta), not O(S^2).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.compat import tpu_compiler_params

_NEG_INF = float(jnp.finfo(jnp.float32).min)
_LANES = 8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def layout_to_lists(layout: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """[H, n, n] 0/1 -> (cols [H, n, A], ncols [H, n]); padded entries repeat
    the row's last active column (their compute is guarded off, and a valid
    index keeps the prefetched DMA in range)."""
    H, n, _ = layout.shape
    ncols = layout.sum(-1).astype(np.int32)
    A = max(1, int(ncols.max()))
    cols = np.zeros((H, n, A), np.int32)
    for h in range(H):
        for i in range(n):
            act = np.nonzero(layout[h, i])[0]
            if act.size:
                cols[h, i, :act.size] = act
                cols[h, i, act.size:] = act[-1]
    return cols, ncols


def layout_to_lists_t(layout: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Transposed lists for the dk/dv walk: [H, n, n] 0/1 ->
    (rows [H, n, Ar], nrows [H, n]) — for key column ki, the active query
    rows. Padding repeats the column's last active row (guarded off)."""
    return layout_to_lists(np.swapaxes(layout, -1, -2))


def _score_tile(q_ref, k_ref, row_blk, col_blk, block, causal):
    """One [block, block] fp32 score tile with the shared causal diagonal
    mask — the single masking definition all three kernels (fwd/dq/dkv) use,
    so forward and backward provably mask identically."""
    s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        # only the diagonal tile needs the iota mask
        rows = row_blk * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = col_blk * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((col_blk != row_blk) | (cols <= rows), s, _NEG_INF)
    return s


def _sparse_fwd_kernel(cols_ref, ncols_ref, q_ref, k_ref, v_ref, o_ref,
                       lse_ref, acc_ref, m_ref, l_ref, *, block, causal):
    h = pl.program_id(1)
    qi = pl.program_id(2)
    j = pl.program_id(3)
    A = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    kj = cols_ref[h, qi, j]
    live = j < ncols_ref[h, qi]
    if causal:
        live = live & (kj <= qi)

    def _compute():
        # q pre-scaled by 1/sqrt(D)
        s = _score_tile(q_ref, k_ref, qi, kj, block, causal)

        m_prev = jnp.max(m_ref[:], axis=-1, keepdims=True)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_cur == _NEG_INF, 0.0, m_cur)
        p = jnp.exp(s - m_safe)
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_prev = jnp.max(l_ref[:], axis=-1, keepdims=True)
        l_ref[:] = jnp.broadcast_to(alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        m_ref[:] = jnp.broadcast_to(m_cur, m_ref.shape)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    pl.when(live)(_compute)

    @pl.when(j == A - 1)
    def _finalize():
        l = jnp.max(l_ref[:], axis=-1, keepdims=True)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        m = jnp.max(m_ref[:], axis=-1, keepdims=True)
        # base-e logsumexp per row; rows with no live tile get -inf (their
        # output is 0 and the backward walks no tiles for them)
        lse = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(l_safe))
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _sparse_fwd(q, k, v, cols, ncols, block, causal):
    """q/k/v: [B, H, S, D] (q pre-scaled). Returns (out [B,H,S,D], lse)."""
    B, H, S, D = q.shape
    n = S // block
    A = cols.shape[-1]

    out, lse = pl.pallas_call(
        functools.partial(_sparse_fwd_kernel, block=block, causal=causal),
        name="sparse_attn_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # cols, ncols
            grid=(B, H, n, A),
            in_specs=[
                pl.BlockSpec((1, 1, block, D), lambda b, h, qi, j, cols, ncols: (b, h, qi, 0)),
                pl.BlockSpec((1, 1, block, D), lambda b, h, qi, j, cols, ncols: (b, h, cols[h, qi, j], 0)),
                pl.BlockSpec((1, 1, block, D), lambda b, h, qi, j, cols, ncols: (b, h, cols[h, qi, j], 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block, D), lambda b, h, qi, j, cols, ncols: (b, h, qi, 0)),
                pl.BlockSpec((1, 1, block, _LANES), lambda b, h, qi, j, cols, ncols: (b, h, qi, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block, D), jnp.float32),
                pltpu.VMEM((block, _LANES), jnp.float32),
                pltpu.VMEM((block, _LANES), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, _LANES), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=_interpret(),
    )(cols, ncols, q, k, v)
    return out, lse


def _sparse_dq_kernel(cols_ref, ncols_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, dq_ref, acc_ref, *, block, causal):
    h = pl.program_id(1)
    qi = pl.program_id(2)
    j = pl.program_id(3)
    A = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kj = cols_ref[h, qi, j]
    live = j < ncols_ref[h, qi]
    if causal:
        live = live & (kj <= qi)

    def _compute():
        s = _score_tile(q_ref, k_ref, qi, kj, block, causal)  # q pre-scaled
        k = k_ref[0, 0]
        lse = jnp.max(lse_ref[0, 0], axis=-1, keepdims=True)
        p = jnp.exp(s - jnp.where(lse == _NEG_INF, 0.0, lse))
        dp = jax.lax.dot_general(do_ref[0, 0], v_ref[0, 0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.max(delta_ref[0, 0], axis=-1, keepdims=True))
        acc_ref[:] += jax.lax.dot_general(ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)

    pl.when(live)(_compute)

    @pl.when(j == A - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _sparse_dkv_kernel(rows_ref, nrows_ref, q_ref, k_ref, v_ref, do_ref,
                       lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                       *, block, causal):
    h = pl.program_id(1)
    ki = pl.program_id(2)
    t = pl.program_id(3)
    Ar = pl.num_programs(3)

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    qt = rows_ref[h, ki, t]
    live = t < nrows_ref[h, ki]
    if causal:
        live = live & (qt >= ki)

    def _compute():
        # q block at row qt (pre-scaled), k/v blocks at column ki
        s = _score_tile(q_ref, k_ref, qt, ki, block, causal)
        q = q_ref[0, 0]
        lse = jnp.max(lse_ref[0, 0], axis=-1, keepdims=True)
        p = jnp.exp(s - jnp.where(lse == _NEG_INF, 0.0, lse))
        do = do_ref[0, 0]
        dv_acc[:] += jax.lax.dot_general(p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0, 0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.max(delta_ref[0, 0], axis=-1, keepdims=True))
        dk_acc[:] += jax.lax.dot_general(ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    pl.when(live)(_compute)

    @pl.when(t == Ar - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _sparse_bwd(q, k, v, do, out, lse, cols, ncols, rows, nrows, block, causal):
    """All arrays [B, H, S, D] (q pre-scaled). Returns (dq, dk, dv) fp32.

    dq walks each row's active columns (cols/ncols); dk/dv walk each column's
    active rows (rows/nrows) — both grids end at the layout population, so
    the backward skips exactly the tiles the forward skipped."""
    B, H, S, D = q.shape
    n = S // block
    A = cols.shape[-1]
    Ar = rows.shape[-1]

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))

    qrow = lambda b, h, qi, j, cols, ncols: (b, h, qi, 0)  # noqa: E731
    kcol = lambda b, h, qi, j, cols, ncols: (b, h, cols[h, qi, j], 0)  # noqa: E731
    dq = pl.pallas_call(
        functools.partial(_sparse_dq_kernel, block=block, causal=causal),
        name="sparse_attn_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # cols, ncols
            grid=(B, H, n, A),
            in_specs=[
                pl.BlockSpec((1, 1, block, D), qrow),
                pl.BlockSpec((1, 1, block, D), kcol),
                pl.BlockSpec((1, 1, block, D), kcol),
                pl.BlockSpec((1, 1, block, D), qrow),
                pl.BlockSpec((1, 1, block, _LANES), qrow),
                pl.BlockSpec((1, 1, block, _LANES), qrow),
            ],
            out_specs=pl.BlockSpec((1, 1, block, D), qrow),
            scratch_shapes=[pltpu.VMEM((block, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=_interpret(),
    )(cols, ncols, q, k, v, do, lse, delta)

    # transposed walk: the "row" block index comes from the rows list
    qrow_t = lambda b, h, ki, t, rows, nrows: (b, h, rows[h, ki, t], 0)  # noqa: E731
    kcol_t = lambda b, h, ki, t, rows, nrows: (b, h, ki, 0)  # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_sparse_dkv_kernel, block=block, causal=causal),
        name="sparse_attn_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # rows, nrows
            grid=(B, H, n, Ar),
            in_specs=[
                pl.BlockSpec((1, 1, block, D), qrow_t),
                pl.BlockSpec((1, 1, block, D), kcol_t),
                pl.BlockSpec((1, 1, block, D), kcol_t),
                pl.BlockSpec((1, 1, block, D), qrow_t),
                pl.BlockSpec((1, 1, block, _LANES), qrow_t),
                pl.BlockSpec((1, 1, block, _LANES), qrow_t),
            ],
            out_specs=[pl.BlockSpec((1, 1, block, D), kcol_t)] * 2,
            scratch_shapes=[pltpu.VMEM((block, D), jnp.float32),
                            pltpu.VMEM((block, D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, S, D), jnp.float32)] * 2,
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=_interpret(),
    )(rows, nrows, q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _sparse_attention(q, k, v, layout_key, block, causal):
    return _sparse_fwd_wrap(q, k, v, layout_key, block, causal)


# LRU-bounded layout cache: entries pin host + device arrays, and callers may
# regenerate layouts (random BigBird blocks, varying seq lens). The key is
# SELF-DESCRIBING (shape, dtype, raw bytes), so eviction is always safe: a
# pending custom-VJP backward that looks up an evicted key just rebuilds the
# arrays from the key itself.
_LAYOUTS: "dict" = {}  # insertion-ordered; oldest evicted past the cap
_LAYOUT_CAP = 32


def _register_layout(layout: np.ndarray):
    key = (layout.shape, layout.dtype.str, layout.tobytes())
    _layout_arrays(key)
    return key


def _layout_arrays(key):
    """(layout, cols, ncols, rows, nrows) for a registry key, rebuilding
    after eviction (cols/ncols drive fwd + dq; rows/nrows drive dk/dv)."""
    if key in _LAYOUTS:
        _LAYOUTS[key] = _LAYOUTS.pop(key)  # refresh LRU position
    else:
        shape, dtype, raw = key
        layout = np.frombuffer(raw, dtype=np.dtype(dtype)).reshape(shape)
        cols, ncols = layout_to_lists(layout)
        rows, nrows = layout_to_lists_t(layout)
        _LAYOUTS[key] = (layout, jnp.asarray(cols), jnp.asarray(ncols),
                         jnp.asarray(rows), jnp.asarray(nrows))
        while len(_LAYOUTS) > _LAYOUT_CAP:
            _LAYOUTS.pop(next(iter(_LAYOUTS)))
    return _LAYOUTS[key]


def _sparse_core(q, k, v, layout_key, block, causal):
    _, cols, ncols, _, _ = _layout_arrays(layout_key)
    scale = q.shape[-1] ** -0.5
    qt = (q * jnp.asarray(scale, q.dtype)).transpose(0, 2, 1, 3)  # [B,H,S,D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out, lse = _sparse_fwd(qt, kt, vt, cols, ncols, block, causal)
    return out.transpose(0, 2, 1, 3), (qt, kt, vt, lse, out)


def _sparse_fwd_wrap(q, k, v, layout_key, block, causal):
    return _sparse_core(q, k, v, layout_key, block, causal)[0]


# the VJP forward's (primal, residuals) contract is exactly _sparse_core's
_sparse_vjp_fwd = _sparse_core


def _sparse_vjp_bwd(layout_key, block, causal, res, g):
    qt, kt, vt, lse, out_bhsd = res
    _, cols, ncols, rows, nrows = _layout_arrays(layout_key)
    do = g.transpose(0, 2, 1, 3)
    dq, dk, dv = _sparse_bwd(qt, kt, vt, do, out_bhsd, lse,
                             cols, ncols, rows, nrows, block, causal)
    # dq was accumulated against unscaled k but for the PRE-SCALED q input:
    # apply the 1/sqrt(D) factor here in fp32. dk used the pre-scaled q, so
    # it already carries the factor.
    scale = qt.shape[-1] ** -0.5
    dq = (dq * scale).transpose(0, 2, 1, 3).astype(qt.dtype)
    dk = dk.transpose(0, 2, 1, 3).astype(kt.dtype)
    dv = dv.transpose(0, 2, 1, 3).astype(vt.dtype)
    return dq, dk, dv


_sparse_attention.defvjp(_sparse_vjp_fwd, _sparse_vjp_bwd)


def block_sparse_attention_pallas(q, k, v, layout: np.ndarray, block: int, causal: bool = True):
    """Public entry: tile-skipping kernel forward + exact backward."""
    key = _register_layout(np.asarray(layout))
    return _sparse_attention(q, k, v, key, block, causal)
