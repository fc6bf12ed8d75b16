"""The hyper-connections of a served prompt as two kernels on the streams (Pallas TPU).

``ops/mhc.py`` is the mathematics, and in ``jax.numpy`` over ``[n, N, chunk, C]``
the chip's compiler makes of a sublayer's three functions float32 copies of
streams in HBM, a pass a term of the write-back and some forty small fusions
for the Sinkhorn rounds: 5.3 ms a sublayer of an ``(8, 2048)`` prefill at ``n``
4, ``C`` 3,584, where the bytes the mathematics needs (the ``n C`` streams read
twice and written once, the sublayer's output read, its input written: 100,352
B a token in bf16) take 2.0 at the v5e's 819 GB/s (PERF.md, section 6, PR 54).
Here the streams are ``[n, tokens, C]`` (the carry reshaped, no copy), a tile of
128 tokens a grid step, and a sublayer is two calls:

- :func:`mhc_mix_read` reads a tile's streams ONCE: the statistic and ``m =
  (X phi) rstd`` in one loop over ``C`` (the product on the MXU, operands as
  held, float32 sums; the squares beside it), sigmoids, clamp,
  ``exp`` and every Sinkhorn round in fast memory, then ``u = sum_i H_pre[i]
  X_i`` from the tile it still holds. It writes ``u`` and the mix, ``n^2 + 2n``
  float32 numbers a token, and nothing else;
- :func:`mhc_write` reads the tile's streams, the sublayer's output and the
  mix, and writes ``X'_i = sum_j H_res[i, j] X_j + H_post[i] y`` over the
  streams it read (``input_output_aliases``): the streams ride a layer scan's
  carry and are not copied.

Layout. A coefficient is one number a token and multiplies a ``[tokens, C]``
tile along ``C``: tokens on the sublanes, the number the same in every lane.
The mix crosses HBM as ``[n^2 + 2n, tokens]`` float32, an entry a lane-dense
ROW (96 B a token; ``[tokens, n^2 + 2n]`` would pad to 512), which is also how
the Sinkhorn rounds want it: a row or column sum is an add of rows. A kernel
turns the row it needs into a ``[128 tokens, 128]`` tile by a transpose of its
broadcast DOWN the sublanes (``ssm_update.py``'s device) once a tile of tokens,
and every lane tile of ``C`` reuses it.

The float32 mix, the 20 rounds with ``eps`` in both sums and the roundings to
the streams' dtype (``u``, each ``X'_i``) are ``ops/mhc.py``'s; the order of
the write-back's sum is too. Neither kernel raises Mosaic's scoped limit: the
tiles are picked from the shapes under the default 16 MiB (a kernel past it
takes fast memory from what the compiler keeps there for the whole program:
PERF.md, section 6, PR 52).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.registry import register
from deepspeed_tpu.utils.compat import tpu_compiler_params

_LANES = 128
_TOKENS = 128  # a tile's tokens: the mix's rows become tiles by 128 x 128 transposes
_ROWS = 16     # the tokens of a tile's inner step: one packed bf16 sublane tile
# what a call's blocks may hold of the default 16 MiB scope, each double-buffered by the pipeline
_VMEM_BUDGET = 13 << 20
# the streams of a call the kernels take: from 112 MiB on. Smaller streams stay in the chip's fast memory between XLA's
# passes over them, and its fusions are then the faster: alone at the xing cell's width, ms a sublayer here against
# XLA's, 0.0185 / 0.0098 at a decode step's 64 tokens, 0.228 / 0.203 at 2,048 (56 MiB), 0.472 / 0.695 at 4,096
# (112 MiB), 2.54 / 6.22 at 16,384 (tools/mhc_kernel_bench.py; PERF.md, section 6, PR 54)
_MIN_STREAM_BYTES = 112 << 20
F32 = jnp.float32


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _mix_read_bytes(n: int, C: int, itemsize: int) -> int:
    """VMEM of :func:`mhc_mix_read`'s blocks: the tile's streams and ``u`` twice, ``phi`` (padded to a lane tile) once."""
    return 2 * _TOKENS * (n + 1) * C * itemsize + n * C * _LANES * itemsize


def _write_columns(n: int, C: int, itemsize: int) -> int:
    """The columns of ``C`` a grid step of :func:`mhc_write` takes: the most
    lane tiles that divide ``C`` whose streams in and out and ``y``, each
    twice, fit the budget beside the coefficients' tiles (0: none do)."""
    tiles = C // _LANES
    fits = lambda d: 2 * _TOKENS * (2 * n + 1) * d * _LANES * itemsize + (n * n + n) * _TOKENS * _LANES * 4  # noqa: E731
    return _LANES * next((d for d in range(tiles, 0, -1) if tiles % d == 0 and fits(d) <= _VMEM_BUDGET), 0)


def takes(n: int, tokens: int, C: int, dtype) -> bool:
    """Whether a call of these sizes goes through the two kernels: the chip's
    compiler takes them (``C`` whole lane tiles, the tokens whole packed sublane
    tiles, the mix's ``n^2 + 2n`` entries within one lane tile, and a 128-token
    tile of the streams within the default Mosaic scope: bf16 at ``n C`` up to
    16 K, float32 at half that), and the streams are past what XLA's passes
    over them keep in fast memory (``_MIN_STREAM_BYTES``: a prompt's, not a
    decode step's)."""
    itemsize = jnp.dtype(dtype).itemsize
    return (C % _LANES == 0 and tokens % (32 // itemsize) == 0 and n * n + 2 * n <= _LANES
            and n * tokens * C * itemsize >= _MIN_STREAM_BYTES
            and jnp.issubdtype(dtype, jnp.floating) and itemsize in (2, 4)
            and _mix_read_bytes(n, C, itemsize) <= _VMEM_BUDGET and _write_columns(n, C, itemsize) > 0)


def _down(row):
    """A mix's row ``[1, 128 tokens]`` as a tile ``[128 tokens, 128]``: a token's number in every lane."""
    return jnp.broadcast_to(row, (_LANES, _TOKENS)).T


def _widen(tile, width: int):
    """``[rows, 128]``, the same in every lane, as ``[rows, width]``."""
    return tile if width == _LANES else jnp.concatenate([tile] * (width // _LANES), axis=1)


# ---- the mix and its read ------------------------------------------------------


def _mix_read_kernel(x_ref, phi_ref, sb_ref, u_ref, mix_ref, pre_ref, *, n, iters, eps, norm_eps, clamp):
    C = u_ref.shape[1]
    tiles = next(d for d in (4, 2, 1) if C // _LANES % d == 0)  # the lane tiles of C a step of the first pass takes
    width = tiles * _LANES

    # one pass over the tile for the statistic and for m = X phi: a step's columns of every stream go to the MXU
    # (against those rows of phi, its n^2 + 2n columns padded to a lane tile) and, squared, into a lane tile of sums
    def columns(c, carry):
        m, ss = carry
        cols = pl.ds(pl.multiple_of(c * width, width), width)
        for i in range(n):
            x = x_ref[i, :, cols]
            m = m + jnp.dot(x.astype(phi_ref.dtype), phi_ref[i, cols, :], preferred_element_type=F32)
            xf = x.astype(F32)
            sq = xf * xf
            for t in range(tiles):
                ss = ss + sq[:, t * _LANES:(t + 1) * _LANES]
        return m, ss

    zeros = jnp.zeros((_TOKENS, _LANES), F32)
    m, ss = jax.lax.fori_loop(0, C // width, columns, (zeros, zeros))
    rstd = jax.lax.rsqrt(jnp.sum(ss, axis=-1, keepdims=True) / (n * C) + norm_eps)  # [tokens, 1]
    z = sb_ref[0:1, :] * (m * rstd) + sb_ref[1:2, :]  # a_pre | a_post | a_res and b along the lanes
    lane = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    gate = jax.nn.sigmoid(z)
    mixed = jnp.where(lane < n, gate, jnp.where(lane < 2 * n, 2.0 * gate, jnp.exp(jnp.clip(z, *clamp))))
    mixed = mixed.T  # [128, tokens]: an entry a row, the tokens on the lanes
    mix_ref[0:2 * n, :] = mixed[0:2 * n]

    # Sinkhorn-Knopp on rows: M[i, j] is row 2n + i n + j; a round normalises the n rows, then the n columns
    groups = [[i * n + j for j in range(n)] for i in range(n)] + [[i * n + j for i in range(n)] for j in range(n)]

    def sinkhorn_round(_, M):
        M = list(M)
        for group in groups:
            r = 1.0 / (functools.reduce(jnp.add, [M[k] for k in group]) + eps)
            for k in group:
                M[k] = M[k] * r
        return tuple(M)

    M = jax.lax.fori_loop(0, iters, sinkhorn_round, tuple(mixed[2 * n + k:2 * n + k + 1] for k in range(n * n)))
    for k in range(n * n):
        mix_ref[2 * n + k:2 * n + k + 1, :] = M[k]

    # u = sum_i H_pre[i] X_i, from the tile as it lies in fast memory
    for i in range(n):
        pre_ref[i] = _down(mixed[i:i + 1])

    def read(r, _):
        rows = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)
        u = _widen(pre_ref[0, rows, :], C) * x_ref[0, rows, :].astype(F32)
        for i in range(1, n):  # (a term added as it is made: four of them held whole are 224 vector registers)
            u = u + _widen(pre_ref[i, rows, :], C) * x_ref[i, rows, :].astype(F32)
        u_ref[rows, :] = u.astype(u_ref.dtype)
        return 0

    jax.lax.fori_loop(0, _TOKENS // _ROWS, read, 0)


@register("mhc_mix_read", "pallas")
@jax.named_scope("mhc")
@jax.named_scope("mhc_mix")  # (the read rides with the mix)
def mhc_mix_read(streams, phi, b, alpha, *, norm_eps: float, iters: int, eps: float,
                 clamp: Tuple[float, float]):
    """``ops/mhc.py``'s ``mix`` and ``read`` of ``streams`` ``[n, ..., C]`` in one
    pass over them: ``(mixed, u)``, ``u`` ``[..., C]`` in the streams' dtype and
    ``mixed`` the coefficients as :func:`mhc_write` takes them (``[n^2 + 2n,
    tokens up to a tile]`` float32: ``H_pre``, ``H_post``, then ``H_res`` row by
    row)."""
    n, C = streams.shape[0], streams.shape[-1]
    K = n * n + 2 * n
    x = streams.reshape(n, -1, C)
    T = x.shape[1]
    tiles = pl.cdiv(T, _TOKENS)
    wide = jnp.promote_types(streams.dtype, phi.dtype)
    phi = jnp.pad(phi.astype(wide).reshape(n, C, K), ((0, 0), (0, 0), (0, _LANES - K)))
    a_pre, a_post, a_res = alpha.astype(F32)
    scale = jnp.concatenate([jnp.full((n,), a_pre), jnp.full((n,), a_post), jnp.full((n * n,), a_res)])
    sb = jnp.pad(jnp.stack([scale, b.astype(F32)]), ((0, 6), (0, _LANES - K)))  # [8, 128]
    u, mixed = pl.pallas_call(
        functools.partial(_mix_read_kernel, n=n, iters=iters, eps=eps, norm_eps=norm_eps, clamp=clamp),
        name="mhc_mix_read",
        grid=(tiles,),
        in_specs=[pl.BlockSpec((n, _TOKENS, C), lambda t: (0, t, 0)),
                  # fetched once and held once: a second buffer of it is 3.7 MB at C 3,584
                  pl.BlockSpec((n, C, _LANES), lambda t: (0, 0, 0), pipeline_mode=pl.Buffered(1)),
                  pl.BlockSpec((8, _LANES), lambda t: (0, 0))],
        out_specs=[pl.BlockSpec((_TOKENS, C), lambda t: (t, 0)),
                   pl.BlockSpec((K, _TOKENS), lambda t: (0, t))],
        out_shape=[jax.ShapeDtypeStruct((T, C), streams.dtype),
                   jax.ShapeDtypeStruct((K, tiles * _TOKENS), F32)],
        scratch_shapes=[pltpu.VMEM((n, _TOKENS, _LANES), F32)],
        compiler_params=tpu_compiler_params(dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(x, phi, sb)
    return mixed, u.reshape(streams.shape[1:])


# ---- the write-back ------------------------------------------------------------


def _write_kernel(mix_ref, x_ref, y_ref, o_ref, coef_ref, *, n):
    width = y_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():  # once a tile of tokens: H_post and H_res, a tile an entry
        for k in range(n + n * n):
            coef_ref[k] = _down(mix_ref[n + k:n + k + 1, :])

    def write(r, _):
        rows = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)
        xf = [x_ref[j, rows, :].astype(F32) for j in range(n)]
        yf = y_ref[rows, :].astype(F32)
        for i in range(n):  # (the sum in ``ops/mhc.py::write``'s order: the streams by j, then y)
            acc = _widen(coef_ref[n + i * n, rows, :], width) * xf[0]
            for j in range(1, n):
                acc = acc + _widen(coef_ref[n + i * n + j, rows, :], width) * xf[j]
            acc = acc + _widen(coef_ref[i, rows, :], width) * yf
            o_ref[i, rows, :] = acc.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, _TOKENS // _ROWS, write, 0)


@register("mhc_write", "pallas")
@jax.named_scope("mhc")
@jax.named_scope("mhc_post")
def mhc_write(streams, y, mixed):
    """``ops/mhc.py``'s ``write`` with :func:`mhc_mix_read`'s ``mixed``:
    ``X'_i = sum_j H_res[i, j] X_j + H_post[i] y`` ``[n, ..., C]``, written
    over ``streams`` (the operand is aliased to the result: donate it, or it is
    copied first)."""
    n, C = streams.shape[0], streams.shape[-1]
    x = streams.reshape(n, -1, C)
    T = x.shape[1]
    cols = _write_columns(n, C, x.dtype.itemsize) or C
    block = pl.BlockSpec((n, _TOKENS, cols), lambda t, c: (0, t, c))
    out = pl.pallas_call(
        functools.partial(_write_kernel, n=n),
        name="mhc_write",
        grid=(pl.cdiv(T, _TOKENS), C // cols),
        in_specs=[pl.BlockSpec((mixed.shape[0], _TOKENS), lambda t, c: (0, t)), block,
                  pl.BlockSpec((_TOKENS, cols), lambda t, c: (t, c))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((n + n * n, _TOKENS, _LANES), F32)],
        input_output_aliases={1: 0},  # the streams, in place
        compiler_params=tpu_compiler_params(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(mixed, x, y.reshape(-1, C))
    return out.reshape(streams.shape)
