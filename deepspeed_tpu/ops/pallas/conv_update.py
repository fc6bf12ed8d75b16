"""One token of a state mixer's causal convolution, in place on the conv pool
(Pallas TPU).

A decode step of a Mamba-2 or Gated DeltaNet layer convolves ONE new input a
row with the ``K - 1`` inputs before it, which the conv pool keeps
(``inference/cache.StatePool.conv``: ``[state layers, slots, (K - 1) X]``, a
slot's tail one lane-dense row). ``ops/ssm.py::conv_inputs`` was written for a
prompt: it concatenates the tail before the inputs, sums ``K`` shifted slices
and gathers the next tail at each row's last live token, around a slice of the
layer's row of the pool and a ``dynamic_update_slice`` back. At one token a row
each of those is a whole pass over the tail (six of them a layer-step as the
chip's compiler schedules them, 107 and 228 GB/s on the bytes the mathematics
needs: PERF.md, section 6, PR 49), and with ``K - 1 = 3`` between the rows and
the channels the compiler lays ``[rows, 3, X]`` out 3-on-the-sublanes and
re-lays it. This kernel makes one read and one write: a grid step holds a block
of rows, reads their tail as ``K - 1`` lane-aligned column slices of width
``X``, forms ``silu(sum_j tap_j input_j + bias)`` in float32, and writes the
tail back shifted by one input. The pool is aliased in and out and nothing else
of it is touched; a dead row's tail is written as it was read, to the bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.registry import register
from deepspeed_tpu.utils.compat import tpu_compiler_params

_LANES = 128
# rows a grid step (a whole (16, 128) tile of the bf16 pool, several steps a call for the pipeline) and lane tiles an
# inner step computes at once (a few vector registers an operand): at 8-32 rows and 1-8 tiles the kernel alone read
# 13.9-15.7 us at the granite cell's shape and 38.5-39.8 at the qwen cell's (tools/conv_update_bench.py --sweep, PR 49)
_ROWS = 16
_CHUNK_TILES = 4


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def takes(X: int, rows: int, at: int = 0) -> bool:
    """Whether the chip's compiler takes the kernel at these sizes: the
    channels whole 128-lane tiles that start on one (the tail's ``K - 1``
    column slices and the inputs' are then lane-aligned), the rows whole
    sublane tiles."""
    return X % _LANES == 0 and at % _LANES == 0 and rows % 8 == 0


def _kernel(layer_ref, pool_ref, x_ref, taps_ref, *refs, K, X, at, width, bias):
    bias_ref = refs[0] if bias else None
    flags_ref, o_ref, y_ref = refs[-3:]
    f32 = jnp.float32
    flags = jnp.broadcast_to(flags_ref[...], (flags_ref.shape[0], width))  # [rows, 1] -> down the lanes
    live, fresh = (flags & 1) > 0, (flags & 2) > 0

    def chunk(c, _):  # ``width`` lanes of every slice: each starts on a lane tile
        col = pl.multiple_of(c * width, width)
        cols = lambda start: pl.ds(pl.multiple_of(start + col, _LANES if X % _LANES == 0 else 1), width)  # noqa: E731
        taps = taps_ref[:, cols(0)].astype(f32)  # [K, width]
        x = x_ref[:, cols(at)]
        # a row that starts a sequence: whatever its slot holds is another's
        tail = [jnp.where(fresh, 0.0, pool_ref[0, :, cols(j * X)].astype(f32)) for j in range(K - 1)]
        seen = [t.astype(x.dtype).astype(f32) for t in tail] + [x.astype(f32)]
        acc = sum(seen[j] * taps[j:j + 1] for j in range(K))
        if bias:
            acc = acc + bias_ref[:, cols(0)].astype(f32)
        y_ref[:, cols(0)] = jax.nn.silu(acc).astype(y_ref.dtype)
        left = tail[1:] + [x.astype(o_ref.dtype).astype(f32)]
        for j in range(K - 1):  # a dead row's comes out as it went in (bf16 -> float32 -> bf16 is the identity)
            o_ref[0, :, cols(j * X)] = jnp.where(live, left[j], tail[j]).astype(o_ref.dtype)
        return 0

    # a loop, not ``X // width`` copies of its body: a program is traced and lowered in every process, compile cache
    # or not, and unrolled the nine kernels of a period cost a chain's set-up a third of a second each
    jax.lax.fori_loop(0, X // width, chunk, 0)


@register("conv_pool_step", "pallas")
def conv_pool_step(pool, layer, x, taps, bias=None, live=None, fresh=None, at: int = 0):
    """``ops/ssm.py::conv_pool_step``: one token of the convolution for the
    program's rows, on row ``layer`` of ``pool`` [layers, slots, (K - 1) X],
    slots 0..rows-1, in place. The inputs are columns ``at .. at + X`` of ``x``
    [rows, W], in the dtype they come in and WHERE they lie in a projection's
    output (a slice handed over would be a copy of its own: a kernel's operand
    is a whole array); ``taps`` [K, X] with tap ``K - 1`` on ``x``, ``bias``
    [X] or None, ``live``/``fresh`` [rows] bool (None: all live, none fresh).
    Returns ``(silu(conv + bias) [rows, X] in x's dtype, pool)``."""
    R, W = x.shape
    K, X = taps.shape
    if pool.shape[2] != (K - 1) * X or pool.shape[1] < R:
        raise ValueError(f"conv_update: a pool of {pool.shape[1]} slots of {pool.shape[2]} for {R} rows of "
                         f"{K - 1} x {X} inputs")
    flags = jnp.ones((R,), jnp.int32) if live is None else live.astype(jnp.int32)
    if fresh is not None:
        flags = flags + 2 * fresh.astype(jnp.int32)
    rb = next((b for b in (_ROWS, 8) if R % b == 0), R)
    # (off the lane tile, which only interpret mode runs, the channels go whole)
    width = X if X % _LANES else _LANES * next(d for d in range(_CHUNK_TILES, 0, -1) if X // _LANES % d == 0)
    tail = pl.BlockSpec((1, rb, (K - 1) * X), lambda r, layer: (layer[0], r, 0))
    rows = pl.BlockSpec((rb, X), lambda r, layer: (r, 0))
    # the inputs' columns as a block of their own where they start on a multiple of their width, else the whole
    # width of ``x`` and the kernel slices (lane-aligned either way)
    block, at = (at // X, 0) if at % X == 0 else (None, at)
    inputs = pl.BlockSpec((rb, W if block is None else X), lambda r, layer: (r, block or 0))
    whole = lambda n: pl.BlockSpec((n, X), lambda r, layer: (0, 0))  # noqa: E731
    operands = [pool, x, taps] + ([bias.reshape(1, X)] if bias is not None else []) + [flags.reshape(R, 1)]
    pool, y = pl.pallas_call(
        functools.partial(_kernel, K=K, X=X, at=at, width=width, bias=bias is not None),
        name="conv_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the layer's row of the pool
            grid=(R // rb,),
            in_specs=[tail, inputs, whole(K)] + ([whole(1)] if bias is not None else [])
            + [pl.BlockSpec((rb, 1), lambda r, layer: (r, 0))],
            out_specs=[tail, rows],
        ),
        # the pool is PINNED in HBM (and with it the operand it aliases): the cells' 57-60 MB fit the chip's fast
        # memory, and left to the compiler the whole pool was copied there and back around every period of the scan
        out_shape=[pltpu.HBM(pool.shape, pool.dtype), jax.ShapeDtypeStruct((R, X), x.dtype)],
        input_output_aliases={1: 0},  # the pool, in place (operand 0 is the prefetched scalar)
        compiler_params=tpu_compiler_params(dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    return y, pool
