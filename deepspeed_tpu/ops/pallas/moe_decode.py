"""A routed layer's decode product over the experts its rows picked (Pallas TPU).

At a decode step's few rows (``T < 2E``) a routed feed-forward layer is bound
by the bytes of its experts' weights, and only the experts some row picked
have to be read. XLA's form (``inference/model.py::_all_experts``) is one
product over ALL ``E`` stacked experts, the unpicked ones at weight 0. This
kernel visits the picked ones alone: the stacked weights ``[layers, E, M, H]``
stay WHOLE where they lie (a custom call on a layer's slice would have the
slice copied for it, 0.4-1.2 GB a layer), and the layer's index, the list
``ids`` of touched experts packed to the front and their count ``n`` come by
scalar prefetch, so a weight block's ``index_map`` reads ``(layer, ids[i], ..)``.

Grid ``(E slots, H / th)``. A slot ``i < n`` streams expert ``ids[i]``'s three
matrices a ``th``-wide tile of the hidden width at a time through the
pipeline's double buffers, each read once: ``h = act(x Wg) * (x Wu)`` on ``[T,
th]`` (``act(x Wu)`` without a gate matrix), times the expert's gate column,
times ``Wd[th, M]``, added to a float32 ``[T, M]`` accumulator that is written
once. A slot ``i >= n`` names the block already resident, so the pipeline
fetches nothing for it, and ``pl.when`` skips its arithmetic. Every row goes
through every touched expert, at weight 0 where it did not pick it, as in the
dense form: the products are bf16 with float32 sums, and the weighted sum over
experts stays in float32 (the dense form rounds each expert's output first).
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
# a grid step's three weight tiles, each double-buffered by the pipeline
_VMEM_BUDGET = 24 << 20
# what the kernel may scope: the tiles, the rows, the accumulator, a step's float32 products
_VMEM_LIMIT = 40 << 20


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def takes(T: int, M: int, H: int, dtype, weight_dtype) -> bool:
    """Whether the chip's compiler takes the kernel at these sizes: rows by
    the eight (``row_bucket``'s least), whole lane tiles of both widths, and
    weights that are multiplied as they are stored."""
    return (dtype == weight_dtype and T % 8 == 0 and M % _LANES == 0 and H % _LANES == 0
            and 6 * M * _LANES * jnp.dtype(dtype).itemsize <= _VMEM_BUDGET)


def _tile(M: int, H: int, itemsize: int) -> int:
    """The widest tile of the hidden width whose three blocks, double-buffered, fit the budget."""
    return next(th for th in range(H, 0, -_LANES) if H % th == 0 and 6 * M * th * itemsize <= _VMEM_BUDGET)


def touched_experts(gate):
    """``(ids [E] int32, n [1] int32)`` of ``gate`` [T, E]: the experts some
    row weighs above or below 0, in their order, packed to the front, the rest
    of the list naming the last of them (0 where there is none)."""
    E = gate.shape[1]
    flags = (gate != 0).any(axis=0)
    n = flags.sum(dtype=jnp.int32)
    ids = jnp.nonzero(flags, size=E, fill_value=0)[0].astype(jnp.int32)
    ids = jnp.where(jnp.arange(E) < n, ids, ids[jnp.maximum(n - 1, 0)])
    return ids, n.reshape(1)


def _kernel(layer_ref, ids_ref, n_ref, x_ref, gate_ref, *refs, act, glu):
    w_refs, (o_ref, acc_ref) = refs[:-2], refs[-2:]
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < n_ref[0])
    def _():
        x = x_ref[...]
        f32 = jnp.float32
        h = jnp.dot(x, w_refs[-2][0, 0], preferred_element_type=f32)  # [T, th]
        h = act(jnp.dot(x, w_refs[0][0, 0], preferred_element_type=f32)) * h if glu else act(h)
        # the expert's column of the gate: a sum over lanes of the one it names
        gate = gate_ref[...]
        column = jax.lax.broadcasted_iota(jnp.int32, gate.shape, 1) == ids_ref[i]
        h = h * jnp.sum(jnp.where(column, gate, 0.0), axis=1, keepdims=True)
        acc_ref[...] += jnp.dot(h.astype(x.dtype), w_refs[-1][0, 0], preferred_element_type=f32)

    @pl.when((i == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@register("moe_decode", "pallas")
def moe_decode(tokens, gate, w_gate, w_up, w_down, layer, activation: str, th=None):
    """``sum_e gate[:, e] * (act(tokens w_gate[layer, e]) * (tokens
    w_up[layer, e])) w_down[layer, e]`` over the experts ``e`` with a non-zero
    column of ``gate`` [T, E] float32, in ``tokens``' dtype [T, M]. ``w_up``
    (and ``w_gate``, or None: ``act(tokens w_up)``) ``[layers, E, M, H]``,
    ``w_down`` ``[layers, E, H, M]``, whole; ``layer`` an int32 scalar."""
    from deepspeed_tpu.models.transformer import act_fn

    T, M = tokens.shape
    E, H = w_up.shape[1], w_up.shape[3]
    glu = w_gate is not None
    th = th or _tile(M, H, tokens.dtype.itemsize)
    nj = H // th
    ids, n = touched_experts(gate)

    # a slot past the touched experts stays on the last block fetched: no DMA
    def tile(i, j, n):
        return jnp.where(i < n[0], j, nj - 1)

    rows = lambda shape: pl.BlockSpec(shape, lambda i, j, layer, ids, n: (0, 0))  # noqa: E731  resident throughout
    up = pl.BlockSpec((1, 1, M, th), lambda i, j, layer, ids, n: (layer[0], ids[i], 0, tile(i, j, n)))
    down = pl.BlockSpec((1, 1, th, M), lambda i, j, layer, ids, n: (layer[0], ids[i], tile(i, j, n), 0))
    return pl.pallas_call(
        functools.partial(_kernel, act=jax.nn.silu if glu else act_fn(activation), glu=glu),
        name="moe_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # the layer's row of the stacks, the touched experts, their count
            grid=(E, nj),
            in_specs=[rows((T, M)), rows((T, E))] + [up] * (1 + glu) + [down],
            out_specs=rows((T, M)),
            scratch_shapes=[pltpu.VMEM((T, M), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, M), tokens.dtype),
        compiler_params=tpu_compiler_params(dimension_semantics=("arbitrary", "arbitrary"),
                                            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), ids, n, tokens, gate.astype(jnp.float32),
      *((w_gate,) if glu else ()), w_up, w_down)
