"""ZeRO++ wiring: quantized weight-gather / gradient-reduce inside the step.

Reference: ``deepspeed/runtime/comm/coalesced_collectives.py:31``
(``all_to_all_quant_reduce``, qgZ), ``zero/partition_parameters.py:1200``
(``all_gather_coalesced(quantize=True)``, qwZ) and the CUDA kernels under
``csrc/quantization/``. There the two are separate subsystems hooked into the
fetch coordinator and the gradient reducer.

TPU-native redesign: one differentiable collective. The stage-3 weight
all-gather IS the forward of a ``jax.custom_vjp`` op whose backward IS the
gradient reduce-scatter — so turning on qwZ quantizes the forward/backward
weight gathers and turning on qgZ quantizes the gradient reduction, both at
exactly one place in the compiled step. The engine runs its micro-batch
gradient computation inside a partial-manual ``shard_map`` over the data axes
(dp/fsdp manual, tp/sp/... auto) so the collectives are addressable; XLA still
schedules/overlaps them over ICI.

Int8 block quantization is the shared wire codec (``parallel/codecs.py`` —
one format across the all_to_all helpers and these custom-vjp gathers); comm
volume per gather/reduce is ~2x less than bf16, ~4x less than fp32 — the
ZeRO++ headline (``docs/_tutorials/zeropp.md:6-17``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from deepspeed_tpu.comm import comm as dist
from deepspeed_tpu.parallel.codecs import DEFAULT_BLOCK, Int8BlockCodec
from deepspeed_tpu.parallel.quant_collectives import exchange_wire, gather_wire
from deepspeed_tpu.utils.compat import axis_size as _axis_size


class CommPlan:
    """Per-leaf gather/scatter plan. A plain object (NOT a pytree node) so a
    plans tree zips against a params tree without being traversed into."""

    __slots__ = ("dim", "axes")

    def __init__(self, dim: Optional[int], axes: Tuple[str, ...] = ()):
        self.dim = dim
        self.axes = axes

    @property
    def sharded(self) -> bool:
        return self.dim is not None

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"CommPlan(dim={self.dim}, axes={self.axes})"


def leaf_comm_plan(spec: Optional[PartitionSpec], live_axes: Tuple[str, ...]) -> CommPlan:
    """Plan for one leaf: the data-axis-sharded dimension (if any).

    ``spec`` is the leaf's master/grad PartitionSpec; entries naming live data
    axes mark the dimension the weight gather / grad scatter works along.
    """
    if spec is None:
        return CommPlan(None)
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        hit = tuple(a for a in names if a in live_axes)
        if hit:
            return CommPlan(dim, hit)
    return CommPlan(None)


def _int8_all_gather_dim(x: jax.Array, dim: int, axes, block: int) -> jax.Array:
    """Encode the local shard once, gather the int8 wire, decode."""
    moved = jnp.moveaxis(x, dim, 0)
    rest = moved.shape[1:]
    flat = moved.reshape(-1)
    M = flat.shape[0]
    codec = Int8BlockCodec(block_size=min(block, M))
    n = _axis_size(axes)
    wire = codec.encode_rows(flat[None])
    deq = codec.decode_rows(gather_wire(wire, axes), M, x.dtype)  # [n, M]
    full = deq.reshape((n * moved.shape[0],) + rest)
    return jnp.moveaxis(full, 0, dim)


def _int8_rs_core(g: jax.Array, err, dim: int, axes, err_beta: float,
                  block: int) -> Tuple[jax.Array, Optional[jax.Array]]:
    """The ONE qgZ wire format (quantize per destination shard -> a2a ->
    dequant -> mean), with optional LoCo error feedback (reference
    ``coalesced_collectives.py:81 all_to_all_loco_quant_reduce`` +
    ``csrc/quantization/pt_binding.cpp loco_*``):

        v       = g + err_beta * err          (when err is carried)
        wire    = Q(v)                        (int8 rows, as plain qgZ)
        new_err = v - dequant(Q(v))           (what the wire dropped)
    """
    n = _axis_size(axes)
    v = g if err is None else g.astype(jnp.float32) + err_beta * err
    moved = jnp.moveaxis(v, dim, 0)
    D, rest = moved.shape[0], moved.shape[1:]
    flat = moved.reshape(-1)
    shard = flat.shape[0] // n
    codec = Int8BlockCodec(block_size=min(block, shard))
    rows = flat.reshape(n, shard)
    wire = codec.encode_rows(rows)

    new_err = None
    if err is not None:
        # local residual: exactly what this rank's wire payload dropped
        local_deq = codec.decode_rows(wire, shard, jnp.float32)
        new_err = (rows - local_deq).reshape(moved.shape)
        new_err = jnp.moveaxis(new_err, 0, dim).astype(err.dtype)

    deq = codec.decode_rows(exchange_wire(wire, axes), shard, jnp.float32)
    red = jnp.mean(deq, axis=0)
    out = red.reshape((D // n,) + rest).astype(g.dtype)
    return jnp.moveaxis(out, 0, dim), new_err


def _int8_reduce_scatter_dim(g: jax.Array, dim: int, axes, block: int) -> jax.Array:
    """Plain qgZ mean-reduce-scatter (coalesced_collectives.py:31)."""
    out, _ = _int8_rs_core(g, None, dim, axes, 0.0, block)
    return out


def _int8_reduce_scatter_dim_loco(g: jax.Array, err: jax.Array, dim: int, axes,
                                  err_beta: float, block: int
                                  ) -> Tuple[jax.Array, jax.Array]:
    """LoCo qgZ: error-feedback compensation + refreshed residual."""
    return _int8_rs_core(g, err, dim, axes, err_beta, block)


def _exact_all_gather_dim(x: jax.Array, dim: int, axes) -> jax.Array:
    return dist.all_gather(x, axes, concat_axis=dim)


def _exact_reduce_scatter_dim(g: jax.Array, dim: int, axes) -> jax.Array:
    n = _axis_size(axes)
    return dist.reduce_scatter(g, axes, scatter_axis=dim) / n


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def sharded_weight_gather(
    shard: jax.Array,
    dim: int,
    gather_axes: Tuple[str, ...],
    other_axes: Tuple[str, ...],
    quantize_weights: bool,
    quantize_grads: bool,
    block: int,
) -> jax.Array:
    """Differentiable ZeRO weight gather (must run inside shard_map).

    forward : shard -> full weight over ``gather_axes`` (int8 wire when
              ``quantize_weights`` — qwZ)
    backward: full-weight grads -> mean-reduced shard grads (int8 all-to-all
              when ``quantize_grads`` — qgZ), plus a mean over ``other_axes``
              (data axes the weight was replicated over).
    """
    if quantize_weights:
        return _int8_all_gather_dim(shard, dim, gather_axes, block)
    return _exact_all_gather_dim(shard, dim, gather_axes)


def _swg_fwd(shard, dim, gather_axes, other_axes, qw, qg, block):
    return sharded_weight_gather(shard, dim, gather_axes, other_axes, qw, qg, block), None


def _swg_bwd(dim, gather_axes, other_axes, qw, qg, block, _res, g):
    if qg:
        gs = _int8_reduce_scatter_dim(g, dim, gather_axes, block)
    else:
        gs = _exact_reduce_scatter_dim(g, dim, gather_axes)
    if other_axes:
        gs = jax.lax.pmean(gs, other_axes)
    return (gs,)


sharded_weight_gather.defvjp(_swg_fwd, _swg_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def sharded_weight_gather_loco(
    shard: jax.Array,
    err: jax.Array,
    inv: jax.Array,
    dim: int,
    gather_axes: Tuple[str, ...],
    other_axes: Tuple[str, ...],
    qw: bool,
    err_beta: float,
    block: int,
) -> jax.Array:
    """LoCo form of :func:`sharded_weight_gather`: same forward, but the
    backward's quantized reduce-scatter carries error feedback. The updated
    residual is smuggled out as ``err``'s cotangent — the engine reads the
    error buffer's "gradient" as the next step's buffer (the same trick the
    1-bit path uses to thread state through a compiled grad program).

    ``err`` is stored in TRUE gradient units; ``inv`` (= 1/loss_scale)
    converts to/from the scaled-loss wire units inside the backward, so a
    dynamic loss-scale change between steps cannot corrupt the residuals
    (same invariant as the 1-bit path)."""
    if qw:
        return _int8_all_gather_dim(shard, dim, gather_axes, block)
    return _exact_all_gather_dim(shard, dim, gather_axes)


def _swgl_fwd(shard, err, inv, dim, gather_axes, other_axes, qw, err_beta, block):
    out = sharded_weight_gather_loco(shard, err, inv, dim, gather_axes,
                                     other_axes, qw, err_beta, block)
    return out, (err, inv)


def _swgl_bwd(dim, gather_axes, other_axes, qw, err_beta, block, res, g):
    err_true, inv = res
    gs, new_err_wire = _int8_reduce_scatter_dim_loco(
        g, err_true / inv, dim, gather_axes, err_beta, block)
    if other_axes:
        gs = jax.lax.pmean(gs, other_axes)
    return gs, new_err_wire * inv, jnp.zeros_like(inv)


sharded_weight_gather_loco.defvjp(_swgl_fwd, _swgl_bwd)


def gather_params_for_compute(params, plans, qw: bool, qg: bool, block: int = DEFAULT_BLOCK,
                              live_axes: Tuple[str, ...] = (),
                              errors=None, err_beta: float = 0.8, inv=None):
    """Map ``sharded_weight_gather`` over a param pytree inside shard_map.

    ``plans`` mirrors ``params`` with a ``CommPlan`` per leaf; replicated
    leaves pass through (their grads get a pmean in the caller instead).
    ``errors`` (a mirror pytree of per-leaf residual buffers) switches the
    sharded leaves to the LoCo gather — their grads then compensate with and
    refresh the residuals (reference all_to_all_loco_quant_reduce); ``inv``
    (1/loss_scale) is required with it.
    """

    if errors is None:
        def one(leaf, plan):
            if not plan.sharded:
                return leaf
            other = tuple(a for a in live_axes if a not in plan.axes)
            return sharded_weight_gather(leaf, plan.dim, plan.axes, other, qw, qg, block)

        return jax.tree_util.tree_map(one, params, plans)

    def one_loco(leaf, err, plan):
        if not plan.sharded:
            return leaf
        other = tuple(a for a in live_axes if a not in plan.axes)
        return sharded_weight_gather_loco(leaf, err, inv, plan.dim, plan.axes,
                                          other, qw, err_beta, block)

    return jax.tree_util.tree_map(one_loco, params, errors, plans)
