"""Normalization ops (fused RMS/LayerNorm).

Reference analog: ``csrc/transformer/inference/csrc/rms_norm.cu`` /
``layer_norm.cu`` and the v2 core_ops. XLA fuses the jnp fallback well; the
Pallas versions exist for the residual-add-fused variants where measurement
shows wins.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.registry import available_impls, dispatch, register


def _rowwise(op_name: str, impl: str, x, *params, eps: float):
    """Dispatch a per-row norm; its Pallas kernel runs per shard under GSPMD
    (``ops/partition.py``) — rows split with the activation, params whole."""
    fn = dispatch(op_name, impl)
    if fn is available_impls(op_name).get("pallas"):
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.ops.partition import kernel_mesh, per_shard, rowwise_spec

        ctx = kernel_mesh()
        if ctx is not None:
            mesh, free = ctx
            spec = rowwise_spec(mesh, free, x.shape)
            return per_shard(lambda x, *ps: fn(x, *ps, eps=eps), mesh, free,
                             (spec,) + (P(),) * len(params), spec)(x, *params)
    return fn(x, *params, eps=eps)


@register("rms_norm", "xla")
def _xla_rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def rms_norm(x, scale, eps: float = 1e-5, impl: str = "auto"):
    return _rowwise("rms_norm", impl, x, scale, eps=eps)


@register("layer_norm", "xla")
def _xla_layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5, impl: str = "auto"):
    return _rowwise("layer_norm", impl, x, scale, bias, eps=eps)
