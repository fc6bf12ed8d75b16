"""Fused RMSNorm / LayerNorm Pallas kernels.

TPU-native answer to the reference's ``csrc/transformer/inference/csrc/
rms_norm.cu`` / ``layer_norm.cu`` and v2 core_ops (``inference/v2/kernels/
core_ops/cuda_rms_norm``, ``cuda_layer_norm``). The forward is a single
VMEM-resident row-block kernel (one HBM read + one write per element); the
backward uses the analytic VJP in jnp — it is a pure elementwise+reduction
expression that XLA fuses into adjacent matmul backward passes, so a
hand-written kernel buys nothing there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.registry import register

_BLOCK_ROWS = 256


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


from deepspeed_tpu.utils.compat import shape_dtype_struct as _sds


def _rms_fwd_kernel(x_ref, scale_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * scale_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_fwd_kernel(x_ref, scale_ref, bias_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * scale_ref[:].astype(jnp.float32) + bias_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


# what a block of _BLOCK_ROWS rows may hold: at 256 x 4,096 the step's two blocks and its float32 copies
# stand under Mosaic's 16 MiB scope; at a hidden width of 6,144 they do not (18.1 MiB), so a wider row
# takes fewer rows a block, by halves
_BLOCK_ELEMENTS = _BLOCK_ROWS * 4096


def _row_blocks(n_rows: int, width: int = 0) -> int:
    rows = _BLOCK_ROWS
    while rows > 8 and rows * width > _BLOCK_ELEMENTS:
        rows //= 2
    return min(rows, n_rows)


def _rms_fwd(x2, scale, eps):
    R, Dm = x2.shape
    br = _row_blocks(R, Dm)
    return pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        name="rms_norm",
        grid=(pl.cdiv(R, br),),
        in_specs=[
            pl.BlockSpec((br, Dm), lambda i: (i, 0)),
            pl.BlockSpec((Dm,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, Dm), lambda i: (i, 0)),
        out_shape=_sds((R, Dm), x2.dtype, x2, scale),
        interpret=_interpret(),
    )(x2, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm_p(x2, scale, eps):
    return _rms_fwd(x2, scale, eps)


def _rms_p_fwd(x2, scale, eps):
    return _rms_fwd(x2, scale, eps), (x2, scale)


def _rms_p_bwd(eps, res, g):
    x2, scale = res
    x = x2.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    s = scale.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = x * inv
    gy = gf * s
    # d/dx of x * rsqrt(mean(x^2)+eps):
    dx = inv * (gy - xhat * jnp.mean(gy * xhat, axis=-1, keepdims=True))
    dscale = jnp.sum(gf * xhat, axis=0)
    return dx.astype(x2.dtype), dscale.astype(scale.dtype)


_rms_norm_p.defvjp(_rms_p_fwd, _rms_p_bwd)


@register("rms_norm", "pallas")
def pallas_rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    return _rms_norm_p(x2, scale, eps).reshape(shape)


def _ln_fwd(x2, scale, bias, eps):
    R, Dm = x2.shape
    br = _row_blocks(R, Dm)
    return pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        name="layer_norm",
        grid=(pl.cdiv(R, br),),
        in_specs=[
            pl.BlockSpec((br, Dm), lambda i: (i, 0)),
            pl.BlockSpec((Dm,), lambda i: (0,)),
            pl.BlockSpec((Dm,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, Dm), lambda i: (i, 0)),
        out_shape=_sds((R, Dm), x2.dtype, x2, scale, bias),
        interpret=_interpret(),
    )(x2, scale, bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layer_norm_p(x2, scale, bias, eps):
    return _ln_fwd(x2, scale, bias, eps)


def _ln_p_fwd(x2, scale, bias, eps):
    return _ln_fwd(x2, scale, bias, eps), (x2, scale)


def _ln_p_bwd(eps, res, g):
    x2, scale = res
    x = x2.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    s = scale.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * inv
    gy = gf * s
    dx = inv * (gy - jnp.mean(gy, axis=-1, keepdims=True) - xhat * jnp.mean(gy * xhat, axis=-1, keepdims=True))
    dscale = jnp.sum(gf * xhat, axis=0)
    dbias = jnp.sum(gf, axis=0)
    return dx.astype(x2.dtype), dscale.astype(scale.dtype), dbias.astype(scale.dtype)


_layer_norm_p.defvjp(_ln_p_fwd, _ln_p_bwd)


@register("layer_norm", "pallas")
def pallas_layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    return _layer_norm_p(x2, scale, bias, eps).reshape(shape)
