"""Block int8 quantize/dequantize — XLA fallback implementations.

Reference analog: ``deepspeed/ops/quantizer`` (``csrc/quantization``) symmetric
block quantization. The Pallas versions (``ops/pallas/quantizer.py``) register
under the same op names and win dispatch on TPU; these jnp versions are the
universal fallback and the numerical baseline in tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.registry import dispatch, register

DEFAULT_BLOCK = 2048
FP8_MAX = 448.0  # float8_e4m3fn max normal


# -- shared block math -------------------------------------------------------
# THE symmetric block-quant formulas, written on [nb, block] fp32 tiles so
# the same code runs as the XLA fallback, inside the Pallas quantizer kernel
# (ops/pallas/quantizer.py) and in the wire codecs (parallel/codecs.py). One
# wire format everywhere.


def int8_block_math(x2: jax.Array):
    """``[nb, block] fp32 -> (int8 values [nb, block], fp32 scales [nb, 1])``
    — symmetric per-block absmax, nearest rounding."""
    absmax = jnp.max(jnp.abs(x2), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
    q = jnp.clip(jnp.round(x2 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_block_dequant(q2: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`int8_block_math` (fp32 out; caller casts)."""
    return q2.astype(jnp.float32) * scale


def fp8_block_math(x2: jax.Array):
    """``[nb, block] fp32 -> (e4m3 values, fp32 scales [nb, 1])`` — absmax
    mapped onto the fp8 dynamic range (emulated via ml_dtypes off-TPU)."""
    absmax = jnp.max(jnp.abs(x2), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / FP8_MAX)
    q = (x2 / scale).astype(jnp.float8_e4m3fn)
    return q, scale


def fp8_block_dequant(q2: jax.Array, scale: jax.Array) -> jax.Array:
    return q2.astype(jnp.float32) * scale


@register("quantize_int8", "xla")
def _xla_quantize_int8(x: jax.Array, block_size: int = DEFAULT_BLOCK, stochastic: bool = False, seed: int = 0):
    del stochastic, seed  # nearest rounding only in the fallback
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    block = min(block_size, n)
    nb = -(-n // block)
    if nb * block != n:
        flat = jnp.pad(flat, (0, nb * block - n))
    q, scale = int8_block_math(flat.reshape(nb, block))
    return q.reshape(-1)[:n], scale.reshape(-1)


@register("dequantize_int8", "xla")
def _xla_dequantize_int8(values: jax.Array, scales: jax.Array, shape, dtype=jnp.bfloat16, block_size: int = DEFAULT_BLOCK):
    n = int(values.shape[0])
    block = min(block_size, n)
    nb = scales.shape[0]
    flat = values
    if nb * block != n:
        flat = jnp.pad(flat, (0, nb * block - n))
    v2 = flat.reshape(nb, block).astype(jnp.float32) * scales.reshape(nb, 1)
    return v2.reshape(-1)[:n].reshape(shape).astype(dtype)


def quantize_int8(x, block_size: int = DEFAULT_BLOCK, stochastic: bool = False, seed: int = 0, impl: str = "auto"):
    return dispatch("quantize_int8", impl)(x, block_size=block_size, stochastic=stochastic, seed=seed)


def dequantize_int8(values, scales, shape, dtype=jnp.bfloat16, block_size: int = DEFAULT_BLOCK, impl: str = "auto"):
    return dispatch("dequantize_int8", impl)(values, scales, shape, dtype=dtype, block_size=block_size)
