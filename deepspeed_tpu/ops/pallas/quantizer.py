"""Block int8 quantize/dequantize Pallas kernels.

TPU-native answer to the reference's quantizer family
(``csrc/quantization/pt_binding.cpp`` — sym/asym block quant, stochastic
rounding, swizzled quant for ZeRO++ qgZ). Symmetric per-block absmax int8 is
the workhorse: it backs quantized weight allgather (qwZ analog), quantized
gradient reduction (qgZ analog — quantize → all_to_all → dequant-reduce
composed in shard_map, see parallel/quant_collectives), and weight-only-quant
inference.

Layout: the flat input is reshaped to [num_blocks, block_size]; each block
gets one f32 scale. Stochastic rounding uses the on-core PRNG
(``pltpu.prng_random_bits``) — deterministic nearest-rounding elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.registry import register

DEFAULT_BLOCK = 2048
_ROWS_PER_STEP = 64


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


from deepspeed_tpu.utils.compat import shape_dtype_struct as _sds


def _quant_kernel(x_ref, vals_ref, scales_ref):
    from deepspeed_tpu.ops.quant import int8_block_math

    q, scale = int8_block_math(x_ref[:].astype(jnp.float32))  # [rows, block]
    vals_ref[:] = q
    scales_ref[:] = scale.astype(jnp.float32)


def _quant_kernel_stochastic(seed_ref, x_ref, vals_ref, scales_ref):
    pltpu.prng_seed(seed_ref[0])
    x = x_ref[:].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
    scaled = x / scale
    # stochastic rounding: add uniform [0,1) then floor
    bits = pltpu.prng_random_bits(scaled.shape)
    u = (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    q = jnp.clip(jnp.floor(scaled + u), -127, 127)
    vals_ref[:] = q.astype(jnp.int8)
    scales_ref[:] = scale.astype(jnp.float32)


def _dequant_kernel(vals_ref, scales_ref, o_ref, *, dtype):
    o_ref[:] = (vals_ref[:].astype(jnp.float32) * scales_ref[:]).astype(dtype)


@register("quantize_int8", "pallas")
def pallas_quantize_int8(x: jax.Array, block_size: int = DEFAULT_BLOCK, stochastic: bool = False, seed: int = 0):
    """Flat symmetric int8 block quantization. Returns (values int8 [N], scales f32 [nb])."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    block = min(block_size, n)
    nb = -(-n // block)
    if nb * block != n:
        flat = jnp.pad(flat, (0, nb * block - n))
    x2 = flat.reshape(nb, block)
    rows = min(_ROWS_PER_STEP, nb)

    if stochastic and not _interpret():
        seed_arr = jnp.asarray([seed], jnp.int32)
        vals, scales = pl.pallas_call(
            _quant_kernel_stochastic,
            name="quantize_int8",
            grid=(pl.cdiv(nb, rows),),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((rows, block), lambda i: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((rows, block), lambda i: (i, 0)),
                pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                _sds((nb, block), jnp.int8, x2),
                _sds((nb, 1), jnp.float32, x2),
            ],
        )(seed_arr, x2)
    else:
        vals, scales = pl.pallas_call(
            _quant_kernel,
            name="quantize_int8",
            grid=(pl.cdiv(nb, rows),),
            in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
            out_specs=[
                pl.BlockSpec((rows, block), lambda i: (i, 0)),
                pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                _sds((nb, block), jnp.int8, x2),
                _sds((nb, 1), jnp.float32, x2),
            ],
            interpret=_interpret(),
        )(x2)
    return vals.reshape(-1)[:n], scales.reshape(-1)


@register("dequantize_int8", "pallas")
def pallas_dequantize_int8(values: jax.Array, scales: jax.Array, shape, dtype=jnp.bfloat16, block_size: int = DEFAULT_BLOCK):
    n = int(values.shape[0])
    block = min(block_size, n)
    nb = scales.shape[0]
    flat = values
    if nb * block != n:
        flat = jnp.pad(flat, (0, nb * block - n))
    v2 = flat.reshape(nb, block)
    rows = min(_ROWS_PER_STEP, nb)
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, dtype=dtype),
        name="dequantize_int8",
        grid=(pl.cdiv(nb, rows),),
        in_specs=[
            pl.BlockSpec((rows, block), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=_sds((nb, block), dtype, v2, scales),
        interpret=_interpret(),
    )(v2, scales.reshape(nb, 1))
    return out.reshape(-1)[:n].reshape(shape)
