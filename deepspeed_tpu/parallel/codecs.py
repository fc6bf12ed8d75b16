"""The int8 block wire: what ZeRO++'s quantized collectives put on the link.

Reference analog: the ZeRO++ CUDA quantizers (``csrc/quantization/
swizzled_quantize.cu``, ``quant_reduce.cu``) — there, quantization is fused
into each collective's staging buffers. Here the codec is a pure encode/decode
pair over jax arrays that ``parallel/quant_collectives.py`` and the custom-vjp
gathers of ``parallel/zeropp.py`` apply around their one ``all_gather`` /
``all_to_all``, so both share one wire format.

Shapes: the codec operates on **blocked rows** — a 2D ``[R, L]`` array where
each row is one wire unit (a destination shard, a gather payload) and blocks
never straddle rows. ``encode_rows`` pads ``L`` up to a whole number of blocks
internally; ``decode_rows`` strips the padding. The wire is a :class:`Wire`
pytree so it can be ``tree_map``-ed through any collective.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

DEFAULT_BLOCK = 2048


class Wire(NamedTuple):
    """One on-wire payload: quantized values + per-block scales."""

    q: jax.Array
    s: jax.Array


def _pad_rows(x: jax.Array, block: int) -> Tuple[jax.Array, int]:
    """Pad the row length up to a whole number of blocks."""
    R, L = x.shape
    Lp = -(-L // block) * block
    if Lp != L:
        x = jnp.pad(x, ((0, 0), (0, Lp - L)))
    return x, Lp


class Int8BlockCodec:
    """Blockwise-symmetric int8: int8 values + one fp32 absmax scale per
    block (the qwZ/qgZ wire — ``csrc/quantization/swizzled_quantize.cu``).
    ~4x fp32 / ~2x bf16 wire reduction at ``block_size >> 4``.

    Quantization routes through the ``ops.quant`` registry (the ONE int8
    block format): the Pallas kernel wins dispatch on TPU, the jnp fallback
    elsewhere. Row padding here guarantees blocks never straddle rows, the
    invariant every collective relies on.
    """

    def __init__(self, block_size: int = DEFAULT_BLOCK):
        self.block_size = int(block_size)

    def encode_rows(self, x: jax.Array) -> Wire:
        """``[R, L] -> Wire``. Rows are independent wire units."""
        from deepspeed_tpu.ops.quant import quantize_int8

        R, _ = x.shape
        block = min(self.block_size, x.shape[1])
        xp, Lp = _pad_rows(x.astype(jnp.float32), block)
        q, scale = quantize_int8(xp, block_size=block)  # row-aligned: Lp % block == 0
        return Wire(q=q.reshape(R, Lp), s=scale.reshape(R, Lp // block))

    def decode_rows(self, wire: Wire, length: int, dtype) -> jax.Array:
        """``Wire -> [R, length]`` in ``dtype`` (padding stripped)."""
        from deepspeed_tpu.ops.quant import dequantize_int8

        R, Lp = wire.q.shape
        block = Lp // wire.s.shape[1]
        out = dequantize_int8(wire.q.reshape(-1), wire.s.reshape(-1), (R, Lp),
                              dtype=dtype, block_size=block)
        return out[:, :length]
