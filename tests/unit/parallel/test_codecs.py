"""The int8 block wire of ZeRO++'s collectives (``parallel/codecs.py``): lengths survive the padding, a block never
straddles two rows, and what comes back is within a block's absmax / 127 of what went in."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.parallel.codecs import Int8BlockCodec

SHAPES = {
    "block-aligned": ((3, 64), 16),
    "block-not-dividing": ((3, 45), 16),
    "block-longer-than-a-row": ((2, 13), 32),
    "one-element": ((1, 1), 2048),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(SHAPES))
def test_int8_rows_round_trip(case, dtype):
    (rows, length), block = SHAPES[case]
    x = jax.random.normal(jax.random.PRNGKey(5), (rows, length), jnp.float32) * 3.0
    codec = Int8BlockCodec(block)
    wire = codec.encode_rows(x.astype(dtype))
    used = min(block, length)  # a block is never longer than a row
    padded = -(-length // used) * used
    assert wire.q.dtype == jnp.int8 and wire.q.shape == (rows, padded)
    assert wire.s.dtype == jnp.float32 and wire.s.shape == (rows, padded // used)
    back = codec.decode_rows(wire, length, dtype)
    assert back.shape == (rows, length) and back.dtype == dtype
    sent = np.asarray(x.astype(dtype).astype(jnp.float32))
    blocks = np.pad(sent, ((0, 0), (0, padded - length))).reshape(rows, padded // used, used)
    step = np.repeat(np.abs(blocks).max(-1) / 127, used, axis=-1).reshape(rows, padded)[:, :length]
    rounding = 2.0 ** -8 * np.abs(sent) if dtype == jnp.bfloat16 else 0.0  # the decode's own cast
    assert np.all(np.abs(np.asarray(back.astype(jnp.float32)) - sent) <= step / 2 + rounding + 1e-6)
    # a row's wire is its own: encoded alone it is the same bytes, so a shard's blocks end with the shard
    for r in range(rows):
        alone = codec.encode_rows(x[r:r + 1].astype(dtype))
        np.testing.assert_array_equal(np.asarray(alone.q[0]), np.asarray(wire.q[r]))
        np.testing.assert_array_equal(np.asarray(alone.s[0]), np.asarray(wire.s[r]))
