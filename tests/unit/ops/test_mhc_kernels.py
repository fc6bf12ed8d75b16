"""The two kernels of the hyper-connections (``ops/pallas/mhc.py``:
``mhc_mix_read``, ``mhc_write``) in interpret mode against the mathematics
they stand in for (``ops/mhc.py``: ``mix``, ``read``, ``write``), at bf16 and
float32 streams, token counts that are and are not whole tiles, the chain's 64
tokens, ``C`` 256 and the cell's 3,584, a clamp that saturates and 2 against
20 Sinkhorn rounds; the write-back leaves its input buffer as its output."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import mhc
from deepspeed_tpu.ops.pallas import mhc as kernels

SIZES = dict(norm_eps=1e-6, eps=1e-6, clamp=(-30.0, 30.0))


def draw(shape, C, dtype, a_res=4.0, n=4, seed=0):
    """Streams ``[n, *shape, C]``, a sublayer's output, and a hyper-connection's
    leaves drawn as the xing cell draws them (``m`` of unit variance, ``b``
    normal, ``a_res`` 4: far from doubly stochastic before the rounds)."""
    key = jax.random.split(jax.random.PRNGKey(seed), 4)
    K = n * n + 2 * n
    x = jax.random.normal(key[0], (n,) + shape + (C,), jnp.float32).astype(dtype)
    y = jax.random.normal(key[1], shape + (C,), jnp.float32).astype(dtype)
    phi = (jax.random.normal(key[2], (n * C, K)) / np.sqrt(n * C)).astype(dtype)
    b = jax.random.normal(key[3], (K,)).astype(dtype)
    return x, y, phi, b, jnp.asarray([1.0, 1.0, a_res], dtype)


def rows_of(mixed: mhc.Mix):
    """A :class:`Mix` as the kernel lays it out: ``[n^2 + 2n, tokens]``."""
    n = mixed.pre.shape[0]
    return np.concatenate([np.asarray(mixed.pre).reshape(n, -1), np.asarray(mixed.post).reshape(n, -1),
                           np.asarray(mixed.res).reshape(n * n, -1)])


def close(got, want, dtype):
    tol = dict(rtol=1e-4, atol=2e-5) if dtype == jnp.float32 else dict(rtol=1e-2, atol=1e-2)  # a bf16 ulp
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **tol)


CASES = [
    # id, the tokens' shape, C, the streams' dtype, Sinkhorn rounds, a_res
    ("two-tiles-bf16", (2, 128), 256, jnp.bfloat16, 20, 4.0),
    ("two-tiles-fp32", (2, 128), 256, jnp.float32, 20, 4.0),
    ("no-whole-tile-bf16", (19, 16), 256, jnp.bfloat16, 20, 4.0),   # 304 tokens: a last tile of 48
    ("no-whole-tile-fp32", (3, 40), 256, jnp.float32, 20, 4.0),     # 120 tokens: under one tile
    ("chain-64-bf16", (64, 1), 256, jnp.bfloat16, 20, 4.0),
    ("chain-64-fp32-cell-width", (64, 1), 3584, jnp.float32, 20, 4.0),
    ("cell-width-bf16", (1, 128), 3584, jnp.bfloat16, 20, 4.0),
    ("clamp-saturates-fp32", (2, 128), 256, jnp.float32, 20, 400.0),
    ("clamp-saturates-bf16", (64, 1), 256, jnp.bfloat16, 20, 400.0),
    ("two-rounds-fp32", (2, 128), 256, jnp.float32, 2, 4.0),
    ("two-rounds-bf16", (19, 16), 256, jnp.bfloat16, 2, 4.0),
]


@pytest.mark.parametrize("shape,C,dtype,iters,a_res", [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_the_kernels_are_the_mathematics(shape, C, dtype, iters, a_res):
    x, y, phi, b, alpha = draw(shape, C, dtype, a_res)
    want = mhc.mix(x, phi, b, alpha, iters=iters, **SIZES)
    mixed, u = kernels.mhc_mix_read(x, phi, b, alpha, iters=iters, **SIZES)
    tokens = int(np.prod(shape))
    assert mixed.dtype == jnp.float32 and mixed.shape == (24, -(-tokens // 128) * 128)  # float32 whatever the streams
    np.testing.assert_allclose(np.asarray(mixed)[:, :tokens], rows_of(want), rtol=2e-5, atol=2e-6)
    assert u.shape == x.shape[1:] and u.dtype == x.dtype
    close(u, mhc.read(x, want), dtype)
    out = kernels.mhc_write(x, y, mixed)
    assert out.shape == x.shape and out.dtype == x.dtype
    close(out, mhc.write(x, y, want), dtype)
    if a_res > 100:  # the clamp acted (without it exp overflows), and at its ends the rounds leave a finite matrix
        assert not np.isfinite(rows_of(mhc.mix(x, phi, b, alpha, iters=iters, **dict(SIZES, clamp=(-1e4, 1e4))))).all()
        res = np.asarray(mixed)[8:, :tokens]
        assert np.isfinite(res).all() and res.max() <= 1 + 1e-5 and res.min() >= 0


def test_two_rounds_are_not_twenty():
    """What the cell's control ``sinkhorn_2`` rests on: the kernel runs the rounds it is asked for."""
    x, _, phi, b, alpha = draw((2, 128), 256, jnp.float32)
    two = kernels.mhc_mix_read(x, phi, b, alpha, iters=2, **SIZES)[0]
    twenty = kernels.mhc_mix_read(x, phi, b, alpha, iters=20, **SIZES)[0]
    np.testing.assert_array_equal(two[:8], twenty[:8])  # H_pre and H_post know no rounds
    rows = lambda m: np.asarray(m)[8:].reshape(4, 4, -1).sum(1)  # noqa: E731
    assert np.abs(rows(two) - 1).max() > 0.1 > np.abs(rows(twenty) - 1).max()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "fp32"])
def test_the_write_back_is_in_place(dtype):
    """``mhc_write`` aliases the streams to its result: in the jaxpr, and a
    donated array's buffer comes back as the result's, with no second array."""
    x, y, phi, b, alpha = draw((2, 128), 256, dtype)
    mixed, _ = kernels.mhc_mix_read(x, phi, b, alpha, iters=20, **SIZES)
    call = next(e for e in jax.make_jaxpr(kernels.mhc_write)(x, y, mixed).jaxpr.eqns if e.primitive.name == "pallas_call")
    assert tuple(call.params["input_output_aliases"]) == ((1, 0),)  # operand 1, the streams [n, tokens, C]
    assert [v.aval.shape for v in call.invars][1] == (4, 256, 256) == call.outvars[0].aval.shape
    want = np.asarray(mhc.write(x, y, mhc.mix(x, phi, b, alpha, iters=20, **SIZES)), np.float32)
    write = jax.jit(kernels.mhc_write, donate_argnums=0)
    assert "tf.aliasing_output = 0" in write.lower(x, y, mixed).as_text()
    held = x.unsafe_buffer_pointer()
    out = write(x, y, mixed)
    assert x.is_deleted() and out.unsafe_buffer_pointer() == held
    close(out, want, dtype)


def test_the_call_s_shapes_choose_the_kernels(monkeypatch):
    """``takes`` decides from the shapes alone, and ``ops/mhc.py``'s
    ``mix_read`` / ``write_back`` hand over by it: off the chip the
    ``jax.numpy`` form and its :class:`Mix`, on it the kernels."""
    from deepspeed_tpu.ops import registry

    assert kernels.takes(4, 8 * 2048, 3584, jnp.bfloat16)           # the cell's prefill
    assert kernels.takes(4, 4096, 3584, jnp.bfloat16)               # 112 MiB of streams: past fast memory
    assert not kernels.takes(4, 2048, 3584, jnp.bfloat16)           # 56 MiB: XLA's passes run from fast memory
    assert not kernels.takes(4, 64, 3584, jnp.bfloat16)             # a decode step: a launch, not bytes
    assert not kernels.takes(4, 8 * 2048, 3584, jnp.float32)        # a 128-token tile is past the default scope
    assert kernels.takes(4, 8 * 2048, 1792, jnp.float32)
    assert not kernels.takes(4, 8 * 2048, 3584 + 64, jnp.bfloat16)  # no whole lane tiles
    assert not kernels.takes(4, 8 * 2048 + 8, 3584, jnp.bfloat16)   # no whole packed sublane tiles
    assert not kernels.takes(12, 8 * 2048, 1024, jnp.bfloat16)      # 168 coefficients a token: more than a lane tile
    x, y, phi, b, alpha = draw((2, 128), 256, jnp.bfloat16)
    mixed, u = mhc.mix_read(x, phi, b, alpha, iters=20, **SIZES)
    assert isinstance(mixed, mhc.Mix)
    close(mhc.write_back(x, y, mixed), mhc.write(x, y, mixed), jnp.float32)
    monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")  # what 'auto' sees there
    monkeypatch.setattr(kernels, "_MIN_STREAM_BYTES", 0)  # (a toy's streams are no 112 MiB)
    text = str(jax.make_jaxpr(lambda x, y: mhc.write_back(x, y, mhc.mix_read(x, phi, b, alpha, iters=20, **SIZES)[0]))(x, y))
    assert text.count("pallas_call") == 2 and "name=mhc_mix_read" in text and "name=mhc_write" in text
    by_name, u2 = mhc.mix_read(x, phi, b, alpha, iters=20, impl="pallas", **SIZES)
    close(u2, u, jnp.bfloat16)
    close(mhc.write_back(x, y, by_name), mhc.write(x, y, mixed), jnp.bfloat16)
