"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on the
hyper-connections of arXiv:2409.19606): the mathematics, once, for the flax
module (``models/transformer.py::HyperConnection``) and the paged serving path
(``inference/paged.py``), so the two cannot drift.

The residual is ``n`` streams a token, ``X`` in ``R^{n x C}``. A sublayer ``F``
(attention or the feed-forward, each behind its own norm) reads a learned,
per-token mix of them and writes back through a doubly stochastic matrix:

- ``xbar = vec(X) * rsqrt(mean(vec(X)^2) + norm_eps)`` (no gain: it would fold
  into ``phi``), ``m = xbar @ phi`` with ``phi`` ``[nC, n^2 + 2n]``;
- ``H_pre = sigmoid(a_pre m[0:n] + b[0:n])``, ``H_post = 2 sigmoid(a_post
  m[n:2n] + b[n:2n])``;
- ``A = clip(a_res reshape(m[2n:], [n, n]) + reshape(b[2n:], [n, n]), clamp)``,
  ``M = exp(A)``, then ``iters`` times ``M <- M / (rowsum(M) + eps)``, ``M <-
  M / (colsum(M) + eps)`` (Sinkhorn-Knopp); ``H_res = M``;
- ``u = sum_i H_pre[i] X_i``; ``y = F(u)``; ``X'_i = sum_j H_res[i, j] X_j +
  H_post[i] y``.

The streams come in copied from the embedding (``spread``) and go out summed
(``collapse``). The mix (statistic, ``m``, sigmoids, Sinkhorn) is float32
whatever the streams' dtype; the streams stay in theirs.

**Layout.** The streams are ``[n, ..., C]``, the stream axis FIRST: a stream is
then a contiguous ``[..., C]`` slab, where ``[..., n, C]`` would pad ``n`` = 4
up to a 16-row tile in bf16. The mix is ``[n, ...]`` and ``[n, n, ...]`` likewise,
the tokens on the minor axes: a row or column sum is then an add of slabs,
not a reduction inside a 4 x 4 tile.

Scopes for a device trace: ``mhc`` > ``mhc_mix`` (statistic, ``phi`` product,
sigmoids, Sinkhorn), ``mhc_pre`` (the weighted read), ``mhc_post`` (the mix and
the write-back), opened here so that training and serving carry the same names.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.registry import dispatch, register

F32 = jnp.float32


class Mix(NamedTuple):
    """One sublayer's mixing coefficients of every token, float32."""

    pre: jax.Array   # [n, ...]     H_pre
    post: jax.Array  # [n, ...]     H_post
    res: jax.Array   # [n, n, ...]  H_res[i, j]: how much of stream j goes to stream i


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``iters`` rounds of row, then column, normalisation of the positive
    matrices ``m`` ``[n, n, ...]`` (row ``i`` on axis 0, column ``j`` on axis 1);
    ``eps`` is added to both sums. After a round the columns sum to ``s / (s +
    eps)`` exactly; the rows approach 1 as the rounds go on."""
    for _ in range(iters):
        m = m / (m.sum(1, keepdims=True) + eps)
        m = m / (m.sum(0, keepdims=True) + eps)
    return m


def mix(streams: jax.Array, phi: jax.Array, b: jax.Array, alpha: jax.Array, *, norm_eps: float,
        iters: int, eps: float, clamp: Tuple[float, float]) -> Mix:
    """streams ``[n, ..., C]``; ``phi`` ``[n * C, n^2 + 2n]`` (rows stream-major,
    as ``vec(X)``); ``b`` ``[n^2 + 2n]``; ``alpha`` ``[3]`` = (a_pre, a_post, a_res)."""
    n, C = streams.shape[0], streams.shape[-1]
    with jax.named_scope("mhc"), jax.named_scope("mhc_mix"):
        xf = streams.astype(F32)
        rstd = jax.lax.rsqrt((xf * xf).mean(axis=(0, -1)) + norm_eps)  # [...]
        # the norm is one scalar a token, so it is applied to the product, whose
        # operands are then the streams themselves and ``phi`` as it is held: both
        # bf16 where the model was created so, float32 wherever ``phi`` is
        wide = jnp.promote_types(streams.dtype, phi.dtype)
        m = jnp.einsum("i...c,icm->...m", streams.astype(wide), phi.astype(wide).reshape(n, C, -1),
                       preferred_element_type=F32) * rstd[..., None]
        m = jnp.moveaxis(m, -1, 0)  # [n^2 + 2n, ...]: an entry a slab, the tokens on the lanes
        a_pre, a_post, a_res = alpha.astype(F32)
        b = b.astype(F32).reshape((-1,) + (1,) * (m.ndim - 1))
        pre = jax.nn.sigmoid(a_pre * m[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a_post * m[n:2 * n] + b[n:2 * n])
        logits = jnp.clip(a_res * m[2 * n:] + b[2 * n:], *clamp).reshape((n, n) + m.shape[1:])
        return Mix(pre, post, sinkhorn(jnp.exp(logits), iters, eps))


def read(streams: jax.Array, mixed: Mix) -> jax.Array:
    """``u = sum_i H_pre[i] X_i``: ``[..., C]`` in the streams' dtype."""
    with jax.named_scope("mhc"), jax.named_scope("mhc_pre"):
        u = sum(mixed.pre[i][..., None] * streams[i].astype(F32) for i in range(streams.shape[0]))
        return u.astype(streams.dtype)


def write(streams: jax.Array, y: jax.Array, mixed: Mix) -> jax.Array:
    """``X'_i = sum_j H_res[i, j] X_j + H_post[i] y``: ``[n, ..., C]``."""
    n = streams.shape[0]
    with jax.named_scope("mhc"), jax.named_scope("mhc_post"):
        xf, yf = [streams[j].astype(F32) for j in range(n)], y.astype(F32)
        return jnp.stack([
            (sum(mixed.res[i, j][..., None] * xf[j] for j in range(n))
             + mixed.post[i][..., None] * yf).astype(streams.dtype) for i in range(n)])


@register("mhc_mix_read", "xla")
def _xla_mix_read(streams, phi, b, alpha, **sizes):
    mixed = mix(streams, phi, b, alpha, **sizes)
    return mixed, read(streams, mixed)


register("mhc_write", "xla")(write)


def mix_read(streams: jax.Array, phi: jax.Array, b: jax.Array, alpha: jax.Array, *, norm_eps: float, iters: int,
             eps: float, clamp: Tuple[float, float], impl: str = "auto"):
    """A sublayer's :func:`mix` and its :func:`read`: ``(mixed, u)``, ``mixed``
    for :func:`write_back` alone. On the TPU, at sizes it takes, one kernel
    that reads the streams once (``ops/pallas/mhc.py``, whose ``mixed`` is the
    coefficients as its write-back reads them); else the two functions above
    and their :class:`Mix`."""
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import mhc as kernels  # (registers the kernels)

    if impl == "auto":
        n, C = streams.shape[0], streams.shape[-1]
        # (the wider of the streams' dtype and ``phi``'s: the kernel holds ``phi`` in the product's operand type)
        takes = kernels.takes(n, streams.size // (n * C), C, jnp.promote_types(streams.dtype, phi.dtype))
        impl = "pallas" if registry._default_backend() == "tpu" and takes else "xla"
    return dispatch("mhc_mix_read", impl)(streams, phi, b, alpha, norm_eps=norm_eps, iters=iters, eps=eps, clamp=clamp)


def write_back(streams: jax.Array, y: jax.Array, mixed) -> jax.Array:
    """:func:`write` with the ``mixed`` of :func:`mix_read`, by the
    implementation that made it: the kernel's writes over ``streams``."""
    return dispatch("mhc_write", "xla" if isinstance(mixed, Mix) else "pallas")(streams, y, mixed)


def spread(x: jax.Array, n: int) -> jax.Array:
    """The embedding copied into ``n`` streams: ``[..., C] -> [n, ..., C]``."""
    return jnp.broadcast_to(x[None], (n,) + x.shape)


def collapse(streams: jax.Array) -> jax.Array:
    """The streams summed (float32 inside): ``[n, ..., C] -> [..., C]``."""
    return streams.astype(F32).sum(0).astype(streams.dtype)
