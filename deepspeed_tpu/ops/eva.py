"""EVA attention's mathematics (EvaByte), shared by the flax module
(``models/transformer.py::EvaAttention``) and the paged serving path
(``inference/paged.py``), so the two cannot drift.

Token ``t`` lies in window ``t // window`` and chunk ``t // chunk``. Under ONE
softmax it attends to the exact keys of its own window up to itself and to one
SUMMARY a chunk of every window before its own; a window's own chunks are not
visible to it. A chunk's summary, per kv head with learned ``phi`` and ``mu``
``[kvH, hd]``: ``a = softmax over the chunk's tokens of k_i . phi`` (no
``1/sqrt(hd)``), ``k~ = sum_i a_i k_i + mu``, ``v~ = sum_i a_i v_i``. Keys
carry their rotary embedding, at their own absolute positions, before they are
pooled.

The two kinds of keys are attended to apart and merged by their log-sum-exps,
which is the one softmax: the exact part is plain causal attention inside a
window (``causal_attention_lse``: the flash kernel on the TPU, XLA elsewhere),
the summaries' part is dense attention over a set that is static a window.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.registry import dispatch, register

_EMPTY = -1e30  # the log-sum-exp of a part with no key


def pool_chunks(k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunk summaries. k, v: ``[..., c, kvH, hd]``, a chunk's tokens on axis
    -3; phi, mu: ``[kvH, hd]``. Returns ``(k~, v~)`` ``[..., kvH, hd]`` in the
    inputs' dtypes; the weights and the sums are fp32."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    a = jax.nn.softmax((kf * phi.astype(jnp.float32)).sum(-1), axis=-2)[..., None]  # [..., c, kvH, 1]
    ks = (a * kf).sum(-3) + mu.astype(jnp.float32)
    return ks.astype(k.dtype), (a * vf).sum(-3).astype(v.dtype)


def _attend(q, k, v, keep=None):
    """Dense grouped-query attention with its log-sum-exp. q ``[B, S, H, hd]``,
    k, v ``[B, T, kvH, hd]``, ``keep`` ``[S, T]`` bool (None: every key) ->
    (out ``[B, S, H, hd]``, lse fp32 ``[B, S, H]``)."""
    B, S, H, hd = q.shape
    kvH = k.shape[2]
    qg = q.reshape(B, S, kvH, H // kvH, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * hd ** -0.5
    if keep is not None:
        scores = jnp.where(keep, scores, _EMPTY)
    lse = jax.nn.logsumexp(scores, axis=-1)  # [B, kvH, G, S]
    probs = jnp.exp(scores - lse[..., None]).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v).reshape(B, S, H, hd)
    return out, lse.transpose(0, 3, 1, 2).reshape(B, S, H)


@register("causal_attention_lse", "xla")
def _xla_causal_attention_lse(q, k, v):
    S = q.shape[1]
    return _attend(q, k, v, keep=jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])


def causal_attention_lse(q, k, v, impl: str = "auto"):
    """Causal attention and its natural log-sum-exp a (query, head): what a
    caller needs to merge it with attention over further keys."""
    import deepspeed_tpu.ops.pallas.flash_attention  # noqa: F401  (registers the kernel)

    return dispatch("causal_attention_lse", impl)(q, k, v)


def merge(parts: List[Tuple[jax.Array, jax.Array]]) -> jax.Array:
    """Attention over the union of disjoint key sets from each set's (out
    ``[..., H, hd]``, lse ``[..., H]``): one softmax over all of them."""
    top = parts[0][1]
    for _, lse in parts[1:]:
        top = jnp.maximum(top, lse)
    weights = [jnp.exp(lse - top) for _, lse in parts]
    total = sum(weights)
    out = sum((w / total)[..., None] * o.astype(jnp.float32) for w, (o, _) in zip(weights, parts))
    return out.astype(parts[0][0].dtype)


def _summaries_seen(q, ks, vs, w: int, per: int) -> List[Tuple[jax.Array, jax.Array]]:
    """What window ``w``'s queries ``q`` attend to beside their window's exact
    keys, as (out, lse) parts: the summaries of the ``w * per`` chunks before
    the window, a STATIC set, so dense attention with no mask and no wasted
    product. ``ks``, ``vs``: every chunk's summary."""
    return [_attend(q, ks[:, :w * per], vs[:, :w * per])] if w else []


def eva_attention(q, k, v, phi, mu, window: int, chunk: int, impl: str = "auto"):
    """EVA attention of a sequence that starts at a window boundary. q
    ``[B, S, H, hd]``, k, v ``[B, S, kvH, hd]`` after RoPE. Returns (out
    ``[B, S, H, hd]``, k~, v~ ``[B, chunks, kvH, hd]``: the summary of every
    chunk of the sequence padded to whole windows, or to whole chunks while it
    is shorter than one window; a chunk that holds padding gives garbage,
    which nothing attends to)."""
    B, S = q.shape[:2]
    width = window if S > window else -(-S // chunk) * chunk
    padded = -(-S // width) * width
    if padded != S:
        q, k, v = (jnp.pad(a, ((0, 0), (0, padded - S), (0, 0), (0, 0))) for a in (q, k, v))
    n_windows, per = padded // width, width // chunk

    def fold(a):  # a window a batch row
        return a.reshape((B * n_windows, width) + a.shape[2:])

    out, lse = causal_attention_lse(fold(q), fold(k), fold(v), impl=impl)
    out = out.reshape((B, n_windows, width) + q.shape[2:])
    lse = lse.reshape(B, n_windows, width, q.shape[2])
    with jax.named_scope("eva_close"):
        ks, vs = pool_chunks(*(a.reshape((B, padded // chunk, chunk) + a.shape[2:]) for a in (k, v)),
                             phi, mu)
    # a window at a time, so that the fp32 of a merge is a window's, not the sequence's
    windows = []
    for w in range(n_windows):
        seen = _summaries_seen(q[:, w * width:(w + 1) * width], ks, vs, w, per)
        windows.append(merge([(out[:, w], lse[:, w])] + seen) if seen else out[:, w])
    return jnp.concatenate(windows, axis=1)[:, :S], ks, vs
