"""A learned sparse-attention indexer (DeepSeek sparse attention, as the
``glm_moe_dsa`` family has it): the mathematics, once, for the flax module
(``models/transformer.py::LatentAttention``) and the paged serving path
(``inference/paged.py::_latent_attention``), so the two cannot drift.

Beside its latent a token caches ONE index key ``kI`` ``[index_head_dim]``,
shared by the indexer's ``index_heads`` heads. A query at position ``t`` scores
every cached position ``s <= t``:

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])            (float32)

and attends the ``index_topk`` positions of largest ``I[t, s]`` alone, all of
them while ``t + 1 <= index_topk``, ties to the lower position. Queries and
keys carry RoPE on their FIRST ``rope_dim`` columns (``rotate``); the key goes
through a LayerNorm with bias before it (``key_norm``); ``w`` comes scaled by
``index_heads^-1/2 * index_head_dim^-1/2`` (``head_weights``).

Three pieces, each with its plain XLA form here:

- ``index_scores``: the products and the weighted sum over heads, ``-inf`` at
  ``s > t``. On the chip a prompt's call is the kernel ``dsa_index``
  (``ops/pallas/dsa.py``): a head's ``[queries, keys]`` scores never leave fast
  memory, where XLA would write all 32 heads' before it sums them.
- ``select_mask``: the choice for every query of a chunk, as a mask. No sort:
  the ``index_topk``-th largest score of a row is found by bisection on the
  scores' bit patterns (31 steps of one compare and one count each, whatever
  ``index_topk``), and a tie at the threshold goes to the lower positions by a
  running count that is computed only in a call that has such a tie. On the
  chip a prompt's call is the kernel ``dsa_select`` (``ops/pallas/dsa.py``):
  the 31 steps run on a tile of queries' keys in fast memory, over the columns
  the tile can see, so HBM gives the scores once and takes the mask once, in
  the type its reader asked for; XLA's form reads a row's keys every step.
- ``select_positions``: the choice for ONE query a row (a decode step), as
  positions, by ``lax.top_k`` (lower index first among equals: the same set).

Scopes for a device trace, opened by the callers: ``dsa_index`` (the indexer's
products and scores), ``dsa_select`` (the choice), ``dsa_attend`` (attention
over the chosen).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.registry import dispatch, register

F32 = jnp.float32
_INT_MIN = np.int32(-2 ** 31)
# a prompt's queries go through the XLA form a tile at a time: 32 heads' scores of
# 8,192 queries against 8,192 keys are 8.6 GB in float32
_XLA_QUERY_TILE = 128
# the kernel takes whole tiles of queries; fewer (a decode step, a token and its drafts) are XLA's
_KERNEL_MIN_QUERIES = 128


def key_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-6) -> jax.Array:
    """LayerNorm with weight and bias over the index key's columns, float32 inside."""
    xf = x.astype(F32)
    centred = xf - xf.mean(-1, keepdims=True)
    y = centred * jax.lax.rsqrt((centred * centred).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(F32) + bias.astype(F32)).astype(x.dtype)


def rotate(x: jax.Array, positions: jax.Array, rope_dim: int, theta: float, interleaved: bool,
           inv_freq=None) -> jax.Array:
    """RoPE on the FIRST ``rope_dim`` columns of ``x`` ``[..., S, heads, D]`` at ``positions`` ``[..., S]``."""
    from deepspeed_tpu.models.transformer import rope_at

    turned = rope_at(x[..., :rope_dim], positions, theta, interleaved, inv_freq)
    return jnp.concatenate([turned, x[..., rope_dim:]], axis=-1)


def head_weights(raw: jax.Array, heads: int, head_dim: int) -> jax.Array:
    """``w`` float32 from the weights projection's output ``[..., heads]``."""
    return raw.astype(F32) * (heads ** -0.5 * head_dim ** -0.5)


@register("dsa_index", "xla")
def _xla_index_scores(q: jax.Array, k: jax.Array, w: jax.Array, q_positions: jax.Array) -> jax.Array:
    N, C, H, D = q.shape

    def tile(args):
        q, w, pos = args  # [N, c, H, D], [N, c, H], [N, c]
        s = jnp.einsum("nchd,nsd->nchs", q, k, preferred_element_type=F32)
        scores = (w[..., None] * jnp.maximum(s, 0.0)).sum(axis=2)
        seen = jnp.arange(k.shape[1])[None, None, :] <= pos[:, :, None]
        return jnp.where(seen, scores, -jnp.inf)

    c = _XLA_QUERY_TILE
    if C <= c or C % c:
        return tile((q, w, q_positions))
    tiles = (q.reshape(N, C // c, c, H, D), w.reshape(N, C // c, c, H), q_positions.reshape(N, C // c, c))
    out = jax.lax.map(tile, tuple(jnp.moveaxis(a, 1, 0) for a in tiles))  # [C / c, N, c, S]
    return jnp.moveaxis(out, 0, 1).reshape(N, C, -1)


def index_scores(q: jax.Array, k: jax.Array, w: jax.Array, q_positions: jax.Array,
                 impl: str = "auto") -> jax.Array:
    """``I`` float32 ``[N, C, S]``: query ``c`` of row ``n`` (``q`` ``[N, C, heads, D]``, ``w`` ``[N, C, heads]``
    float32, at ``q_positions`` ``[N, C]``) against the row's keys ``k`` ``[N, S, D]``, slot ``s`` position
    ``s``; ``-inf`` where ``s`` is past the query's position (a pad query's position is -1: all ``-inf``)."""
    import deepspeed_tpu.ops.pallas.dsa  # noqa: F401  (registers the kernel)

    if impl == "auto" and q.shape[1] < _KERNEL_MIN_QUERIES:
        impl = "xla"
    return dispatch("dsa_index", impl)(q, k, w, q_positions)


def _ordered(scores: jax.Array) -> jax.Array:
    """int32 with the order of the float32 ``scores`` (both zeros one value)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(scores == 0, 0.0, scores).astype(F32), jnp.int32)
    return jnp.where(bits < 0, bits ^ np.int32(0x7FFFFFFF), bits)


@register("dsa_select", "xla")
def _xla_select_mask(scores: jax.Array, topk: int, q_positions=None, dtype=jnp.bool_) -> jax.Array:
    key = _ordered(scores)
    candidate = scores > -jnp.inf
    if scores.shape[-1] <= topk:
        return candidate.astype(dtype)

    def enough(at):  # rows with topk keys or more at or over ``at``
        return (key >= at[..., None]).sum(-1) >= topk

    # the topk-th largest key of a row, a bit at a time from the sign down (INT_MIN where a row has fewer)
    kth = jnp.where(enough(jnp.zeros(key.shape[:-1], jnp.int32)), np.int32(0), _INT_MIN)

    def bit(i, kth):
        at = kth | jnp.left_shift(np.int32(1), 30 - i)
        return jnp.where(enough(at), at, kth)

    kth = jax.lax.fori_loop(0, 31, bit, kth)[..., None]
    over, tied = key > kth, (key == kth) & candidate
    left = topk - over.sum(-1, keepdims=True)  # what the tied positions may still take
    lower_first = lambda: over | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= left))
    chosen = jax.lax.cond((tied.sum(-1, keepdims=True) > left).any(), lower_first, lambda: over | tied)
    return (chosen & candidate).astype(dtype)  # (bool as bool: no conversion)


def select_mask(scores: jax.Array, topk: int, dtype=jnp.bool_, q_positions: jax.Array = None,
                impl: str = "auto") -> jax.Array:
    """``[..., S]``, 1 (True) at the ``topk`` largest of each row of ``scores`` (``-inf``: no candidate), at
    every candidate of a row with no more than ``topk``, ties to the lower index; in ``dtype``, the type the
    mask's reader takes it in (bool; the latent kernel's walk the queries' own). ``q_positions`` ``[N, C]``, where
    the caller has them, say that query ``c`` of row ``n`` of ``scores`` ``[N, C, S]`` has no candidate past that
    column (``index_scores``' ``-inf``): the kernel then neither fetches nor counts what no query of a tile sees.
    The kernel takes a chunk of whole tiles of queries with more columns than it keeps; fewer queries (a token
    and its drafts) and ``S <= topk`` (every candidate) are XLA's."""
    import deepspeed_tpu.ops.pallas.dsa  # noqa: F401  (registers the kernel)

    if impl == "auto" and (scores.ndim != 3 or scores.shape[1] < _KERNEL_MIN_QUERIES or scores.shape[2] <= topk):
        impl = "xla"
    return dispatch("dsa_select", impl)(scores, topk, q_positions, dtype)


def select_positions(scores: jax.Array, topk: int) -> jax.Array:
    """int32 ``[..., min(topk, S)]``: the positions ``select_mask`` marks, -1 where a row has fewer."""
    values, at = jax.lax.top_k(jnp.where(scores == 0, 0.0, scores), min(topk, scores.shape[-1]))  # (-0.0 is 0.0)
    return jnp.where(values > -jnp.inf, at, -1).astype(jnp.int32)


def pack_mask(mask: jax.Array) -> jax.Array:
    """bool ``[..., S]`` as int32 ``[..., ceil(S / 32)]``, position ``s`` bit ``s % 32`` of word ``s // 32``."""
    S = mask.shape[-1]
    words = -(-S // 32)
    padded = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, words * 32 - S)])
    bits = padded.reshape(mask.shape[:-1] + (words, 32)).astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(bits.sum(-1, dtype=jnp.uint32), jnp.int32)
