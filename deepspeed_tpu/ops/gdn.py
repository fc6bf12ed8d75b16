"""Gated DeltaNet layers (linear attention with a gated delta rule,
arXiv:2412.06464): the mathematics, once, for the flax module
(``models/transformer.py::GatedDeltaNet``) and the paged serving path
(``inference/paged.py``), so the two cannot drift.

A layer has ``Hk`` query/key heads of ``Dk`` and ``Hv`` value heads of ``Dv``
(value head ``h`` reads key head ``h // (Hv / Hk)``) and, a value head, a state
``S`` in ``R^{Dk x Dv}`` (keys x values). With ``u`` the normed residual:

- ``[q | k | v | z] = u W_qkvz`` (``Hk Dk | Hk Dk | Hv Dv | Hv Dv``), ``[b | a]
  = u W_ba`` (``Hv`` each);
- ``[q | k | v] <- silu(conv([q | k | v]))``: one causal depthwise convolution
  over the last ``d_conv`` inputs of each channel, no bias (``ops/ssm.py::
  conv_inputs``); ``z``, ``b``, ``a`` do not pass through it;
- ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` (float32);
  ``q``, ``k`` L2-normalised over ``Dk`` (eps 1e-6), ``q`` times ``Dk^-0.5``;
- ``S <- exp(g) S``; ``d = beta (v - S^T k)``; ``S <- S + k d^T``; ``o = S^T q``;
- ``out = (rmsnorm_w(o) * silu(z)) W_out``, the norm a value head over its
  ``Dv`` (the gate AFTER the norm).

Two forms of the recurrence that give the same numbers: :func:`gdn_step`, one
token (a decode step), and :func:`gdn_chunked`, a run of tokens in chunks of
``chunk``: inside a chunk the delta rule's corrections solve a unit
lower-triangular system ``(I + A) U = [beta v | beta k e^G]`` with ``A_ij =
beta_i (k_i . k_j) e^{G_i - G_j}`` below the diagonal (``G`` the running sum of
``g`` in the chunk), between chunks the state is carried.

**Float32 between the projections.** ``[q | k | v | z]`` come in as the
in-projection's own float32 sums (the caller's product hands them over
unrounded; ``[b | a]`` in the activations' dtype) and stay float32 through the
convolution, the normalisation, the rule and the gated norm, whose result
leaves in the activations' dtype; the chunk's products take float32 operands
at the HIGHEST precision. Why: at a sequence's first tokens nothing averages.
A head whose state holds one term or few (also a head just decayed to nothing)
puts out ``(q . k) beta v``, gated by ``z``: a product of factors that each
carry the input's relative error whole, one of them a dot product of two unit
vectors that cancels to a tenth of their length, and the gated norm brings
whatever is left back to unit size, rounding included. There the FUNCTION is
ill-conditioned (one bfloat16 rounding of the embedding moves the float32
reference's own logits by 7% at a sequence's first three positions, 0.9% at
the median one), so what the mixer adds of its own counts many times over.
With q, k and v rounded to bfloat16 at each of three steps between the
projections the benchmark's cell read ``logit_rel_err`` 0.018-0.024 and its
routers' picks up to 0.32 sigma from ones they could have made, the largest at
positions 0-2 of layers 9-11; so, 0.013-0.017 and 0.12 on the same seed
(``tools/qwen3_next_controls.py --control where``; PERF.md, PR 48). A mixer
alone at the published head sizes, bfloat16 against float32 on the CPU: its
worst position's error 0.0155 where the median position's is 0.0052; so,
0.0030 and 0.0023. Operands that come in bfloat16 are computed as they come
(a caller's choice).

**Tokens that are not there.** As in ``ops/ssm.py``: a pad token (and every
token of a dead row) has ``g = 0`` and ``beta = 0``, so the state stands still,
exactly, and the convolution's tail is gathered from the last LIVE inputs.

Scopes for a device trace (the caller opens ``gdn`` around the mixer):
``gdn_conv``, ``gdn_chunk`` (the chunked form), ``gdn_update`` (one token),
``gdn_norm``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.registry import dispatch, register
from deepspeed_tpu.ops.ssm import PoolRow

# elements of one group of rows' in-chunk matrices ``[rows, chunks, Hv, chunk,
# chunk]``: past it ``gdn_chunked`` takes the rows a group at a time (at 2 ** 25,
# 64 rows of a (128, 256) prefill, the chip's compiler ran out of room, made a
# layer's in-projection twice and copied the state pool to keep a row of it)
_GROUP_ELEMENTS = 2 ** 24
L2_EPS = 1e-6
_EXACT = jax.lax.Precision.HIGHEST  # float32 operands multiplied as float32 (bfloat16 ones are, whatever is asked)


def group_rows(rows: int, tokens: int, chunk: int, heads: int) -> int:
    """How many of a call's ``rows`` of ``tokens`` tokens go through the chunked
    form at once: all, or the largest divisor of them whose in-chunk matrices
    stay under ``_GROUP_ELEMENTS``. ``gdn_chunked`` takes its rows so; a caller
    that makes the float32 ``[q | k | v | z]`` a group at a time as well holds a
    group's of them, not the call's (``inference/paged.py``)."""
    chunk = min(chunk, tokens)
    most = max(_GROUP_ELEMENTS // ((tokens + -tokens % chunk) * chunk * heads), 1)
    return rows if most >= rows else next(d for d in range(most, 0, -1) if rows % d == 0)


def rule_inputs(qkv, ba, A_log, dt_bias, sizes):
    """``(q [.., Hk, Dk], k [.., Hk, Dk], v [.., Hv, Dv], g [.., Hv], beta [..,
    Hv])`` from the convolved ``qkv`` [.., X] and the projection's ``[b | a]``:
    ``q`` and ``k`` normalised (``q`` scaled) in float32 and left in ``qkv``'s
    dtype, the log decay ``g`` and ``beta`` float32."""
    Hk, Hv, Dk, Dv = sizes.n_k_heads, sizes.n_v_heads, sizes.head_k_dim, sizes.head_v_dim
    lead = qkv.shape[:-1]
    q = qkv[..., :Hk * Dk].reshape(lead + (Hk, Dk))
    k = qkv[..., Hk * Dk:2 * Hk * Dk].reshape(lead + (Hk, Dk))
    v = qkv[..., 2 * Hk * Dk:].reshape(lead + (Hv, Dv))

    def unit(x, scale=1.0):
        xf = x.astype(jnp.float32)
        return (xf * (jax.lax.rsqrt((xf * xf).sum(-1, keepdims=True) + L2_EPS) * scale)).astype(x.dtype)

    b, a = ba[..., :Hv].astype(jnp.float32), ba[..., Hv:].astype(jnp.float32)
    g = -jnp.exp(A_log.astype(jnp.float32)) * jax.nn.softplus(a + dt_bias.astype(jnp.float32))
    return unit(q, Dk ** -0.5), unit(k), v, g, jax.nn.sigmoid(b)


def _value_heads(x, Hv: int):
    """``q`` or ``k`` [.., Hk, Dk] as every value head reads it, [.., Hv, Dk]."""
    Hk = x.shape[-2]
    return x if Hk == Hv else jnp.repeat(x, Hv // Hk, axis=-2)


def gdn_step(state, q, k, v, g, beta, live=None):
    """One token of the recurrence. ``state`` [B, Hv, Dk, Dv] float32, ``q``/``k``
    [B, Hk, Dk], ``v`` [B, Hv, Dv], ``g``/``beta`` [B, Hv] float32, ``live`` [B]
    bool (None: all). Returns ``(o [B, Hv, Dv] in v's dtype, state)``. Products
    and sums on the elements, float32: no matrix unit rounds the state."""
    Hv = v.shape[-2]
    if live is not None:
        g, beta = jnp.where(live[:, None], g, 0.0), jnp.where(live[:, None], beta, 0.0)
    qh = _value_heads(q, Hv).astype(jnp.float32)[..., None]  # [B, Hv, Dk, 1]
    kh = _value_heads(k, Hv).astype(jnp.float32)[..., None]
    state = jnp.exp(g)[..., None, None] * state
    d = beta[..., None] * (v.astype(jnp.float32) - (state * kh).sum(-2))  # [B, Hv, Dv]
    state = state + kh * d[..., None, :]
    return (state * qh).sum(-2).astype(v.dtype), state


def pool_rows(row: PoolRow, rows: int):
    """The call's rows of a layer's states, ``[rows, Hv, Dk, Dv]``, a fresh row's
    as zeros. The pool ``[layers, slots, Hv, Dk, Dv]`` float32 keeps a state as
    both forms and the kernel use it (``Dv`` on the lanes): a slice, no re-layout."""
    pool, layer, fresh = row
    came = jax.lax.dynamic_slice(pool, (layer, 0, 0, 0, 0), (1, rows) + pool.shape[2:])[0]
    return jnp.where(fresh[:, None, None, None], 0.0, came)


def put_pool_rows(row: PoolRow, states):
    """``states`` [rows, Hv, Dk, Dv] into the call's slots of the layer's row of the pool: the pool."""
    return jax.lax.dynamic_update_slice(row.pool, states[None], (row.layer, 0, 0, 0, 0))


@register("gdn_pool_step", "xla")
def _xla_pool_step(pool, layer, q, k, v, g, beta, live=None, fresh=None):
    row = PoolRow(pool, layer, jnp.zeros(v.shape[:1], bool) if fresh is None else fresh)
    o, states = gdn_step(pool_rows(row, v.shape[0]), q, k, v, g, beta, live)
    return o, put_pool_rows(row, states)


def gdn_pool_step(pool, layer, q, k, v, g, beta, live=None, fresh=None, impl: str = "auto"):
    """:func:`gdn_step` on row ``layer`` of the state pool, in place: ``(o, the
    pool)``. On the TPU, at sizes it takes, one kernel that reads a row's state
    once and writes it once (``ops/pallas/gdn_update.py``)."""
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import gdn_update  # (registers the kernel)

    if impl == "auto":
        takes = gdn_update.takes(q.shape[1], v.shape[1], q.shape[2], v.shape[2])
        impl = "pallas" if registry._default_backend() == "tpu" and takes else "xla"
    return dispatch("gdn_pool_step", impl)(pool, layer, q, k, v, g, beta, live=live, fresh=fresh)


def _chunks(q, k, v, g, beta, chunk, state):
    """The chunked form over ``[rows, T, ...]``, ``T`` a multiple of ``chunk``,
    pad tokens' ``g`` and ``beta`` already 0."""
    R, T, Hv, Dv = v.shape
    nc, C = T // chunk, chunk
    dtype, f32 = v.dtype, jnp.float32

    def by_chunk(x):  # [R, T, H, ...] -> [R, nc, H, C, ...]
        return jnp.moveaxis(x.reshape((R, nc, C) + x.shape[2:]), 2, 3)

    q, k = by_chunk(_value_heads(q, Hv)), by_chunk(_value_heads(k, Hv))  # [R, nc, Hv, C, Dk]
    v, g, beta = by_chunk(v), by_chunk(g), by_chunk(beta)
    cum = jnp.cumsum(g, axis=-1)  # G: the log of the decay from the chunk's start through i
    lower = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))  # e^{G_i - G_j}, j <= i
    kb = k.astype(f32) * beta[..., None]
    # the corrections inside a chunk: (I + A) U = [beta v | beta k e^G]
    kk = jnp.einsum("rchid,rchjd->rchij", kb.astype(dtype), k, preferred_element_type=f32, precision=_EXACT)
    A = jnp.where(jnp.tril(lower, -1), kk * decay, 0.0)
    rhs = jnp.concatenate([v.astype(f32) * beta[..., None], kb * jnp.exp(cum)[..., None]], axis=-1)
    U = jax.scipy.linalg.solve_triangular(A + jnp.eye(C, dtype=f32), rhs, lower=True, unit_diagonal=True)
    value, k_cum = U[..., :Dv], U[..., Dv:].astype(dtype)
    qk = jnp.where(lower, jnp.einsum("rchid,rchjd->rchij", q, k, preferred_element_type=f32, precision=_EXACT) * decay,
                   0.0)
    q_in = (q.astype(f32) * jnp.exp(cum)[..., None]).astype(dtype)  # what a token reads of the state it started from
    k_end = (k.astype(f32) * jnp.exp(cum[..., -1:] - cum)[..., None]).astype(dtype)  # what it leaves by the chunk's end
    whole = jnp.exp(cum[..., -1])  # [R, nc, Hv]: a chunk's whole decay

    def carry(s, xs):
        value, k_cum, qk, q_in, k_end, whole = xs
        sd = s.astype(dtype)
        product = functools.partial(jnp.einsum, preferred_element_type=f32, precision=_EXACT)
        v_new = value - product("rhid,rhdv->rhiv", k_cum, sd)
        o = product("rhid,rhdv->rhiv", q_in, sd) + product("rhij,rhjv->rhiv", qk.astype(dtype), v_new.astype(dtype))
        s = whole[..., None, None] * s + product("rhjd,rhjv->rhdv", k_end, v_new.astype(dtype))
        return s, o.astype(dtype)

    state, o = jax.lax.scan(carry, state, tuple(jnp.moveaxis(a, 1, 0) for a in (value, k_cum, qk, q_in, k_end, whole)))
    return jnp.moveaxis(o, (0, 3), (1, 2)).reshape(R, T, Hv, Dv), state  # [nc, R, Hv, C, Dv] -> [R, T, Hv, Dv]


@jax.named_scope("gdn_chunk")
def gdn_chunked(q, k, v, g, beta, chunk: int, initial_state=None, live=None):
    """The recurrence over a run of tokens, in chunks. ``q``/``k`` [B, T, Hk,
    Dk] (normalised, ``q`` scaled), ``v`` [B, T, Hv, Dv], ``g``/``beta`` [B, T,
    Hv] float32, ``initial_state`` [B, Hv, Dk, Dv] float32 (None: zeros),
    ``live`` [B, T] bool (None: all). Returns ``(o [B, T, Hv, Dv] in v's dtype,
    the state after each row's last live token, float32)``."""
    R, T, Hv, Dv = v.shape
    Dk = q.shape[-1]
    if live is not None:
        g, beta = jnp.where(live[..., None], g, 0.0), jnp.where(live[..., None], beta, 0.0)
    if initial_state is None:
        initial_state = jnp.zeros((R, Hv, Dk, Dv), jnp.float32)
    chunk = min(chunk, T)
    pad = -T % chunk
    if pad:  # g and beta 0: the state stands still over them
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (q, k, v, g, beta))
    rows = group_rows(R, T, chunk, Hv)
    if rows == R:
        o, state = _chunks(q, k, v, g, beta, chunk, initial_state)
    else:  # a group of rows at a time: the in-chunk matrices of all of them at once are the call's largest arrays
        grouped = jax.tree_util.tree_map(lambda a: a.reshape((R // rows, rows) + a.shape[1:]),
                                         (q, k, v, g, beta, initial_state))
        o, state = jax.lax.map(lambda a: _chunks(*a[:5], chunk, a[5]), grouped)
        o, state = o.reshape((R,) + o.shape[2:]), state.reshape((R,) + state.shape[2:])
    return o[:, :T], state


@jax.named_scope("gdn_norm")
def gated_norm(o, z, scale, eps: float, dtype=None):
    """``rmsnorm_w(o) * silu(z)`` a value head over its ``Dv`` (``o``, ``z`` [..,
    Hv, Dv]), float32 inside, in ``dtype`` (None: ``o``'s): the norm multiplies
    by ``w`` itself, the gate comes AFTER it."""
    of = o.astype(jnp.float32)
    of = of * jax.lax.rsqrt(jnp.mean(of * of, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)
    return (of * jax.nn.silu(z.astype(jnp.float32))).astype(dtype or o.dtype)


def mix(qkvz, ba, p, sizes, norm_eps: float, state=None, tail=None, new_lens=None
        ) -> Tuple[jax.Array, Optional[jax.Array], jax.Array]:
    """Everything of a mixer between its projections. ``qkvz`` [B, T, .]
    (float32: the product's own sums) and ``ba`` [B, T, 2 Hv] (the activations'
    dtype, which the result leaves in) are the in-projections' outputs; ``p`` the mixer's own
    leaves: ``gdn_conv`` [K, X], ``A_log``, ``dt_bias`` [Hv] and ``gdn_norm``
    (``scale`` [Dv]). ``state`` [B, Hv, Dk, Dv] float32 and ``tail`` [B, K - 1,
    X] are what the rows come with (None: nothing, a sequence's start),
    ``new_lens`` [B] how many of the ``T`` tokens are live (None: all). One
    token a row with a state takes the recurrence, anything else the chunked
    form. Returns ``(the normed, gated o [B, T, Hv Dv], state, tail)``. Where
    ``state`` is a :class:`PoolRow` the states are read from and written to the
    pool, and the pool comes back in their place; ``tail`` likewise, a
    :class:`PoolRow` of the conv pool (``ops/ssm.py::conv_inputs``)."""
    B, T = qkvz.shape[:2]
    X, Hv, Dv = sizes.conv_dim, sizes.n_v_heads, sizes.head_v_dim
    with jax.named_scope("gdn_conv"):
        qkv, tail = ssm.conv_inputs(qkvz, tail, p["gdn_conv"], None, new_lens)  # its first X columns
    q, k, v, g, beta = rule_inputs(qkv, ba, p["A_log"], p["dt_bias"], sizes)
    if T == 1 and state is not None:
        live = None if new_lens is None else new_lens > 0
        step = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], live)
        with jax.named_scope("gdn_update"):
            if isinstance(state, PoolRow):
                o, state = gdn_pool_step(state.pool, state.layer, *step, fresh=state.fresh)
            else:
                o, state = gdn_step(state, *step)
        o = o[:, None]
    else:
        live = None if new_lens is None else jnp.arange(T)[None, :] < new_lens[:, None]
        came = pool_rows(state, B) if isinstance(state, PoolRow) else state
        o, left = gdn_chunked(q, k, v, g, beta, sizes.chunk_size, came, live)
        state = put_pool_rows(state, left) if isinstance(state, PoolRow) else left
    y = gated_norm(o, qkvz[..., X:].reshape(B, T, Hv, Dv), p["gdn_norm"]["scale"], norm_eps, ba.dtype)
    return y.reshape(B, T, Hv * Dv), state, tail
