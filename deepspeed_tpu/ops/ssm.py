"""Mamba-2 state-space layers (SSD, arXiv:2405.21060): the mathematics, once,
for the flax module (``models/transformer.py::Mamba2Mixer``) and the paged
serving path (``inference/paged.py``), so the two cannot drift.

A layer has ``H`` heads of ``P`` channels and, a head, a state ``S`` in
``R^{P x N}``. With ``u`` the normed residual and ``W_in`` one projection:

- ``[z | xBC | dt] = u W_in`` (``H P | H P + 2 G N | H``; ``G`` groups share
  ``B`` and ``C`` among ``H / G`` heads each);
- ``xBC' = silu(conv(xBC) + b)``: a causal depthwise convolution over the last
  ``d_conv`` inputs of each channel, ``x``, ``B`` and ``C`` together;
  ``[x | B | C] = xBC'``;
- ``dt_h = softplus(dt_h + dt_bias_h)``, ``a_h = exp(dt_h A_h)``, ``A_h =
  -exp(A_log_h)``; ``S_h <- a_h S_h + dt_h x_h (outer) B``; ``y_h = S_h C +
  D_h x_h``;
- ``out = rmsnorm_w(y * silu(z)) W_out`` (the gate BEFORE the norm).

Two forms of the recurrence that give the same numbers: :func:`ssm_step`, one
token (a decode step), and :func:`ssd_chunked`, a run of tokens in chunks of
``chunk`` (a prompt, a training step): inside a chunk the masked product ``(C
B^T * L) (dt x)`` with ``L_ij = exp(sum_{j<k<=i} dt_k A)``, between chunks the
state carried. The state, the decays and ``dt`` are float32 whatever the
activations' dtype; the chunk's large products take operands in the
activations' dtype and accumulate in float32.

**Tokens that are not there.** A row's ``new_lens`` first tokens are live, the
rest is padding up to the call's shape, and a row with none is dead (a pad row,
a row an EOS ended). A pad token has ``dt = 0``: ``a = 1`` and nothing is
added, so the state stands still, exactly. The convolution's tail (its last
``d_conv - 1`` inputs, what the next call starts from) is gathered from the last
LIVE inputs, the tail that came in before them: a dead row's comes out as it
went in.

Scopes for a device trace, opened here so that training and serving carry the
same names (the caller opens ``ssm`` around the mixer): ``ssm_conv``,
``ssm_scan`` (the chunked form), ``ssm_update`` (one token), ``ssm_norm``.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.registry import dispatch, register

# elements of one group of rows' masked in-chunk products ``[rows, chunks, H,
# chunk, chunk]``: past it ``ssd_chunked`` takes the rows a group at a time
# (64 prompts of one 256-token chunk at 64 heads are 1 GiB of float32 at once)
_GROUP_ELEMENTS = 2 ** 26
_LANES = 128


def split_projection(zxbcdt, sizes):
    """``[z | xBC | dt]`` of the in-projection's output, by ``sizes``
    (``TransformerConfig.ssm``)."""
    d, c = sizes.d_inner, sizes.conv_dim
    return zxbcdt[..., :d], zxbcdt[..., d:d + c], zxbcdt[..., d + c:]


def conv_inputs(xbc, tail, kernel, bias, new_lens=None, at: int = 0):
    """``silu(conv(xBC) + bias)`` of ``xbc`` [B, T, X] after the inputs ``tail``
    [B, K - 1, X] that came before it (None: none did, zeros), ``kernel`` [K, X]
    with tap ``K - 1`` on the current token, ``bias`` [X] or None. Returns it
    and the tail the next call starts from: the ``K - 1`` inputs that end at
    each row's last live token, ``new_lens`` [B] of the ``T`` (None: all).
    ``xbc`` may be the whole of a projection's output [B, T, W]: the inputs are
    its columns ``at .. at + X``. Where ``tail`` is a :class:`PoolRow` of the
    conv pool the tails are read from and written to the pool, and the pool
    comes back in their place: one token a row by :func:`conv_pool_step`, in
    place, which takes the inputs where they lie.
    Under no scope of its own (``ops/gdn.py`` opens ``gdn_conv`` around it)."""
    B, T = xbc.shape[:2]
    K, X = kernel.shape
    if isinstance(tail, PoolRow):
        if T == 1:
            live = None if new_lens is None else new_lens > 0
            out, pool = conv_pool_step(tail.pool, tail.layer, xbc[:, 0], kernel, bias, live, tail.fresh, at=at)
            return out[:, None], pool
        out, left = conv_inputs(xbc, tail_rows(tail, B, K), kernel, bias, new_lens, at)
        return out, put_tail_rows(tail, left)
    xbc = xbc[..., at:at + X]
    if tail is None:
        tail = jnp.zeros((B, K - 1, X), xbc.dtype)
    seen = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)  # [B, K - 1 + T, X]
    out = sum(seen[:, j:j + T].astype(jnp.float32) * kernel[j].astype(jnp.float32) for j in range(K))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    out = jax.nn.silu(out).astype(xbc.dtype)
    if new_lens is None:
        return out, seen[:, T:]
    last = new_lens[:, None] + jnp.arange(K - 1)[None, :]  # a dead row: 0..K-2, the tail it came with
    return out, jnp.take_along_axis(seen, last[:, :, None], axis=1)


causal_conv = jax.named_scope("ssm_conv")(conv_inputs)  # the state-space mixer's, under its own scope


def scan_inputs(xbc, dt, dt_bias, sizes):
    """``(x [.., H, P], B [.., G, N], C [.., G, N], dt [.., H] float32)`` from
    the convolved ``xbc`` [.., X] and the projection's raw ``dt`` [.., H]."""
    H, P, G, N = sizes.n_heads, sizes.head_dim, sizes.n_groups, sizes.d_state
    lead = xbc.shape[:-1]
    x = xbc[..., :H * P].reshape(lead + (H, P))
    b = xbc[..., H * P:H * P + G * N].reshape(lead + (G, N))
    c = xbc[..., H * P + G * N:].reshape(lead + (G, N))
    return x, b, c, jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))


def _heads(bc, H: int):
    """``B`` or ``C`` [.., G, N] as every head reads it, [.., H, N]."""
    G = bc.shape[-2]
    return bc if G == H else jnp.repeat(bc, H // G, axis=-2)


def ssm_step(state, x, dt, A_log, B, C, D, live=None):
    """One token of the recurrence. ``state`` [B, H, P, N] float32, ``x`` [B, H,
    P], ``dt`` [B, H] (after the softplus), ``B``/``C`` [B, G, N], ``live`` [B]
    bool (None: all). Returns ``(y [B, H, P] in x's dtype, state)``."""
    H = x.shape[-2]
    dt = dt.astype(jnp.float32)
    if live is not None:
        dt = jnp.where(live[:, None], dt, 0.0)
    a = jnp.exp(dt * -jnp.exp(A_log.astype(jnp.float32)))  # [B, H]
    xf = x.astype(jnp.float32)
    bh, ch = _heads(B, H).astype(jnp.float32), _heads(C, H).astype(jnp.float32)
    state = a[..., None, None] * state + (dt[..., None] * xf)[..., None] * bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", state, ch) + D.astype(jnp.float32)[:, None] * xf
    return y.astype(x.dtype), state


def pool_tile(channels: int) -> int:
    """Lanes of a pool tile: 128 of a layer's ``H P`` channels, or all of them
    where they are not whole tiles (toy sizes)."""
    return _LANES if channels % _LANES == 0 else channels


def to_pool(states):
    """States ``[rows, H, P, N]`` as the pool keeps them, ``[rows, H P / W, N,
    W]`` (``W`` = :func:`pool_tile`): a tile is ``W`` consecutive channels ON
    THE LANES and the state's ``N`` on the sublanes. What is a vector over the
    channels (the decay, ``dt x``, ``y``) is then a lane-dense row that
    broadcasts down a tile, ``B`` and ``C`` one column a row of the call, and
    the read-out ``S C`` a sum over sublanes: plain adds, no lane reduction."""
    rows, H, P, N = states.shape
    W = pool_tile(H * P)
    return jnp.swapaxes(states.reshape(rows, H * P // W, W, N), -1, -2)


def from_pool(tiles, H: int, P: int):
    """:func:`to_pool`'s inverse: ``[rows, H P / W, N, W]`` -> ``[rows, H, P, N]``."""
    rows, _, N, _ = tiles.shape
    return jnp.swapaxes(tiles, -1, -2).reshape(rows, H, P, N)


class PoolRow(NamedTuple):
    """A layer's states where serving keeps them (``inference/cache.StatePool``):
    row ``layer`` of ``pool`` [layers, slots, H P / W, N, W] float32
    (:func:`to_pool`), the call's rows in slots 0..rows-1, to be updated IN
    PLACE; ``fresh`` [rows] bool marks the rows that start a sequence, whose
    slot still holds another's state. A layer's convolution tails likewise:
    row ``layer`` of the conv pool [layers, slots, (K - 1) X]."""

    pool: jax.Array
    layer: jax.Array
    fresh: jax.Array


def _kernels(H: int, P: int, N: int, groups: int = 1) -> bool:
    """Whether this call's states move through the pool's kernels
    (``ops/pallas/ssm_update.py``): on the TPU, at sizes they take."""
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import ssm_update

    return registry._default_backend() == "tpu" and ssm_update.takes(H, P, groups, N)


def tail_rows(row: PoolRow, rows: int, K: int):
    """The call's rows of a layer's convolution tails, ``[rows, K - 1, X]`` of
    row ``layer`` of the conv pool [layers, slots, (K - 1) X], a fresh row's as zeros."""
    pool, layer, fresh = row
    came = jax.lax.dynamic_slice(pool, (layer, 0, 0), (1, rows, pool.shape[2]))[0]
    return jnp.where(fresh[:, None], 0, came).reshape(rows, K - 1, -1)


def put_tail_rows(row: PoolRow, tail):
    """``tail`` [rows, K - 1, X] into the call's slots of the layer's row of the conv pool: the pool."""
    return jax.lax.dynamic_update_slice(row.pool, tail.astype(row.pool.dtype).reshape(1, tail.shape[0], -1),
                                        (row.layer, 0, 0))


@register("conv_pool_step", "xla")
def _xla_conv_pool_step(pool, layer, x, taps, bias=None, live=None, fresh=None, at: int = 0):
    row = PoolRow(pool, layer, jnp.zeros(x.shape[:1], bool) if fresh is None else fresh)
    out, left = conv_inputs(x[:, None], tail_rows(row, x.shape[0], taps.shape[0]), taps, bias,
                            None if live is None else live.astype(jnp.int32), at)
    return out[:, 0], put_tail_rows(row, left)


def conv_pool_step(pool, layer, x, taps, bias=None, live=None, fresh=None, at: int = 0, impl: str = "auto"):
    """One token of :func:`conv_inputs` on row ``layer`` of the conv pool, in
    place: ``(silu(conv(x) + bias) [rows, X], the pool)``, the inputs columns
    ``at .. at + X`` of ``x`` [rows, W]. On the TPU, at sizes it takes, one
    kernel that reads a row's tail once and writes it once
    (``ops/pallas/conv_update.py``); XLA's form, the prompt's lines at one
    token, makes six passes over it."""
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import conv_update  # (registers the kernel)

    if impl == "auto":
        takes = conv_update.takes(taps.shape[1], x.shape[0], at)
        impl = "pallas" if registry._default_backend() == "tpu" and takes else "xla"
    return dispatch("conv_pool_step", impl)(pool, layer, x, taps, bias, live=live, fresh=fresh, at=at)


def pool_rows(row: PoolRow, rows: int, H: int, P: int):
    """The call's rows of a layer's states, ``[rows, H, P, N]``, a fresh row's as zeros."""
    pool, layer, fresh = row
    N = pool.shape[3]
    if _kernels(H, P, N):
        from deepspeed_tpu.ops.pallas import ssm_update

        came = ssm_update.rows_out(pool, layer, rows).reshape(rows, H, P, N)
    else:
        came = from_pool(jax.lax.dynamic_slice(pool, (layer, 0, 0, 0, 0), (1, rows) + pool.shape[2:])[0], H, P)
    return jnp.where(fresh[:, None, None, None], 0.0, came)


def put_pool_rows(row: PoolRow, states):
    """``states`` [rows, H, P, N] into the call's slots of the layer's row of the pool: the pool."""
    rows, H, P, N = states.shape
    if _kernels(H, P, N):
        from deepspeed_tpu.ops.pallas import ssm_update

        return ssm_update.rows_in(row.pool, row.layer, states.reshape(rows, -1, pool_tile(H * P), N))
    return jax.lax.dynamic_update_slice(row.pool, to_pool(states)[None], (row.layer, 0, 0, 0, 0))


@register("ssm_pool_step", "xla")
def _xla_pool_step(pool, layer, x, dt, A_log, B, C, D, live=None, fresh=None):
    row = PoolRow(pool, layer, jnp.zeros(x.shape[:1], bool) if fresh is None else fresh)
    y, states = ssm_step(pool_rows(row, *x.shape), x, dt, A_log, B, C, D, live)
    return y, put_pool_rows(row, states)


def ssm_pool_step(pool, layer, x, dt, A_log, B, C, D, live=None, fresh=None, impl: str = "auto"):
    """:func:`ssm_step` on row ``layer`` of the state pool, in place: ``(y, the
    pool)``. On the TPU, at sizes it takes, one kernel that reads a row's state
    once and writes it once (``ops/pallas/ssm_update.py``); XLA's form reads it
    twice."""
    import deepspeed_tpu.ops.pallas.ssm_update  # noqa: F401  (registers the kernel)

    if impl == "auto":
        impl = "pallas" if _kernels(*x.shape[1:], B.shape[2], B.shape[1]) else "xla"
    return dispatch("ssm_pool_step", impl)(pool, layer, x, dt, A_log, B, C, D, live=live, fresh=fresh)


def _ssd(x, dt, A, B, C, chunk, state):
    """The chunked form over ``[rows, T, ...]``, ``T`` a multiple of ``chunk``,
    ``dt`` float32 with pad tokens' already 0. Heads are ``[G, H / G]``, a
    group's sharing its ``B`` and ``C``: the scores ``C B^T`` are a group's."""
    R, T, H, P = x.shape
    G, N = B.shape[-2:]
    nc, Q, K = T // chunk, chunk, H // G
    dtype = x.dtype
    x = jnp.moveaxis(x.reshape(R, nc, Q, G, K, P), 2, 4)  # [R, nc, G, K, Q, P]
    dt = jnp.moveaxis(dt.reshape(R, nc, Q, G, K), 2, 4)  # [R, nc, G, K, Q]
    b = jnp.moveaxis(B.reshape(R, nc, Q, G, N), 2, 3)  # [R, nc, G, Q, N]
    c = jnp.moveaxis(C.reshape(R, nc, Q, G, N), 2, 3)
    cum = jnp.cumsum(dt * A.reshape(G, K, 1), axis=-1)  # log of the decay from the chunk's start through i
    xdt = x.astype(jnp.float32) * dt[..., None]
    # inside a chunk: y_i = sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
    seg = cum[..., :, None] - cum[..., None, :]  # [R, nc, G, K, i, j]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), seg, -jnp.inf))
    scores = jnp.einsum("rcgin,rcgjn->rcgij", c, b, preferred_element_type=jnp.float32)
    y = jnp.einsum("rcgkij,rcgkjp->rcgkip", (scores[:, :, :, None] * decay).astype(dtype), xdt.astype(dtype),
                   preferred_element_type=jnp.float32)
    # what each chunk adds to the state by its end, and the state each starts from
    to_end = jnp.exp(cum[..., -1:] - cum)  # [R, nc, G, K, Q]
    added = jnp.einsum("rcgjn,rcgkjp->rcgkpn", b, (xdt * to_end[..., None]).astype(dtype),
                       preferred_element_type=jnp.float32)
    whole = jnp.exp(cum[..., -1])  # [R, nc, G, K]: a chunk's whole decay

    def carry(s, xs):
        w, add = xs
        return w[..., None, None] * s + add, s

    state, starts = jax.lax.scan(carry, state.reshape(R, G, K, P, N),
                                 (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)  # [R, nc, G, K, P, N], float32
    y = y + jnp.exp(cum)[..., None] * jnp.einsum("rcgin,rcgkpn->rcgkip", c.astype(jnp.float32), starts)
    return jnp.moveaxis(y, 4, 2).reshape(R, T, H, P), state.reshape(R, H, P, N)


@jax.named_scope("ssm_scan")
def ssd_chunked(x, dt, A_log, B, C, D, chunk: int, initial_state=None, live=None):
    """The recurrence over a run of tokens, in chunks. ``x`` [B, T, H, P],
    ``dt`` [B, T, H] (after the softplus), ``B``/``C`` [B, T, G, N], ``D`` [H],
    ``initial_state`` [B, H, P, N] float32 (None: zeros), ``live`` [B, T] bool
    (None: all). Returns ``(y [B, T, H, P] in x's dtype, the state after each
    row's last live token, float32)``."""
    R, T, H, P = x.shape
    N = B.shape[-1]
    dt = dt.astype(jnp.float32)
    if live is not None:
        dt = jnp.where(live[..., None], dt, 0.0)
    A = -jnp.exp(A_log.astype(jnp.float32))
    if initial_state is None:
        initial_state = jnp.zeros((R, H, P, N), jnp.float32)
    chunk = min(chunk, T)
    pad = -T % chunk
    if pad:  # dt 0: the state stands still over them
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (x, dt, B, C))
    args = (x, dt, B, C, initial_state)
    rows = max(_GROUP_ELEMENTS // ((T + pad) * chunk * H), 1)
    if rows >= R or R % rows:
        y, state = _ssd(x, dt, A, B, C, chunk, initial_state)
    else:  # a group of rows at a time: the in-chunk products of all of them at once are the call's largest array
        grouped = jax.tree_util.tree_map(lambda a: a.reshape((R // rows, rows) + a.shape[1:]), args)
        y, state = jax.lax.map(lambda g: _ssd(g[0], g[1], A, g[2], g[3], chunk, g[4]), grouped)
        y, state = y.reshape((R,) + y.shape[2:]), state.reshape((R,) + state.shape[2:])
    y = y[:, :T] + D.astype(jnp.float32)[:, None] * x[:, :T].astype(jnp.float32)
    return y.astype(x.dtype), state


@jax.named_scope("ssm_norm")
def gated_norm(y, z, scale, eps: float):
    """``rmsnorm_w(y * silu(z))`` over the whole inner width, float32 inside."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g * scale.astype(jnp.float32)).astype(y.dtype)


def mix(zxbcdt, p, sizes, norm_eps: float, state=None, tail=None, new_lens=None
        ) -> Tuple[jax.Array, Optional[jax.Array], jax.Array]:
    """Everything of a mixer between its two projections. ``zxbcdt`` [B, T, .]
    is the in-projection's output; ``p`` the mixer's own leaves: ``ssm_conv``
    (``kernel`` [K, X], ``bias``), ``A_log``, ``dt_bias``, ``D`` [H] and
    ``ssm_norm`` (``scale``). ``state`` [B, H, P, N] float32 and ``tail`` [B, K
    - 1, X] are what the rows come with (None: nothing, a sequence's start),
    ``new_lens`` [B] how many of the ``T`` tokens are live (None: all). One
    token a row with a state takes the recurrence, anything else the chunked
    form. Returns ``(the gated, normed y [B, T, H P], state, tail)``. Where
    ``state`` is a :class:`PoolRow` the states are read from and written to the
    pool, and the pool comes back in their place; ``tail`` likewise, a
    :class:`PoolRow` of the conv pool (:func:`conv_inputs`)."""
    B, T = zxbcdt.shape[:2]
    z, _, dt = split_projection(zxbcdt, sizes)
    xbc, tail = causal_conv(zxbcdt, tail, p["ssm_conv"]["kernel"], p["ssm_conv"]["bias"], new_lens, sizes.d_inner)
    x, b, c, dt = scan_inputs(xbc, dt, p["dt_bias"], sizes)
    if T == 1 and state is not None:
        live = None if new_lens is None else new_lens > 0
        step = (x[:, 0], dt[:, 0], p["A_log"], b[:, 0], c[:, 0], p["D"], live)
        with jax.named_scope("ssm_update"):
            if isinstance(state, PoolRow):
                y, state = ssm_pool_step(state.pool, state.layer, *step, fresh=state.fresh)
            else:
                y, state = ssm_step(state, *step)
        y = y[:, None]
    else:
        live = None if new_lens is None else jnp.arange(T)[None, :] < new_lens[:, None]
        came = pool_rows(state, B, *x.shape[2:]) if isinstance(state, PoolRow) else state
        y, left = ssd_chunked(x, dt, p["A_log"], b, c, p["D"], sizes.chunk_size, came, live)
        state = put_pool_rows(state, left) if isinstance(state, PoolRow) else left
    y = gated_norm(y.reshape(B, T, -1), z, p["ssm_norm"]["scale"], norm_eps)
    return y, state, tail
