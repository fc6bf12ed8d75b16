"""``granitemoehybrid`` (dense: ``num_local_experts`` 0) the plain way: a
decoder whose layers follow a PATTERN (``layer_types``), Mamba-2 state-space
mixers beside grouped-query attention with no positional term at all, every
mixer followed by one silu-GLU. Float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no cache, no kernels, no chunks:
a state-space layer is the SEQUENTIAL recurrence, one token after another (a
``lax.scan`` over the tokens); nothing imported from the system under test.

With ``h`` the residual stream [S, hidden] and ``E`` the tied embedding:

- ``h = embedding_multiplier * E[token]``. Every layer: ``h = h +
  residual_multiplier * mixer(rmsnorm(h))``, then ``h = h + residual_multiplier
  * mlp(rmsnorm(h))``, ``mlp(u) = (silu(u W_gate) * (u W_up)) W_down``, no bias,
  ``rms_norm_eps``. ``logits = rmsnorm(h) E^T / logits_scaling``.
- an ``attention`` layer: ``q, k, v = u W_q, u W_k, u W_v`` (``num_attention_
  heads`` query heads, ``num_key_value_heads`` key and value heads, no bias, NO
  rotary and no other position term), causal softmax of ``q . k *
  attention_multiplier`` (not ``head_dim ** -0.5``), ``W_o``.
- a ``mamba`` layer (``H`` = ``mamba_n_heads`` heads of ``P`` = ``mamba_d_head``,
  ``G`` = ``mamba_n_groups``, ``N`` = ``mamba_d_state``, ``K`` = ``mamba_d_conv``):
  ``[z | xBC | dt] = u W_in`` (``H P | H P + 2 G N | H``); ``xBC'_t = silu(sum_j
  w_j xBC_{t - K + 1 + j} + b)`` a channel (zeros before the sequence);
  ``[x | B | C] = xBC'``; ``dt_h = softplus(dt_h + dt_bias_h)``, ``a_h =
  exp(-dt_h exp(A_log_h))``; from ``S_h = 0`` [P, N], token by token, ``S_h <-
  a_h S_h + dt_h x_h (outer) B_g``, ``y_h = S_h C_g + D_h x_h`` (head ``h``
  reads group ``h // (H / G)``); ``out = rmsnorm_w(y * silu(z)) W_out``, the
  norm over all ``H P`` channels, the gate BEFORE it.

What the published config does not spell out is listed under ``assumed`` in
``benchmarks/configs/granite-4.0-h-micro.json``.

The weights come in as the program's own arrays relabelled, bf16 at the size of
the benchmark's cell; they are cast up one layer at a time. The pattern's
shortest period is found from ``layer_types``; the layers are scanned a period
a step, and the sequences one after another:

    embed [V, h]   final_norm [h]
    period: one entry a layer of ONE period, its leaves stacked over the periods
      attention: norm1 norm2 [n, h]   wq [n, h, H, d]   wk wv [n, h, Hkv, d]   wo [n, H, d, h]
      mamba:     norm1 norm2 [n, h]   w_in [n, h, 2 H P + 2 G N + H]   conv_w [n, K, X]   conv_b [n, X]
                 A_log dt_bias D [n, H]   norm_w [n, H P]   w_out [n, H P, h]
      both:      w_gate w_up [n, h, f]   w_down [n, f, h]
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
MASKED = -1e30
VOCAB_PIECES = 8  # the head a piece of the vocabulary at a time: E in float32 is never whole


def period_of(layer_types):
    """The shortest run of kinds that the pattern repeats whole."""
    types = tuple(layer_types)
    L = len(types)
    return next(types[:p] for p in range(1, L + 1) if L % p == 0 and types == types[:p] * (L // p))


def rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def attention(u, w, cfg):
    S = u.shape[0]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = jnp.einsum("se,ehd->shd", u, w["wq"])
    k = jnp.repeat(jnp.einsum("se,ehd->shd", u, w["wk"]), H // Hkv, axis=1)
    v = jnp.repeat(jnp.einsum("se,ehd->shd", u, w["wv"]), H // Hkv, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) * cfg["attention_multiplier"]
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, MASKED), axis=-1)
    return jnp.einsum("shd,hde->se", jnp.einsum("hst,thd->shd", probs, v), w["wo"])


def mamba(u, w, cfg):
    S = u.shape[0]
    H, P, G = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"]
    N, K = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    zxbcdt = u @ w["w_in"]
    z, xbc, dt = zxbcdt[:, :H * P], zxbcdt[:, H * P:2 * H * P + 2 * G * N], zxbcdt[:, 2 * H * P + 2 * G * N:]
    before = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    xbc = jax.nn.silu(sum(w["conv_w"][j] * before[j:j + S] for j in range(K)) + w["conv_b"])
    x = xbc[:, :H * P].reshape(S, H, P)
    B = jnp.repeat(xbc[:, H * P:H * P + G * N].reshape(S, G, N), H // G, axis=1)  # [S, H, N]
    C = jnp.repeat(xbc[:, H * P + G * N:].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [S, H]
    a = jnp.exp(-dt * jnp.exp(w["A_log"]))

    def token(state, t):
        a_t, dt_t, x_t, b_t, c_t = t
        state = a_t[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + w["D"][:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), F32), (a, dt, x, B, C))
    gated = y.reshape(S, H * P) * jax.nn.silu(z)
    return rms_norm(gated, w["norm_w"], cfg["rms_norm_eps"]) @ w["w_out"]


MIXERS = {"attention": attention, "mamba": mamba}


def layer(h, w, kind, cfg):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
    h = h + r * MIXERS[kind](rms_norm(h, w["norm1"], eps), w, cfg)
    u = rms_norm(h, w["norm2"], eps)
    return h + r * ((jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"])


def head(x, embed):
    """``x E^T``, a piece of the vocabulary cast up at a time."""
    V, width = embed.shape
    n = VOCAB_PIECES if V % VOCAB_PIECES == 0 else 1
    out = jax.lax.map(lambda e: x @ e.astype(F32).T, embed.reshape(n, V // n, width))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)


def _row(weights, cfg, tokens):
    kinds = period_of(cfg["layer_types"])
    h = cfg["embedding_multiplier"] * jnp.take(weights["embed"], tokens, axis=0).astype(F32)

    def period(h, w):
        for kind, layer_w in zip(kinds, w):
            h = layer(h, layer_w, kind, cfg)
        return h, None

    h, _ = jax.lax.scan(period, h, weights["period"])
    x = rms_norm(h, weights["final_norm"].astype(F32), cfg["rms_norm_eps"])
    return head(x, weights["embed"]) / cfg["logits_scaling"]


def forward(weights, cfg, tokens):
    """tokens [B, S] int -> logits [B, S, V] float32."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda t: _row(weights, cfg, t), jnp.asarray(tokens))


def loss(weights, cfg, tokens):
    """Mean next-token cross-entropy over a batch [B, S]."""
    logits = forward(weights, cfg, tokens)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, jnp.asarray(tokens)[:, 1:, None], axis=-1).mean()
