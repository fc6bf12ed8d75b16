"""``evabyte`` the plain way: a llama-shaped decoder over bytes whose attention
is EVA, an exact causal window beside chunk summaries of everything before it.
Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
cache, no kernels, no log-sum-exp merging (ONE softmax over one row of
scores); nothing imported from the system under test.

A layer, for ``x`` [S, hidden] kept in float32 (``fp32_skip_add``), RMSNorm
with weight ``1 + w`` (``norm_add_unit_offset``) and ``rms_norm_eps``, no bias:

- ``h = norm(x)``; ``q, k, v = h W_q, h W_k, h W_v`` a head; rotary (theta
  ``rope_theta``, the whole head, halves rotate together) on ``q`` and ``k``
  at the token's own absolute position, BEFORE anything below.
- token ``t`` lies in window ``t // window_size`` and chunk ``t //
  chunk_size``. A chunk's summary, per head with the head's ``phi`` and ``mu``:
  ``a = softmax over the chunk's tokens of k_i . phi`` (no ``1/sqrt(d)``),
  ``k~ = sum_i a_i k_i + mu``, ``v~ = sum_i a_i v_i``.
- query ``t`` attends, under one softmax with scores ``q . key / sqrt(d)``, to
  the exact keys ``i <= t`` of its OWN window and to the summaries of every
  chunk of the windows BEFORE its own; its window's own chunks are not visible
  to it. ``x <- x + o W_o``.
- ``x <- x + W_down(silu(W_gate n) * W_up n)``, ``n = norm(x)``.
- final norm; the head is ``[hidden, num_pred_heads * vocab]``, head ``m``
  (columns ``m * vocab ...``) predicts byte ``t + 1 + m``; float32 logits.

What the published config does not spell out is listed under ``assumed`` in
``benchmarks/configs/evabyte.json``: ``phi`` and ``mu`` are one vector of the
head's size a head, the pooling scores carry no ``1/sqrt(d)``, ``mu`` is added
to the summary key alone, head 0 is the head's first ``vocab`` columns.

The weights come in as the program's own arrays relabelled, bf16 at the size
of the benchmark's cell; they are cast up ONE LAYER at a time (``lax.scan``
over the stacked leaves). A sequence is worked through in blocks of
``QUERY_BLOCK`` queries, attention and the feed-forward layer alike, and the
sequences one after another, so that neither an ``[S, S]`` score nor an
``[S, intermediate]`` pair is ever whole:

    embed_in [V, h]   head [h, heads * V]   final_norm [h]
    layers: every entry stacked over the L layers
      norm1 norm2 [L, h]   wq [L, h, H, d]   wk wv [L, h, Hkv, d]   wo [L, H, d, h]
      phi mu [L, Hkv, d]   w_gate w_up [L, h, f]   w_down [L, f, h]
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256
MASKED = -1e30  # not -inf: a padded query that sees no key stays finite


def rms_norm(x, w, cfg):
    scale = 1.0 + w if cfg.get("norm_add_unit_offset") else w
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + cfg["rms_norm_eps"]) * scale


def rotary(x, positions, theta):
    """x [S, H, d] at ``positions`` [S]: dimension i pairs with i + d/2."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None, None] * inv_freq  # [S, 1, d/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def summaries(k, v, phi, mu, chunk):
    """k, v [S, H, d] -> (k~, v~) [S // chunk, H, d] of the whole chunks."""
    J = k.shape[0] // chunk
    kc = k[: J * chunk].reshape(J, chunk, *k.shape[1:])
    vc = v[: J * chunk].reshape(J, chunk, *v.shape[1:])
    a = jax.nn.softmax(jnp.einsum("jcnd,nd->jcn", kc, phi), axis=1)
    return jnp.einsum("jcn,jcnd->jnd", a, kc) + mu, jnp.einsum("jcn,jcnd->jnd", a, vc)


def layer(x, w, cfg):
    """One block of one sequence, x [S, hidden]."""
    w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
    S, hidden = x.shape
    H, d = w["wq"].shape[-2:]
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    group = H // w["wk"].shape[-2]
    pos = jnp.arange(S)

    h = rms_norm(x, w["norm1"], cfg)
    q = rotary(jnp.einsum("sh,hnd->snd", h, w["wq"]), pos, cfg["rope_theta"])
    k = rotary(jnp.einsum("sh,hnd->snd", h, w["wk"]), pos, cfg["rope_theta"])
    v = jnp.einsum("sh,hnd->snd", h, w["wv"])
    k, v, phi, mu = (jnp.repeat(a, group, axis=-2) for a in (k, v, w["phi"], w["mu"]))
    ks, vs = summaries(k, v, phi, mu, chunk)
    chunk_window = (jnp.arange(ks.shape[0]) * chunk) // window  # the window a chunk lies in

    blocks = -(-S // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - S
    qp, xp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))), jnp.pad(x, ((0, pad), (0, 0)))

    def block(i):
        t = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(qp, i * QUERY_BLOCK, QUERY_BLOCK)
        xb = jax.lax.dynamic_slice_in_dim(xp, i * QUERY_BLOCK, QUERY_BLOCK)
        exact = jnp.einsum("qnd,knd->nqk", qb, k) / math.sqrt(d)
        own = (pos[None, :] // window == t[:, None] // window) & (pos[None, :] <= t[:, None])
        pooled = jnp.einsum("qnd,jnd->nqj", qb, ks) / math.sqrt(d)
        before = chunk_window[None, :] < t[:, None] // window
        scores = jnp.concatenate([jnp.where(own[None], exact, MASKED),
                                  jnp.where(before[None], pooled, MASKED)], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("nqk,knd->qnd", probs[..., :S], v) + jnp.einsum("nqj,jnd->qnd", probs[..., S:], vs)
        xb = xb + jnp.einsum("qnd,ndh->qh", o, w["wo"])
        n = rms_norm(xb, w["norm2"], cfg)
        return xb + (jax.nn.silu(n @ w["w_gate"]) * (n @ w["w_up"])) @ w["w_down"]

    return jax.lax.map(block, jnp.arange(blocks)).reshape(blocks * QUERY_BLOCK, hidden)[:S]


def hidden_states(weights, cfg, tokens):
    """tokens [B, S] int -> the final norm's output [B, S, hidden] float32."""
    def one(seq):
        x = weights["embed_in"].astype(F32)[seq]
        x, _ = jax.lax.scan(lambda x, w: (layer(x, w, cfg), None), x, weights["layers"])
        return rms_norm(x, weights["final_norm"].astype(F32), cfg)

    return jax.lax.map(one, tokens)


def forward(weights, cfg, tokens):
    """tokens [B, S] int -> head 0's logits [B, S, V] float32: the next byte's."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(weights, cfg, tokens)
        return x @ weights["head"][:, : cfg["vocab_size"]].astype(F32)


def forward_all_heads(weights, cfg, tokens):
    """tokens [B, S] int -> [B, S, num_pred_heads, V]: head m predicts byte t + 1 + m."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(weights, cfg, tokens)
        logits = x @ weights["head"].astype(F32)
        return logits.reshape(logits.shape[:2] + (cfg["num_pred_heads"], cfg["vocab_size"]))
