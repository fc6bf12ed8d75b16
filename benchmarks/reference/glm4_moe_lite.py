"""``glm4_moe_lite`` the plain way: a decoder with latent attention, leading
dense layers, then routed layers of ``n_routed_experts`` silu-GLU experts at
top ``num_experts_per_tok`` beside a shared expert. Float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no cache, no kernels, attention
NOT absorbed (every head's keys and values are formed from the latent);
nothing imported from the system under test.

A layer, for ``x`` [S, hidden] (RMSNorm with ``rms_norm_eps``, no biases):

- ``h = norm(x)``; ``c_q = norm(h W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` a
  head; ``[c_kv | k_r] = h W_kva``; ``c_kv <- norm(c_kv)``; rotary over all
  ``qk_rope_head_dim`` columns of ``q_rope`` and ``k_r`` (``rope_theta``, no
  scaling), ``k_r`` ONE head shared by all; ``[k_nope | v] = c_kv W_kvb`` a
  head; scores ``(q_nope k_nope + q_rope k_rope) / sqrt(nope + rope)``, causal
  softmax, ``o = P v``; ``x <- x + concat(o) W_o``.
- ``h2 = norm(x)``. The first ``first_k_dense_replace`` layers: ``x <- x +
  (silu(h2 W_g) * h2 W_u) W_d``. The others: ``s = sigmoid(h2 W_r)``; the picks
  are ``top_k(s + b)``, ``b`` the correction bias (``noaux_tc``, one group);
  ``w_e = routed_scaling_factor * s_e / (sum over the picked of s + 1e-20)``
  (``norm_topk_prob``): the bias picks, it never weighs; ``x <- x + sum_e w_e
  GLU_e(h2) + GLU_shared(h2)``.
- final norm, untied head. The next-token-prediction layer is not built.

Rotary pairs are ADJACENT columns (``x[2i], x[2i+1]``), the layout this family
stores (its modelling code de-interleaves q and k alike, then rotates halves:
the same scores); the configuration file says so under ``assumed``.

``forward(weights, cfg, tokens, picks=None)``: with ``picks`` ``[B, S, routed
layers, k]`` every position goes to exactly those experts, weighted from this
file's own fp32 scores over them; with ``None`` the choice is this file's own
top-k. ``route_shortfall`` says, along the same pinned pass, how far the picks
are from ones this router could have made, on the SELECTION score ``s + b``.

The weights come in as the program's own arrays relabelled, bf16 at the size
of the benchmark's cell, 10 GB of them: they are cast up ONE LAYER, and within
it ONE EXPERT, at a time (``lax.scan`` over the stacked leaves), so that no
more than a layer's small matrices and one expert are held in float32:

    embed_in [V, h]   embed_out [h, V]   final_norm [h]
    dense, routed: every entry stacked over that group's layers
      norm1 norm2 [L, h]   wq_a [L, h, rq]   q_norm [L, rq]   wq_b [L, rq, H, nope+rope]
      wkv_a [L, h, r+rope]   kv_norm [L, r]   wkv_b [L, r, H, nope+v]   wo [L, H, v, h]
    dense:   w_gate w_up [L, h, F]   w_down [L, F, h]
    routed:  router [L, h, E]   router_bias [L, E]
             w_gate w_up [L, E, h, f]   w_down [L, E, f, h]
             shared_gate shared_up [L, h, fs]   shared_down [L, fs, h]
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rotary(x, positions, theta):
    """[B, S, heads, d]: adjacent pairs rotate together."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]  # [S, d/2]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def attention(h, w, cfg):
    nope, rank, eps = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(h.shape[1])
    c_q = rms_norm(h @ w["wq_a"], w["q_norm"], eps)
    q = jnp.einsum("bsr,rnd->bsnd", c_q, w["wq_b"])
    kv = h @ w["wkv_a"]
    c_kv = rms_norm(kv[..., :rank], w["kv_norm"], eps)
    k_rope = rotary(kv[..., None, rank:], pos, cfg["rope_theta"])  # one head for all
    q_rope = rotary(q[..., nope:], pos, cfg["rope_theta"])
    up = jnp.einsum("bsr,rnd->bsnd", c_kv, w["wkv_b"])
    k_nope, v = up[..., :nope], up[..., nope:]
    scores = (jnp.einsum("bqnd,bknd->bnqk", q[..., :nope], k_nope)
              + jnp.einsum("bqnd,bkd->bnqk", q_rope, k_rope[:, :, 0])) / math.sqrt(q.shape[-1])
    scores = jnp.where((pos[:, None] >= pos[None, :])[None, None], scores, -jnp.inf)
    ctx = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqnd,ndh->bqh", ctx, w["wo"])


def glu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def experts(h, w, experts_w, cfg, picks):
    """[B, S, h] -> the routed and shared experts' output, and the shortfall
    [B, S] of ``picks`` (this router's own top-k where ``picks`` is None).
    ``experts_w``: the routed experts' three stacked leaves, not yet cast."""
    scores = jax.nn.sigmoid(h @ w["router"])  # [B, S, E]
    select = scores + w["router_bias"]  # what the top-k is taken over; the weights are not
    if picks is None:
        picks = jax.lax.top_k(select, cfg["num_experts_per_tok"])[1]
    chosen = jax.nn.one_hot(picks, scores.shape[-1], dtype=F32).sum(-2) > 0  # [B, S, E]
    gate = jnp.where(chosen, scores, 0.0)
    if cfg["norm_topk_prob"]:
        gate = gate / (gate.sum(-1, keepdims=True) + 1e-20)
    gate = gate * cfg["routed_scaling_factor"]

    def one(out, ew):  # one expert cast up at a time, every token through it
        g, (w_gate, w_up, w_down) = ew[0], (a.astype(F32) for a in ew[1:])
        return out + g[..., None] * glu(h, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (jnp.moveaxis(gate, -1, 0),) + tuple(experts_w))
    out = out + glu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
    best_left = jnp.where(chosen, -jnp.inf, select).max(-1)
    worst_taken = jnp.where(chosen, select, jnp.inf).min(-1)
    return out, (best_left - worst_taken) / select.std(-1)


def _cast(w, leave=()):
    return {k: a if k in leave else a.astype(F32) for k, a in w.items()}


def dense_layer(x, w, cfg):
    w = _cast(w)
    x = x + attention(rms_norm(x, w["norm1"], cfg["rms_norm_eps"]), w, cfg)
    return x + glu(rms_norm(x, w["norm2"], cfg["rms_norm_eps"]), w["w_gate"], w["w_up"], w["w_down"])


def routed_layer(x, w, cfg, picks):
    routed = tuple(w[k] for k in EXPERT_LEAVES)
    w = _cast({k: a for k, a in w.items() if k not in EXPERT_LEAVES})
    x = x + attention(rms_norm(x, w["norm1"], cfg["rms_norm_eps"]), w, cfg)
    out, shortfall = experts(rms_norm(x, w["norm2"], cfg["rms_norm_eps"]), w, routed, cfg, picks)
    return x + out, shortfall


def _run(weights, cfg, tokens, picks):
    with jax.default_matmul_precision("highest"):
        x = weights["embed_in"][tokens].astype(F32)
        x, _ = jax.lax.scan(lambda x, w: (dense_layer(x, w, cfg), None), x, weights["dense"])
        if picks is None:
            x, shortfall = jax.lax.scan(lambda x, w: routed_layer(x, w, cfg, None), x,
                                        weights["routed"])
        else:
            by_layer = jnp.moveaxis(jnp.asarray(picks), 2, 0)  # [L, B, S, k]
            x, shortfall = jax.lax.scan(lambda x, wp: routed_layer(x, wp[0], cfg, wp[1]), x,
                                        (weights["routed"], by_layer))
        x = rms_norm(x, weights["final_norm"].astype(F32), cfg["rms_norm_eps"])
        return x @ weights["embed_out"].astype(F32), jnp.moveaxis(shortfall, 0, -1)


def forward(weights, cfg, tokens, picks=None):
    """tokens [B, S] int -> logits [B, S, V] float32."""
    return _run(weights, cfg, tokens, picks)[0]


def route_shortfall(weights, cfg, tokens, picks):
    """float32 [B, S, routed layers]: along the pass pinned to ``picks``, the
    best selection score ``s + b`` among the experts NOT picked minus the
    worst among those picked, in units of that position's standard deviation
    of the selection score over the experts. Zero or less where the picks are
    this router's own top-k; positive by how far a pick is from one it could
    have made."""
    return _run(weights, cfg, tokens, picks)[1]
