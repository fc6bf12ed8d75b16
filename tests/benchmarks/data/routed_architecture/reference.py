"""A routed decoder, the plain way, as the contract for a ROUTED architecture's
reference has it (PERF.md, section 7): one leading dense layer, then layers of
``n_routed_experts`` silu-GLU experts at top ``num_experts_per_tok`` beside one
shared expert. The router is the published ``noaux_tc`` one: sigmoid scores, a
per-expert correction bias added for the CHOICE only, the chosen scores
renormalised (``norm_topk_prob``) and scaled by ``routed_scaling_factor``.
RMSNorm, rotary over the whole head (half-split pairing), full multi-head
attention, no biases, sequential residual. Float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, every expert computed for every
token and weighted by its gate (zero where not chosen); nothing imported from
the system under test or from its stand-in.

``forward(weights, cfg, tokens, picks=None)``: with ``picks`` ``[B, S, routed
layers, k]`` every position goes to exactly those experts, weighted from this
file's own fp32 scores over them; with ``None`` the choice is this file's own
top-k. ``route_shortfall`` says, along the same pinned pass, how far the picks
are from ones this router could have made. Weights come in as a plain dict:

    embed_in [V, h]   embed_out [h, V]   final_norm [h]
    dense, routed: every entry stacked over that group's layers
      norm1 norm2 [L, h]   wq wk wv [L, h, H, d]   wo [L, H, d, h]
    dense:   w_gate w_up [L, h, F]   w_down [L, F, h]
    routed:  router [L, h, E]   router_bias [L, E]
             w_gate w_up [L, E, h, f]   w_down [L, E, f, h]
             shared_gate shared_up [L, h, f]   shared_down [L, f, h]
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rotary(x, positions, theta):
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]  # [S, d/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rotated * sin


def attention(h, w, cfg):
    pos = jnp.arange(h.shape[1])
    q = rotary(jnp.einsum("bsh,hnd->bsnd", h, w["wq"]), pos, cfg["rope_theta"])
    k = rotary(jnp.einsum("bsh,hnd->bsnd", h, w["wk"]), pos, cfg["rope_theta"])
    v = jnp.einsum("bsh,hnd->bsnd", h, w["wv"])
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where((pos[:, None] >= pos[None, :])[None, None], scores, -jnp.inf)
    ctx = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqnd,ndh->bqh", ctx, w["wo"])


def glu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def experts(h, w, cfg, picks):
    """[B, S, h] -> the routed and shared experts' output, and the shortfall
    [B, S] of ``picks`` (this router's own top-k where ``picks`` is None)."""
    scores = jax.nn.sigmoid(h @ w["router"])  # [B, S, E]
    select = scores + w["router_bias"]  # what the top-k is taken over; the weights are not
    if picks is None:
        picks = jax.lax.top_k(select, cfg["num_experts_per_tok"])[1]
    chosen = jax.nn.one_hot(picks, scores.shape[-1], dtype=F32).sum(-2) > 0  # [B, S, E]
    gate = jnp.where(chosen, scores, 0.0)
    if cfg["norm_topk_prob"]:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * cfg["routed_scaling_factor"]
    hidden = jax.nn.silu(jnp.einsum("bsh,ehf->bsef", h, w["w_gate"])) \
        * jnp.einsum("bsh,ehf->bsef", h, w["w_up"])
    out = jnp.einsum("bse,bsef,efh->bsh", gate, hidden, w["w_down"])
    out = out + glu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
    best_left = jnp.where(chosen, -jnp.inf, select).max(-1)
    worst_taken = jnp.where(chosen, select, jnp.inf).min(-1)
    return out, (best_left - worst_taken) / select.std(-1)


def dense_layer(x, w, cfg):
    w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
    x = x + attention(rms_norm(x, w["norm1"], cfg["rms_norm_eps"]), w, cfg)
    return x + glu(rms_norm(x, w["norm2"], cfg["rms_norm_eps"]), w["w_gate"], w["w_up"], w["w_down"])


def routed_layer(x, w, cfg, picks):
    w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
    x = x + attention(rms_norm(x, w["norm1"], cfg["rms_norm_eps"]), w, cfg)
    out, shortfall = experts(rms_norm(x, w["norm2"], cfg["rms_norm_eps"]), w, cfg, picks)
    return x + out, shortfall


def _run(weights, cfg, tokens, picks):
    with jax.default_matmul_precision("highest"):
        x = weights["embed_in"].astype(F32)[tokens]
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
    best selection score among the experts NOT picked minus the worst among
    those picked, in units of that position's standard deviation of the
    selection score over the experts. Zero or less where the picks are this
    router's own top-k; positive by how far a pick is from one it could have
    made."""
    return _run(weights, cfg, tokens, picks)[1]
