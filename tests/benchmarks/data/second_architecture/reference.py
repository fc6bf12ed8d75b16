"""A Mixtral-shaped decoder (Jiang et al. 2024, "Mixtral of Experts", section
2, and the ``mixtral`` config keys), the plain way: RMSNorm, rotary over the
whole head (half-split pairing), grouped-query attention, a router that takes
the softmax over all experts, keeps the top ``num_experts_per_tok`` and
renormalises them, silu-GLU experts, no biases, sequential residual. Float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, every expert
computed for every token and weighted by its gate (zero where not chosen);
nothing imported from the system under test.

``tests/benchmarks/test_data_driven.py`` copies this file into a copy of
``benchmarks/`` as ``reference/mixtral.py``: the second architecture the
harness takes from new files alone. Weights come in as a plain dict:

    embed_in [V, h]   embed_out [h, V]   final_norm [h]
    layers: every entry stacked over the L layers
      norm1 norm2 [L, h]
      wq [L, h, H, d]   wk wv [L, h, K, d]   wo [L, H, d, h]
      router [L, h, E]
      w_gate w_up [L, E, h, f]   w_down [L, E, f, h]
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


def experts(h, w, top_k):
    """[B, S, h] -> [B, S, h]: the chosen experts' outputs, weighted by the
    router's renormalised probabilities."""
    probs = jax.nn.softmax(h @ w["router"], axis=-1)  # [B, S, E]
    kth = jnp.sort(probs, axis=-1)[..., -top_k][..., None]
    gate = jnp.where(probs >= kth, probs, 0.0)
    gate = gate / gate.sum(-1, keepdims=True)
    hidden = jax.nn.silu(jnp.einsum("bsh,ehf->bsef", h, w["w_gate"])) \
        * jnp.einsum("bsh,ehf->bsef", h, w["w_up"])
    return jnp.einsum("bse,bsef,efh->bsh", gate, hidden, w["w_down"])


def layer(x, w, cfg):
    w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
    eps, S = cfg["rms_norm_eps"], x.shape[1]
    d = w["wq"].shape[-1]
    groups = w["wq"].shape[-2] // w["wk"].shape[-2]
    pos = jnp.arange(S)

    h = rms_norm(x, w["norm1"], eps)
    q = rotary(jnp.einsum("bsh,hnd->bsnd", h, w["wq"]), pos, cfg["rope_theta"])
    k = rotary(jnp.einsum("bsh,hnd->bsnd", h, w["wk"]), pos, cfg["rope_theta"])
    v = jnp.einsum("bsh,hnd->bsnd", h, w["wv"])
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)  # a KV head serves a group
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(d)
    scores = jnp.where((pos[:, None] >= pos[None, :])[None, None], scores, -jnp.inf)
    ctx = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("bqnd,ndh->bqh", ctx, w["wo"])
    return x + experts(rms_norm(x, w["norm2"], eps), w, cfg["num_experts_per_tok"])


def forward(weights, cfg, tokens):
    """tokens [B, S] int -> logits [B, S, V] float32."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed_in"].astype(F32)[tokens]
        x, _ = jax.lax.scan(lambda x, w: (layer(x, w, cfg), None), x, weights["layers"])
        x = rms_norm(x, weights["final_norm"].astype(F32), cfg["rms_norm_eps"])
        return x @ weights["embed_out"].astype(F32)


def loss(weights, cfg, tokens):
    """Mean next-token cross-entropy, with no router auxiliary term."""
    logp = jax.nn.log_softmax(forward(weights, cfg, tokens)[:, :-1], axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()
