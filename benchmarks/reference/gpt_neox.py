"""GPT-NeoX (Pythia) forward pass and loss, the plain way.

Written from the published description (Black et al. 2022, "GPT-NeoX-20B",
section 2, and the ``gpt_neox`` config keys) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks, and nothing imported from the system under test. The
benchmark's ``correct`` compares the system's logits and losses with these.

Weights come in as a plain dict (any float dtype; upcast here):

    embed_in   [V, h]                         final_ln_scale, final_ln_bias [h]
    embed_out  [h, V]
    layers: every entry stacked over the L layers
      ln1_scale ln1_bias ln2_scale ln2_bias   [L, h]
      wq wk wv  [L, h, H, d]   bq bk bv [L, H, d]
      wo        [L, H, d, h]   bo       [L, h]
      w_in      [L, h, f]      b_in     [L, f]
      w_out     [L, f, h]      b_out    [L, h]

Departures from the published checkpoint layout, neither of which changes the
mathematics: the fused ``query_key_value`` matrix is given as its three parts,
one slice per head, and the layers are visited by ``lax.scan`` so that the
program compiles once per layer shape instead of L times.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def rotary(x, positions, rotary_dim, base):
    """Rotate the first ``rotary_dim`` of each head, NeoX half-split pairing
    (dimension i pairs with i + rotary_dim/2); the rest passes through."""
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    inv_freq = 1.0 / (base ** (jnp.arange(0, rotary_dim, 2, dtype=F32) / rotary_dim))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]  # [S, rd/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    half = rotary_dim // 2
    rotated = jnp.concatenate([-rot[..., half:], rot[..., :half]], -1)
    return jnp.concatenate([rot * cos + rotated * sin, rest], -1)


def layer(x, w, cfg):
    """One block: x + attention(ln1(x)) + mlp(ln2(x)) when the residual is
    parallel (every Pythia), the sequential form otherwise."""
    w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
    eps = cfg["layer_norm_eps"]
    S = x.shape[1]
    d = w["wq"].shape[-1]
    rd = int(cfg["rotary_pct"] * d)
    pos = jnp.arange(S)

    def attention(h):
        q = jnp.einsum("bsh,hnd->bsnd", h, w["wq"]) + w["bq"]
        k = jnp.einsum("bsh,hnd->bsnd", h, w["wk"]) + w["bk"]
        v = jnp.einsum("bsh,hnd->bsnd", h, w["wv"]) + w["bv"]
        q = rotary(q, pos, rd, cfg["rotary_emb_base"])
        k = rotary(k, pos, rd, cfg["rotary_emb_base"])
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(d)
        causal = pos[:, None] >= pos[None, :]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v)
        return jnp.einsum("bqnd,ndh->bqh", ctx, w["wo"]) + w["bo"]

    def mlp(h):
        act = {"gelu": lambda a: jax.nn.gelu(a, approximate=False),
               "gelu_new": lambda a: jax.nn.gelu(a, approximate=True),
               "relu": jax.nn.relu}[cfg["hidden_act"]]
        return act(h @ w["w_in"] + w["b_in"]) @ w["w_out"] + w["b_out"]

    a = attention(layer_norm(x, w["ln1_scale"], w["ln1_bias"], eps))
    if cfg.get("use_parallel_residual", True):
        return x + a + mlp(layer_norm(x, w["ln2_scale"], w["ln2_bias"], eps))
    x = x + a
    return x + mlp(layer_norm(x, w["ln2_scale"], w["ln2_bias"], eps))


def forward(weights, cfg, tokens):
    """tokens [B, S] int -> logits [B, S, V] float32."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed_in"].astype(F32)[tokens]
        x, _ = jax.lax.scan(lambda x, w: (layer(x, w, cfg), None), x, weights["layers"])
        x = layer_norm(x, weights["final_ln_scale"].astype(F32),
                       weights["final_ln_bias"].astype(F32), cfg["layer_norm_eps"])
        return x @ weights["embed_out"].astype(F32)


def loss(weights, cfg, tokens):
    """Mean next-token cross-entropy over every position that has a next
    token, one sequence at a time so that [S, V] logits are all that is held."""
    def one(seq):
        logits = forward(weights, cfg, seq[None])[0, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, seq[1:, None], axis=-1).sum()

    total = jax.lax.map(one, tokens).sum()
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))
