"""``xing4_0`` the plain way: a decoder whose residual is ``hc_mult`` streams a
token (manifold-constrained hyper-connections, mHC, arXiv:2512.24880, on the
hyper-connections of arXiv:2409.19606) around latent attention with YaRN
rotary, leading dense layers, then routed layers of ``n_routed_experts``
silu-GLU experts at top ``num_experts_per_tok`` beside a shared expert. Float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no cache, no
kernels, attention NOT absorbed; nothing imported from the system under test.

Per token, ``n = hc_mult``, ``C = hidden_size``, streams ``X`` ``[n, C]``:

- in: ``X_i = e`` for every ``i`` (the token's embedding row, copied);
- a sublayer ``F`` (attention or the feed-forward, each behind its RMSNorm)
  with its own ``phi`` ``[nC, n^2 + 2n]``, ``b`` ``[n^2 + 2n]``, ``alpha`` =
  (a_pre, a_post, a_res): ``xbar = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)``,
  ``m = xbar phi``; ``H_pre = sigmoid(a_pre m[0:n] + b[0:n])``; ``H_post = 2
  sigmoid(a_post m[n:2n] + b[n:2n])``; ``A = clip(a_res reshape(m[2n:], [n, n])
  + reshape(b[2n:], [n, n]), mhc_h_res_clamp_min, mhc_h_res_clamp_max)``, ``M
  = exp(A)``, then ``hc_sinkhorn_iters`` times ``M <- M / (rowsum(M) + hc_eps)``,
  ``M <- M / (colsum(M) + hc_eps)``; ``u = sum_i H_pre[i] X_i``; ``y = F(u)``;
  ``X'_i = sum_j M[i, j] X_j + H_post[i] y``;
- out: ``x = sum_i X_i``, the final norm, the untied head.

Attention is ``glm4_moe_lite``'s (``benchmarks/reference/glm4_moe_lite.py``:
``c_q = norm(h W_qa)``, ``[q_nope | q_rope] = c_q W_qb`` a head, ``[c_kv | k_r]
= h W_kva``, one rotary key for all heads, ``[k_nope | v] = norm(c_kv) W_kvb``)
with YaRN as the DeepSeek-V2/V3 modelling code has it: ``f_i = theta^(-2i/d)``
over ``d = qk_rope_head_dim``, ``corr(r) = d ln(L0 / (2 pi r)) / (2 ln theta)``,
``low = max(floor(corr(beta_fast)), 0)``, ``high = min(ceil(corr(beta_slow)),
d - 1)``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``, pair ``i`` turns by
``(f_i / factor) ramp_i + f_i (1 - ramp_i)`` a position; cos and sin times
``ym(mscale) / ym(mscale_all_dim)``, the softmax scale ``(nope + rope)^-0.5
ym(mscale_all_dim)^2``, ``ym(s) = 0.1 s ln(factor) + 1``. Rotary pairs are
ADJACENT columns. The router and the experts are that file's too: ``s =
sigmoid(h W_r)``, picks ``top_k(s + b)``, weights ``routed_scaling_factor s_e /
(sum of the picked s + 1e-20)``. The next-token-prediction layer is not built.

``forward(weights, cfg, tokens, picks=None)`` and ``route_shortfall`` as a
routed reference has them (PERF.md, section 7).

**In blocks, so that it fits beside the engine.** The weights come in as the
program's own arrays relabelled, bf16 at the size of the benchmark's cell, 9.8
GB of them, and the check's sequences are 2,058 tokens: the layers are cast up
one at a time and within a routed one an expert at a time (``lax.scan`` over
the stacked leaves), the sequences go through ONE ROW AT A TIME (a row's scores
are 0.54 GB, its logits over 131,072 words 1.08 GB), and each row's logits are
handed to the HOST's memory as they are made: ``[4, 2058, 131072]`` float32 is
4.3 GB, which no chip that holds the engine has room for. The head's product
takes the bf16 matrix as it is (a float32 copy would be 1.9 GB), the
activations in float32.

    embed_in [V, h]   embed_out [h, V]   final_norm [h]
    dense, routed: every entry stacked over that group's layers
      norm1 norm2 [L, h]   wq_a [L, h, rq]   q_norm [L, rq]   wq_b [L, rq, H, nope+rope]
      wkv_a [L, h, r+rope]   kv_norm [L, r]   wkv_b [L, r, H, nope+v]   wo [L, H, v, h]
      hc_attn_phi hc_mlp_phi [L, n*h, n^2+2n]   hc_attn_b hc_mlp_b [L, n^2+2n]
      hc_attn_alpha hc_mlp_alpha [L, 3]
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


def yarn(cfg):
    """(frequencies [d/2], what cos and sin are multiplied by, the softmax scale)."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    scale = 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + d)
    sc = cfg.get("rope_scaling")
    if not sc:
        return freq, 1.0, scale
    factor, original = sc["factor"], sc["original_max_position_embeddings"]

    def corr(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(corr(sc["beta_fast"])), 0), min(math.ceil(corr(sc["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / max(high - low, 0.001), 0.0, 1.0)

    def ym(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0

    return (freq / factor * ramp + freq * (1 - ramp), ym(sc["mscale"]) / ym(sc["mscale_all_dim"]),
            scale * ym(sc["mscale_all_dim"]) ** 2)


def rotary(x, positions, freq, amplitude):
    """[S, heads, d]: adjacent pairs rotate together."""
    angles = positions.astype(F32)[:, None] * freq[None, :]  # [S, d/2]
    cos, sin = amplitude * jnp.cos(angles)[:, None, :], amplitude * jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def attention(h, w, cfg):
    """h [S, hidden], one sequence from position 0."""
    nope, rank, eps = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(h.shape[0])
    freq, amplitude, scale = yarn(cfg)
    c_q = rms_norm(h @ w["wq_a"], w["q_norm"], eps)
    q = jnp.einsum("sr,rnd->snd", c_q, w["wq_b"])
    kv = h @ w["wkv_a"]
    c_kv = rms_norm(kv[..., :rank], w["kv_norm"], eps)
    k_rope = rotary(kv[:, None, rank:], pos, freq, amplitude)[:, 0]  # one head for all
    q_rope = rotary(q[..., nope:], pos, freq, amplitude)
    up = jnp.einsum("sr,rnd->snd", c_kv, w["wkv_b"])
    k_nope, v = up[..., :nope], up[..., nope:]
    scores = (jnp.einsum("qnd,knd->nqk", q[..., :nope], k_nope)
              + jnp.einsum("qnd,kd->nqk", q_rope, k_rope)) * scale
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    ctx = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("qnd,ndh->qh", ctx, w["wo"])


def glu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def experts(h, w, experts_w, cfg, picks):
    """[S, h] -> the routed and shared experts' output, and the shortfall [S]
    of ``picks`` [S, k] (this router's own top-k where ``picks`` is None).
    ``experts_w``: the routed experts' three stacked leaves, not yet cast."""
    scores = jax.nn.sigmoid(h @ w["router"])  # [S, E]
    select = scores + w["router_bias"]  # what the top-k is taken over; the weights are not
    if picks is None:
        picks = jax.lax.top_k(select, cfg["num_experts_per_tok"])[1]
    chosen = jax.nn.one_hot(picks, scores.shape[-1], dtype=F32).sum(-2) > 0  # [S, E]
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


def hyper_connection(X, phi, b, alpha, cfg):
    """X [S, n, C] -> (H_pre [S, n], H_post [S, n], H_res [S, n, n])."""
    S, n, C = X.shape
    flat = X.reshape(S, n * C)
    m = (flat / jnp.sqrt((flat * flat).mean(-1, keepdims=True) + cfg["rms_norm_eps"])) @ phi
    h_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    a = alpha[2] * m[:, 2 * n:].reshape(S, n, n) + b[2 * n:].reshape(n, n)
    mat = jnp.exp(jnp.clip(a, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"]):
        mat = mat / (mat.sum(-1, keepdims=True) + cfg["hc_eps"])  # rows
        mat = mat / (mat.sum(-2, keepdims=True) + cfg["hc_eps"])  # columns
    return h_pre, h_post, mat


def sublayer(X, w, which, cfg, f):
    """The streams through one sublayer ``f`` ([S, C] -> ([S, C], anything))."""
    h_pre, h_post, h_res = hyper_connection(X, w[f"hc_{which}_phi"], w[f"hc_{which}_b"],
                                            w[f"hc_{which}_alpha"], cfg)
    y, other = f(jnp.einsum("sn,snc->sc", h_pre, X))
    return jnp.einsum("sij,sjc->sic", h_res, X) + h_post[:, :, None] * y[:, None, :], other


def _cast(w, leave=()):
    return {k: a if k in leave else a.astype(F32) for k, a in w.items()}


def _attend(X, w, cfg):
    return sublayer(X, w, "attn", cfg,
                    lambda u: (attention(rms_norm(u, w["norm1"], cfg["rms_norm_eps"]), w, cfg), None))[0]


def dense_layer(X, w, cfg):
    w = _cast(w)
    X = _attend(X, w, cfg)
    return sublayer(X, w, "mlp", cfg, lambda u: (glu(rms_norm(u, w["norm2"], cfg["rms_norm_eps"]),
                                                     w["w_gate"], w["w_up"], w["w_down"]), None))[0]


def routed_layer(X, w, cfg, picks):
    routed = tuple(w[k] for k in EXPERT_LEAVES)
    w = _cast({k: a for k, a in w.items() if k not in EXPERT_LEAVES})
    X = _attend(X, w, cfg)
    return sublayer(X, w, "mlp", cfg, lambda u: experts(rms_norm(u, w["norm2"], cfg["rms_norm_eps"]),
                                                        w, routed, cfg, picks))


def _row(weights, cfg, tokens, picks):
    """One sequence [S] (picks [S, L, k] or None) -> (logits [S, V] in the host's memory, shortfall [S, L])."""
    e = weights["embed_in"][tokens].astype(F32)
    X = jnp.broadcast_to(e[:, None, :], (e.shape[0], cfg["hc_mult"], e.shape[1]))
    X, _ = jax.lax.scan(lambda X, w: (dense_layer(X, w, cfg), None), X, weights["dense"])
    if picks is None:
        X, shortfall = jax.lax.scan(lambda X, w: routed_layer(X, w, cfg, None), X, weights["routed"])
    else:
        X, shortfall = jax.lax.scan(lambda X, wp: routed_layer(X, wp[0], cfg, wp[1]), X,
                                    (weights["routed"], jnp.moveaxis(picks, 1, 0)))
    x = rms_norm(X.sum(1), weights["final_norm"].astype(F32), cfg["rms_norm_eps"])
    # the bf16 head as it is: float32 activations, every pass, float32 sums
    logits = jax.lax.dot_general(x, weights["embed_out"], (((1,), (0,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32)
    return jax.device_put(logits, jax.memory.Space.Host), jnp.moveaxis(shortfall, 0, -1)


_asked = []  # what the newest traced pass was asked of, and what it gave


def _run(weights, cfg, tokens, picks):
    """(logits, shortfall) of one pass. ``forward`` and ``route_shortfall`` asked
    of the SAME traced arrays inside one jitted function, as the benchmark's
    check asks them, share it: XLA does not merge the two scans (compiled for
    the v5e the check's program held eight loops, the four of a pass twice: PR 39)."""
    asked = (weights, cfg, tokens, picks)
    if isinstance(tokens, jax.core.Tracer) and _asked and all(a is b for a, b in zip(_asked[0], asked)):
        return _asked[1]
    with jax.default_matmul_precision("highest"):
        rows = jnp.asarray(tokens)
        if picks is None:
            out = jax.lax.scan(lambda _, t: (None, _row(weights, cfg, t, None)), None, rows)[1]
        else:
            out = jax.lax.scan(lambda _, tp: (None, _row(weights, cfg, *tp)), None,
                               (rows, jnp.asarray(picks)))[1]
    _asked[:] = [asked, out] if isinstance(tokens, jax.core.Tracer) else []
    return out


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
