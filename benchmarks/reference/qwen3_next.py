"""``qwen3_next`` the plain way: a decoder whose layers follow a PATTERN
(``full_attention_interval``: three Gated DeltaNet layers, then one gated
softmax-attention layer), every layer followed by a ROUTED feed-forward layer.
Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
cache, no kernels, no chunks: a DeltaNet layer is the SEQUENTIAL recurrence, one
token after another (a ``lax.scan`` over the tokens), so the program's chunked
form is checked against another algorithm; nothing imported from the system
under test.

With ``h`` the residual stream [S, hidden] and every norm ``rmsnorm(x) (1 + w)``
at ``rms_norm_eps`` but the DeltaNet's gated one, which multiplies by ``w``:
``h = E[token]``; a layer ``h = h + mixer(norm(h))``, then ``h = h +
routed(norm(h))``; ``logits = norm(h) W_head`` (untied).

- a *Gated DeltaNet* layer (``Hk`` = ``linear_num_key_heads`` heads of ``Dk`` =
  ``linear_key_head_dim``, ``Hv`` = ``linear_num_value_heads`` of ``Dv`` =
  ``linear_value_head_dim``, ``K`` = ``linear_conv_kernel_dim``): ``[q | k | v |
  z] = u W_qkvz`` (``Hk Dk | Hk Dk | Hv Dv | Hv Dv``), ``[b | a] = u W_ba``
  (``Hv`` each); ``[q | k | v]_t <- silu(sum_j w_j [q | k | v]_{t - K + 1 + j})``
  a channel, no bias, zeros before the sequence (``z``, ``b``, ``a`` do not pass
  through it); a value head ``h`` (key head ``h // (Hv / Hk)``): ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; ``q``, ``k``
  L2-normalised over ``Dk`` (``x / sqrt(sum x^2 + 1e-6)``), ``q`` times
  ``Dk^-0.5``; from ``S = 0`` [Dk, Dv], token by token, ``S <- exp(g_t) S``, ``d
  = beta_t (v_t - S^T k_t)``, ``S <- S + k_t d^T``, ``o_t = S^T q_t``; ``out =
  (rmsnorm_w(o_t) * silu(z_t)) W_out``, the norm a head over its ``Dv``.
  DEPARTURE from the published code: the checkpoint's ``in_proj_qkvz`` and
  ``in_proj_ba`` interleave their columns by key-head group (a group's q, k,
  its value heads' v and z together); here, as in the program, they are plain
  ``[q | k | v | z]`` and ``[b | a]`` (``checkpoint/hf.py``'s key map says so).
- a *gated attention* layer: ``[q | gate] = u W_q`` a head (``2 x head_dim``),
  ``k``, ``v`` over ``num_key_value_heads``; ``q``, ``k`` through a per-head
  ``rmsnorm (1 + w)``; rotary (halves rotate together) on the first
  ``partial_rotary_factor x head_dim`` dimensions, ``rope_theta``, no scaling;
  causal softmax of ``q . k head_dim^-0.5``; ``(attention * sigmoid(gate)) W_o``.
- the *routed* layer: ``p = softmax(u W_r)`` over ALL the deployment's experts
  (``W_r``'s columns); the picks are the ``num_experts_per_tok`` largest, their
  weights divided by their sum (``norm_topk_prob``); ``y = sum_e w_e GLU_e(u) +
  sigmoid(u w_sg) GLU_shared(u)``. **The share**: the weights hold the
  ``num_experts`` experts of ONE chip of ``expert_parallel.size`` (rank
  ``expert_parallel.rank``: experts ``rank x num_experts ...`` of the router's
  numbering); the sum runs over the picks that are among them, with the
  weights still renormalised over ALL the picks; what the other chips' experts
  would have added is left out, and that partial result goes on to the next
  layer. Without ``expert_parallel`` every expert is here.
- the multi-token-prediction layer of the checkpoints is not built.

``forward(weights, cfg, tokens, picks=None)``: with ``picks`` ``[B, S, layers,
k]`` (the router's numbering) every position goes to exactly those experts,
weighted from this file's own fp32 probabilities over them; with ``None`` the
choice is this file's own top-k. ``route_shortfall`` says, along the same
pinned pass, how far the picks are from ones this router could have made, on
the router's logits, in units of their standard deviation over the experts.

The weights come in as the program's own arrays relabelled, bf16 at the size of
the benchmark's cell; they are cast up one layer, and within it one expert, at
a time. The layers are scanned a period a step, the sequences one after another:

    embed [V, h]   head [h, V]   final_norm [h]
    period: one entry a layer of ONE period, its leaves stacked over the periods
      DeltaNet:  norm1 [n, h]   w_qkvz [n, h, 2 Hk Dk + 2 Hv Dv]   w_ba [n, h, 2 Hv]   conv_w [n, K, X]
                 A_log dt_bias [n, Hv]   norm_w [n, Dv]   w_out [n, Hv Dv, h]
      attention: norm1 [n, h]   wq [n, h, H, 2 d]   wk wv [n, h, Hkv, d]   q_norm k_norm [n, d]   wo [n, H, d, h]
      both:      norm2 [n, h]   router [n, h, E_all]   w_gate w_up [n, E, h, f]   w_down [n, E, f, h]
                 shared_gate shared_up [n, h, fs]   shared_down [n, fs, h]   shared_w [n, h, 1]
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
MASKED = -1e30
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def layer_kinds(cfg):
    kinds = cfg.get("layer_types")
    if kinds is None:
        n = cfg.get("full_attention_interval", 4)
        kinds = ["full_attention" if (i + 1) % n == 0 else "linear_attention"
                 for i in range(cfg["num_hidden_layers"])]
    return tuple(kinds)


def period_of(kinds):
    """The shortest run of kinds that the pattern repeats whole."""
    L = len(kinds)
    return next(kinds[:p] for p in range(1, L + 1) if L % p == 0 and kinds == kinds[:p] * (L // p))


def rms_norm(x, w, eps):
    """``rmsnorm(x) (1 + w)``."""
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + w)


def rotary(x, theta, dims):
    """[S, heads, d]: the first ``dims`` dimensions rotate, halves together."""
    S = x.shape[0]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dims, 2, dtype=F32) / dims))
    angles = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :dims // 2], x[..., dims // 2:dims]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., dims:]], axis=-1)


def attention(u, w, cfg):
    S = u.shape[0]
    H, Hkv, d, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    qg = jnp.einsum("se,ehd->shd", u, w["wq"])
    q, gate = qg[..., :d], qg[..., d:]
    k, v = jnp.einsum("se,ehd->shd", u, w["wk"]), jnp.einsum("se,ehd->shd", u, w["wv"])
    dims = int(cfg["partial_rotary_factor"] * d)
    q = rotary(rms_norm(q, w["q_norm"], eps), cfg["rope_theta"], dims)
    k = rotary(rms_norm(k, w["k_norm"], eps), cfg["rope_theta"], dims)
    k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) * d ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, MASKED), axis=-1)
    ctx = jnp.einsum("hst,thd->shd", probs, v) * jax.nn.sigmoid(gate)
    return jnp.einsum("shd,hde->se", ctx, w["wo"])


def delta_net(u, w, cfg):
    S = u.shape[0]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    Dk, Dv, K = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
    X = 2 * Hk * Dk + Hv * Dv
    qkvz, ba = u @ w["w_qkvz"], u @ w["w_ba"]
    qkv, z = qkvz[:, :X], qkvz[:, X:].reshape(S, Hv, Dv)
    before = jnp.concatenate([jnp.zeros((K - 1, X), F32), qkv])
    qkv = jax.nn.silu(sum(w["conv_w"][j] * before[j:j + S] for j in range(K)))
    q = qkv[:, :Hk * Dk].reshape(S, Hk, Dk)
    k = qkv[:, Hk * Dk:2 * Hk * Dk].reshape(S, Hk, Dk)
    v = qkv[:, 2 * Hk * Dk:].reshape(S, Hv, Dv)

    def unit(x):
        return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(q) * Dk ** -0.5, Hv // Hk, axis=1)  # value head h reads key head h // (Hv / Hk)
    k = jnp.repeat(unit(k), Hv // Hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, Hv:] + w["dt_bias"])

    def token(state, t):
        q_t, k_t, v_t, g_t, beta_t = t
        state = jnp.exp(g_t)[:, None, None] * state
        d = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((Hv, Dk, Dv), F32), (q, k, v, g, beta))
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + cfg["rms_norm_eps"]) * w["norm_w"]
    return (o * jax.nn.silu(z)).reshape(S, Hv * Dv) @ w["w_out"]


def glu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def held(cfg, experts_all: int):
    """(first, count) of the router's numbering that the weights hold."""
    count = cfg["num_experts"]
    share = cfg.get("expert_parallel")
    if not share:
        return 0, experts_all
    return int(share.get("rank", 0)) * count, count


def routed(u, w, experts_w, cfg, picks):
    """[S, h] -> the held routed experts' and the shared expert's output, and
    the shortfall [S] of ``picks`` (this router's own top-k where ``picks`` is
    None). ``experts_w``: the held experts' three stacked leaves, not yet cast."""
    logits = u @ w["router"]  # [S, E_all]
    probs = jax.nn.softmax(logits, axis=-1)
    if picks is None:
        picks = jax.lax.top_k(probs, cfg["num_experts_per_tok"])[1]
    chosen = jax.nn.one_hot(picks, logits.shape[-1], dtype=F32).sum(-2) > 0  # [S, E_all]
    gate = jnp.where(chosen, probs, 0.0)
    if cfg.get("norm_topk_prob", True):
        gate = gate / gate.sum(-1, keepdims=True)  # over ALL the picks, wherever their experts live
    first, count = held(cfg, logits.shape[-1])
    gate = gate[:, first:first + count]

    def one(out, ew):  # one expert cast up at a time, every token through it
        g, (w_gate, w_up, w_down) = ew[0], (a.astype(F32) for a in ew[1:])
        return out + g[:, None] * glu(u, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (gate.T,) + tuple(experts_w))
    out = out + jax.nn.sigmoid(u @ w["shared_w"]) * glu(u, w["shared_gate"], w["shared_up"], w["shared_down"])
    best_left = jnp.where(chosen, -jnp.inf, logits).max(-1)
    worst_taken = jnp.where(chosen, logits, jnp.inf).min(-1)
    return out, (best_left - worst_taken) / logits.std(-1)


MIXERS = {"full_attention": attention, "attention": attention, "linear_attention": delta_net}


def layer(h, w, kind, cfg, picks):
    eps = cfg["rms_norm_eps"]
    experts_w = tuple(w[k] for k in EXPERT_LEAVES)
    w = {k: a.astype(F32) for k, a in w.items() if k not in EXPERT_LEAVES}
    h = h + MIXERS[kind](rms_norm(h, w["norm1"], eps), w, cfg)
    out, shortfall = routed(rms_norm(h, w["norm2"], eps), w, experts_w, cfg, picks)
    return h + out, shortfall


def _row(weights, cfg, tokens, picks):
    kinds = period_of(layer_kinds(cfg))
    P = len(kinds)
    h = jnp.take(weights["embed"], tokens, axis=0).astype(F32)
    # picks [S, layers, k] -> [periods, P, S, k]
    by_layer = None if picks is None else jnp.moveaxis(picks, 1, 0).reshape((-1, P) + (picks.shape[0], picks.shape[2]))

    def period(h, xs):
        w, p = xs if picks is not None else (xs, None)
        short = []
        for j, kind in enumerate(kinds):
            h, s = layer(h, w[j], kind, cfg, None if p is None else p[j])
            short.append(s)
        return h, jnp.stack(short)

    h, shortfall = jax.lax.scan(period, h, weights["period"] if picks is None else (weights["period"], by_layer))
    x = rms_norm(h, weights["final_norm"].astype(F32), cfg["rms_norm_eps"])
    return x @ weights["head"].astype(F32), shortfall.reshape(-1, shortfall.shape[-1]).T  # [S, V], [S, layers]


def _run(weights, cfg, tokens, picks):
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        if picks is None:
            return jax.lax.map(lambda t: _row(weights, cfg, t, None), tokens)
        return jax.lax.map(lambda tp: _row(weights, cfg, tp[0], tp[1]), (tokens, jnp.asarray(picks)))


def forward(weights, cfg, tokens, picks=None):
    """tokens [B, S] int -> logits [B, S, V] float32."""
    return _run(weights, cfg, tokens, picks)[0]


def route_shortfall(weights, cfg, tokens, picks):
    """float32 [B, S, layers]: along the pass pinned to ``picks``, the best
    router logit among the experts NOT picked minus the worst among those
    picked, in units of the logits' standard deviation over the experts at that
    position. Zero or less where the picks are this
    router's own top-k; positive by how far a pick is from one it could have
    made. (The softmax is monotone: the logits rank as the probabilities the
    published router takes its top-k of.)"""
    return _run(weights, cfg, tokens, picks)[1]
