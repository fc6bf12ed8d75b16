"""``mimo_v2`` (MiMo-V2.5's language model) the plain way: a decoder whose
layers follow a PATTERN of two attention kinds that differ in more than the
band (``hybrid_layer_pattern``: 0 a *global* layer, 1 a *sliding* one), a dense
MLP in the leading layers ``moe_layer_freq`` marks 0 and a routed MLP in the
others. Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``;
no cache, no ring of pages, no kernels; nothing imported from the system under
test.

With ``x`` the residual stream [S, hidden] and ``RMS(x) = x / sqrt(mean(x^2) +
layernorm_epsilon) * g``, a layer is sequential and pre-norm: ``a = x +
Attn(RMS_1(x))``, ``x' = a + MLP(RMS_2(a))``; a final RMSNorm and an untied head.

- a *global* layer: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key-value heads, queries and keys of ``head_dim``,
  values of ``v_head_dim``, rotary base ``rope_theta``, no sink; a query at
  ``t`` sees every key ``j <= t``.
- a *sliding* layer: ``swa_num_key_value_heads`` key-value heads, ``swa_head_dim``
  and ``swa_v_head_dim``, rotary base ``swa_rope_theta``; a query at ``t`` sees
  the keys ``j`` with ``0 <= t - j < sliding_window`` (its own counted); a
  learned logit ``s_h`` a query head, the SINK, joins the softmax's denominator
  and nothing else: ``p_tj = exp(a_tj) / (exp(s_h) + sum_k exp(a_tk))``.
- in both: ``q = rope(h Wq)``, ``k = rope(h Wk)``, ``v = attention_value_scale *
  (h Wv)``; no bias, no qk-norm; query head ``i`` reads key-value head ``i //
  (heads / kv heads)``; rotary over the FIRST ``int(partial_rotary_factor *
  head_dim)`` columns of a head (64 of 192), rotated in halves (column ``i``
  with column ``i + 32``), the rest pass; scores times ``head_dim ** -0.5``;
  ``out = concat(heads) Wo``, ``Wo`` ``[heads x v_head_dim, hidden]``.
- the dense MLP: ``(silu(h G) * (h U)) D`` of width ``intermediate_size``.
- the routed MLP: ``s = sigmoid(h Wr)`` over ALL the deployment's experts
  (``Wr``'s columns) in float32; the picks are the ``num_experts_per_tok``
  largest of ``s + b``, ``b`` a per-expert correction bias (``noaux_tc``, one
  group); ``w_e = s_e / sum of the picked s`` (``norm_topk_prob``: the bias
  chooses and does not weigh); no routed scale, NO SHARED EXPERT: ``y = sum over
  the picks of w_e (silu(h G_e) * (h U_e)) D_e``.

Departures from the published description, each the configuration file's
``assumed`` too: the block's order and the norms' places, the 0 / 1 reading of
``hybrid_layer_pattern``, the window counting the query's own key
(``attention_chunk_size`` read as the same window), the sink's shape (one a
query head), which 64 columns rotate and how, are READINGS of keys that do not
settle them; the vision tower, the audio encoder and the three
multi-token-prediction layers are not built (the catalog's config has no key
of theirs); the weights are seeded random, not the checkpoint's.

ONE CHIP'S SHARE (``expert_parallel: {size, rank}`` in the configuration): the
weights hold ``n_routed_experts`` experts, numbers ``rank * n_routed_experts
...`` of the router's numbering; the routed sum runs over the picks that are
among them, with the weights still renormalised over ALL the picks; what the
other chips' experts would have added is left out (a token none of whose picks
is held here leaves the routed layer with its residual alone), and that partial
result goes on to the next layer. Without ``expert_parallel`` every expert is
here. The vocabulary is the chip's slice, as the configuration says.

``forward(weights, cfg, tokens, picks=None)``: with ``picks`` ``[B, S, routed
layers, k]`` (the router's numbering; the leading dense layers not counted)
every position goes to exactly those experts, weighted from this file's own
fp32 scores over them; with ``None`` the choice is this file's own top-k.
``route_shortfall`` says, along the same pinned pass, how far the picks are from
ones this router could have made, on what it takes its top-k of (``s + b``), in
units of that quantity's standard deviation over the experts.

The weights come in as the program's own arrays relabelled, bf16 at the size of
the benchmark's cell; they are cast up a layer, and within it an expert and a
key-value head's group, at a time. Attention is computed in blocks of
``ATTENTION_BLOCK`` queries of one key-value head's group against the keys the
block's queries can see; the sequences one after another; so that it fits
beside the program on the chip. The leading layers one by one, then the routed
layers scanned a period a step:

    embed [V, h]   head [h, V]   final_norm [h]
    dense: one entry a leading layer
      norm1 norm2 [h]   wq [h, H, d]   wk [h, Hkv, d]   wv [h, Hkv, dv]   wo [H, dv, h]   (sink [H])
      w_gate w_up [h, F]   w_down [F, h]
    period: one entry a layer of ONE period, its leaves stacked over the periods
      norm1 norm2 [n, h]   wq wk wv wo as above with a leading n   (sink [n, H])
      router [n, h, E_all]   router_bias [n, E_all]   w_gate w_up [n, E, h, f]   w_down [n, E, f, h]
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
MASKED = -1e30
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
LARGE_LEAVES = ("wq", "wk", "wv", "wo") + EXPERT_LEAVES  # cast up where they are used
ATTENTION_BLOCK = 256


def layer_kinds(cfg):
    return tuple("sliding" if kind else "global" for kind in cfg["hybrid_layer_pattern"])


def dense_layers(cfg) -> int:
    """The leading layers with a dense MLP: ``moe_layer_freq``'s leading zeros."""
    freq = list(cfg["moe_layer_freq"])
    return next((i for i, f in enumerate(freq) if f), len(freq))


def period_of(kinds):
    """The shortest run of kinds that the pattern repeats whole."""
    L = len(kinds)
    return next(kinds[:p] for p in range(1, L + 1) if L % p == 0 and kinds == kinds[:p] * (L // p))


def shape_of(cfg, kind):
    """(kv heads, key width, value width, rotary base, window or None) of an attention layer of ``kind``."""
    if kind == "sliding":
        return (cfg["swa_num_key_value_heads"], cfg["swa_head_dim"], cfg["swa_v_head_dim"],
                float(cfg["swa_rope_theta"]), int(cfg["sliding_window"]))
    return cfg["num_key_value_heads"], cfg["head_dim"], cfg["v_head_dim"], float(cfg["rope_theta"]), None


def rms_norm(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rotary_halves(x, theta, rotary):
    """[S, heads, d]: the first ``rotary`` columns rotate, column ``i`` with column ``i + rotary / 2``."""
    S = x.shape[0]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=F32) / rotary))
    angles = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rotary:]], axis=-1)


def attention(u, w, cfg, kind):
    """One key-value head's group of query heads at a time (``wq``, ``wk``, ``wv``, ``wo`` come in as they are
    held and are cast up a group at a time), its queries a block at a time."""
    S, h = u.shape
    H = cfg["num_attention_heads"]
    Hkv, d, dv, theta, window = shape_of(cfg, kind)
    G = H // Hkv
    rotary = int(cfg.get("partial_rotary_factor", 1.0) * d) // 2 * 2
    value_scale = float(cfg.get("attention_value_scale") or 1.0)
    block = min(ATTENTION_BLOCK, S)
    n_blocks = -(-S // block)
    Sp = n_blocks * block
    # the keys a block of queries can see: every key up to its last query, or its window's
    behind = Sp - block if window is None else min(window - 1, Sp - block)
    span = behind + block
    sunk = kind == "sliding" and bool(cfg.get("add_swa_attention_sink_bias"))

    def group(out, ws):
        wq, wk, wv, wo = (a.astype(F32) for a in ws[:4])  # [h, G, d], [h, d], [h, dv], [G, dv, h]
        q, k, v = jnp.einsum("se,egd->sgd", u, wq), u @ wk, value_scale * (u @ wv)
        q, k = rotary_halves(q, theta, rotary), rotary_halves(k[:, None], theta, rotary)[:, 0]
        q = jnp.pad(q, ((0, Sp - S), (0, 0), (0, 0))).reshape(n_blocks, block, G, d)
        k = jnp.pad(k, ((behind, Sp - S), (0, 0)))  # key j at row j + behind
        v = jnp.pad(v, ((behind, Sp - S), (0, 0)))

        def one(i):
            qb = jax.lax.dynamic_index_in_dim(q, i, 0, keepdims=False)  # [block, G, d]
            kb = jax.lax.dynamic_slice(k, (i * block, 0), (span, d))
            vb = jax.lax.dynamic_slice(v, (i * block, 0), (span, dv))
            t = i * block + jnp.arange(block)[:, None]  # the queries' positions
            j = i * block - behind + jnp.arange(span)[None, :]  # the keys'
            seen = (j >= 0) & (j <= t)
            if window is not None:
                seen = seen & (t - j < window)
            scores = jnp.where(seen[None], jnp.einsum("qgd,kd->gqk", qb, kb) * d ** -0.5, MASKED)
            if sunk:  # the sink: one more column of the softmax, which weighs no value
                column = jnp.broadcast_to(ws[4].astype(F32)[:, None, None], scores.shape[:2] + (1,))
                probs = jax.nn.softmax(jnp.concatenate([scores, column], axis=-1), axis=-1)[..., :-1]
            else:
                probs = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("gqk,kd->qgd", probs, vb)

        ctx = jax.lax.map(one, jnp.arange(n_blocks)).reshape(Sp, G, dv)[:S]
        return out + jnp.einsum("sgd,gde->se", ctx, wo), None

    per_group = (jnp.moveaxis(w["wq"].reshape(h, Hkv, G, d), 1, 0), jnp.moveaxis(w["wk"], 1, 0),
                 jnp.moveaxis(w["wv"], 1, 0), w["wo"].reshape(Hkv, G, dv, h))
    if sunk:
        per_group += (w["sink"].reshape(Hkv, G),)
    return jax.lax.scan(group, jnp.zeros_like(u), per_group)[0]


def glu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def held(cfg, experts_all: int):
    """(first, count) of the router's numbering that the weights hold."""
    share = cfg.get("expert_parallel")
    if not share:
        return 0, experts_all
    return int(share.get("rank", 0)) * cfg["n_routed_experts"], cfg["n_routed_experts"]


def routed(u, w, experts_w, cfg, picks):
    """[S, h] -> the held routed experts' output, and the shortfall [S] of ``picks`` (this router's own top-k
    where ``picks`` is None). ``experts_w``: the held experts' three stacked leaves, not yet cast."""
    scores = jax.nn.sigmoid(u @ w["router"])  # [S, E_all]
    select = scores + w["router_bias"]  # what the top-k is taken over; the weights are not
    if picks is None:
        picks = jax.lax.top_k(select, cfg["num_experts_per_tok"])[1]
    chosen = jax.nn.one_hot(picks, scores.shape[-1], dtype=F32).sum(-2) > 0  # [S, E_all]
    gate = jnp.where(chosen, scores, 0.0)
    if cfg.get("norm_topk_prob", True):
        gate = gate / (gate.sum(-1, keepdims=True) + 1e-20)  # over ALL the picks, wherever their experts live
    gate = gate * float(cfg.get("routed_scaling_factor") or 1.0)
    first, count = held(cfg, scores.shape[-1])
    gate = gate[:, first:first + count]

    def one(out, ew):  # one expert cast up at a time, every token through it
        g, (w_gate, w_up, w_down) = ew[0], (a.astype(F32) for a in ew[1:])
        return out + g[:, None] * glu(u, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (gate.T,) + tuple(experts_w))
    best_left = jnp.where(chosen, -jnp.inf, select).max(-1)
    worst_taken = jnp.where(chosen, select, jnp.inf).min(-1)
    return out, (best_left - worst_taken) / select.std(-1)


def layer(x, w, kind, cfg, picks, dense: bool):
    """-> (the layer's output, the shortfall [S] of its router's picks; None for a dense layer)."""
    large = {k: w[k] for k in LARGE_LEAVES if k in w}
    w = {k: a.astype(F32) for k, a in w.items() if k not in LARGE_LEAVES}
    eps = cfg["layernorm_epsilon"]
    a = x + attention(rms_norm(x, w["norm1"], eps), dict(w, **large), cfg, kind)
    u = rms_norm(a, w["norm2"], eps)
    if dense:
        return a + glu(u, *(large[k].astype(F32) for k in EXPERT_LEAVES)), None
    mlp, shortfall = routed(u, w, tuple(large[k] for k in EXPERT_LEAVES), cfg, picks)
    return a + mlp, shortfall


def _row(weights, cfg, tokens, picks):
    kinds, D = layer_kinds(cfg), dense_layers(cfg)
    period = period_of(kinds[D:])
    P = len(period)
    x = jnp.take(weights["embed"], tokens, axis=0).astype(F32)
    for i in range(D):
        x, _ = layer(x, weights["dense"][i], kinds[i], cfg, None, dense=True)
    # picks [S, routed layers, k] -> [periods, P, S, k]
    by_layer = None if picks is None else jnp.moveaxis(picks, 1, 0).reshape((-1, P) + (picks.shape[0], picks.shape[2]))

    def step(x, xs):
        w, p = xs if picks is not None else (xs, None)
        short = []
        for j, kind in enumerate(period):
            x, s = layer(x, w[j], kind, cfg, None if p is None else p[j], dense=False)
            short.append(s)
        return x, jnp.stack(short)

    x, shortfall = jax.lax.scan(step, x, weights["period"] if picks is None else (weights["period"], by_layer))
    x = rms_norm(x, weights["final_norm"].astype(F32), cfg["layernorm_epsilon"])
    logits = x @ weights["head"].astype(F32)
    return logits, shortfall.reshape(-1, shortfall.shape[-1]).T  # [S, V], [S, routed layers]


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
    """float32 [B, S, routed layers]: along the pass pinned to ``picks``, the best ``s + b`` among the experts
    NOT picked minus the worst among those picked, in units of that quantity's standard deviation over the
    experts at that position. Zero or less where the picks are this router's own top-k; positive by how far a
    pick is from one it could have made."""
    return _run(weights, cfg, tokens, picks)[1]
