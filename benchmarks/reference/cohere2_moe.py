"""``cohere2_moe`` (Command A+) the plain way: a decoder whose layers follow a
PATTERN of two attention kinds (``layer_types``: three ``sliding_attention``
layers, then one ``full_attention`` layer), every layer ONE PARALLEL BLOCK in
which attention, a routed MLP and an averaged shared MLP all read the same
LayerNorm of the layer's input. Float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no cache, no ring of pages, no
kernels; nothing imported from the system under test.

With ``x`` the residual stream [S, hidden], ``LN(x) = (x - mean(x)) / sqrt(var(x)
+ layer_norm_eps) * g`` (no bias) and ``h = LN(x)``, a layer is

- ``q = h Wq`` (``num_attention_heads`` heads of ``head_dim``), ``k = h Wk``, ``v
  = h Wv`` (``num_key_value_heads`` heads), no bias, no qk-norm; query head ``i``
  reads key-value head ``i // (heads / kv heads)``.
- a *sliding* layer: rotary on ``q`` and ``k`` over the whole head, ADJACENT
  PAIRS ``(2i, 2i + 1)`` rotating together (``rope_gptj``), ``rope_theta``; the
  query at position ``t`` sees the keys ``j`` with ``0 <= t - j <
  sliding_window`` (its own counted).
- a *full* layer: no rotary and no other position term; causal.
- scores times ``head_dim ** -0.5``, softmax, ``a = concat(heads) Wo``.
- the router: ``s = sigmoid(h Wr)`` over ALL the deployment's experts (``Wr``'s
  columns); the picks are the ``num_experts_per_tok`` largest ``s``; ``w_e = s_e
  / sum of the picked s`` (``norm_topk_prob``); no correction bias, no group
  limit, no routed scale.
- ``routed = sum over the picks of w_e (silu(h G_e) * (h U_e)) D_e``.
- ``shared = (1 / num_shared_experts) * sum over the shared experts of (silu(h
  G'_j) * (h U'_j)) D'_j``, each of width ``intermediate_size``
  (``shared_expert_combination_strategy: "average"`` read as the arithmetic
  mean of the shared experts' outputs, ADDED to the routed sum; the other
  reading, the mean of the routed and the shared part, is not taken). The
  program keeps the shared experts as ONE GLU of ``num_shared_experts`` times
  the width; shared expert ``j`` is columns ``j f .. (j + 1) f`` of its
  ``shared_gate`` / ``shared_up`` and those rows of ``shared_down``.
- ``x_next = x + a + routed + shared``.

``logits = LN_f(x_L) E^T * logit_scale`` with ``E`` the tied embedding.

**The share**: the weights hold the ``num_experts`` experts of ONE chip of
``expert_parallel.size`` (rank ``expert_parallel.rank``: experts ``rank x
num_experts ...`` of the router's numbering); the routed sum runs over the
picks that are among them, with the weights still renormalised over ALL the
picks; what the other chips' experts would have added is left out, and that
partial result goes on to the next layer. Without ``expert_parallel`` every
expert is here. The vocabulary is the chip's slice, as the configuration says.

``forward(weights, cfg, tokens, picks=None)``: with ``picks`` ``[B, S, layers,
k]`` (the router's numbering) every position goes to exactly those experts,
weighted from this file's own fp32 scores over them; with ``None`` the choice
is this file's own top-k. ``route_shortfall`` says, along the same pinned pass,
how far the picks are from ones this router could have made, on the router's
logits, in units of their standard deviation over the experts.

The weights come in as the program's own arrays relabelled, bf16 at the size
of the benchmark's cell; they are cast up a layer, and within it an expert, at
a time (the attention's and the shared experts' matrices a key-value head's
group and a shared expert at a time). Attention is computed in blocks of
``ATTENTION_BLOCK`` queries of one
key-value head's group, against every key (a full layer) or against the keys
the block's queries can see (a sliding layer), so that 16,000 positions x 128
heads never hold their scores at once; the sequences one after another; and
where the logits of every row would not fit the chip beside the program
(``HOST_LOGITS_BYTES``), a row's logits go to the host's memory as they are
made. The layers are scanned a period a step:

    embed [V, h]   final_norm [h]
    period: one entry a layer of ONE period, its leaves stacked over the periods
      norm [n, h]   wq [n, h, H, d]   wk wv [n, h, Hkv, d]   wo [n, H, d, h]   router [n, h, E_all]
      w_gate w_up [n, E, h, f]   w_down [n, E, f, h]
      shared_gate shared_up [n, h, ns f]   shared_down [n, ns f, h]
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
MASKED = -1e30
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
ATTENTION_BLOCK = 256
# a [B, S, V] float32 result of more than this leaves the device a row at a time
HOST_LOGITS_BYTES = 2 << 30


def layer_kinds(cfg):
    kinds = cfg.get("layer_types")
    if kinds is None:
        n = cfg.get("layer_switch", 4)
        kinds = ["full_attention" if (i + 1) % n == 0 else "sliding_attention"
                 for i in range(cfg["num_hidden_layers"])]
    return tuple(kinds)


def period_of(kinds):
    """The shortest run of kinds that the pattern repeats whole."""
    L = len(kinds)
    return next(kinds[:p] for p in range(1, L + 1) if L % p == 0 and kinds == kinds[:p] * (L // p))


def layer_norm(x, g, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g


def rotary_pairs(x, theta):
    """[S, heads, d]: the whole head rotates, columns ``2i`` and ``2i + 1`` together."""
    S, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def rope_theta(cfg):
    return float((cfg.get("rope_parameters") or {}).get("rope_theta", cfg.get("rope_theta", 50000.0)))


def attention(u, w, cfg, kind):
    """One key-value head's group of query heads at a time (``wq``, ``wk``, ``wv``, ``wo`` come in as they are
    held and are cast up a group at a time), its queries a block at a time."""
    S = u.shape[0]
    H, Hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    G = H // Hkv
    window = int(cfg["sliding_window"]) if kind == "sliding_attention" else None
    block = min(ATTENTION_BLOCK, S)
    n_blocks = -(-S // block)
    Sp = n_blocks * block
    # the keys a block of queries can see: every key up to its last query, or its window's
    behind = Sp - block if window is None else min(window - 1, Sp - block)
    span = behind + block

    def group(out, ws):
        wq, wk, wv, wo = (a.astype(F32) for a in ws)  # [h, G, d], [h, d], [h, d], [G, d, h]
        q, k, v = jnp.einsum("se,egd->sgd", u, wq), u @ wk, u @ wv
        if window is not None:
            q, k = rotary_pairs(q, rope_theta(cfg)), rotary_pairs(k[:, None], rope_theta(cfg))[:, 0]
        q = jnp.pad(q, ((0, Sp - S), (0, 0), (0, 0))).reshape(n_blocks, block, G, d)
        k = jnp.pad(k, ((behind, Sp - S), (0, 0)))  # key j at row j + behind
        v = jnp.pad(v, ((behind, Sp - S), (0, 0)))

        def one(i):
            qb = jax.lax.dynamic_index_in_dim(q, i, 0, keepdims=False)  # [block, G, d]
            kb = jax.lax.dynamic_slice(k, (i * block, 0), (span, d))
            vb = jax.lax.dynamic_slice(v, (i * block, 0), (span, d))
            t = i * block + jnp.arange(block)[:, None]  # the queries' positions
            j = i * block - behind + jnp.arange(span)[None, :]  # the keys'
            seen = (j >= 0) & (j <= t)
            if window is not None:
                seen = seen & (t - j < window)
            scores = jnp.einsum("qgd,kd->gqk", qb, kb) * d ** -0.5
            probs = jax.nn.softmax(jnp.where(seen[None], scores, MASKED), axis=-1)
            return jnp.einsum("gqk,kd->qgd", probs, vb)

        ctx = jax.lax.map(one, jnp.arange(n_blocks)).reshape(Sp, G, d)[:S]
        return out + jnp.einsum("sgd,gde->se", ctx, wo), None

    h = u.shape[1]
    per_group = (jnp.moveaxis(w["wq"].reshape(h, Hkv, G, d), 1, 0), jnp.moveaxis(w["wk"], 1, 0),
                 jnp.moveaxis(w["wv"], 1, 0), w["wo"].reshape(Hkv, G, d, h))
    return jax.lax.scan(group, jnp.zeros_like(u), per_group)[0]


def glu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def shared_experts(u, w, ns: int):
    """The SUM of the ``ns`` shared experts' outputs, each cast up as it is used: expert ``j`` is columns ``j f
    .. (j + 1) f`` of the program's one GLU."""
    f = w["shared_gate"].shape[-1] // ns
    cut = lambda name, j, axis: jax.lax.slice_in_dim(w[name], j * f, (j + 1) * f, axis=axis).astype(F32)  # noqa: E731
    return sum(glu(u, cut("shared_gate", j, 1), cut("shared_up", j, 1), cut("shared_down", j, 0)) for j in range(ns))


def held(cfg, experts_all: int):
    """(first, count) of the router's numbering that the weights hold."""
    count = cfg["num_experts"]
    share = cfg.get("expert_parallel")
    if not share:
        return 0, experts_all
    return int(share.get("rank", 0)) * count, count


def routed_and_shared(u, w, experts_w, cfg, picks):
    """[S, h] -> the held routed experts' and the averaged shared experts'
    output, and the shortfall [S] of ``picks`` (this router's own top-k where
    ``picks`` is None). ``experts_w``: the held experts' three stacked leaves,
    not yet cast."""
    logits = u @ w["router"]  # [S, E_all]
    scores = jax.nn.sigmoid(logits)
    if picks is None:
        picks = jax.lax.top_k(scores, cfg["num_experts_per_tok"])[1]
    chosen = jax.nn.one_hot(picks, logits.shape[-1], dtype=F32).sum(-2) > 0  # [S, E_all]
    gate = jnp.where(chosen, scores, 0.0)
    if cfg.get("norm_topk_prob", True):
        gate = gate / gate.sum(-1, keepdims=True)  # over ALL the picks, wherever their experts live
    first, count = held(cfg, logits.shape[-1])
    gate = gate[:, first:first + count]

    def one(out, ew):  # one expert cast up at a time, every token through it
        g, (w_gate, w_up, w_down) = ew[0], (a.astype(F32) for a in ew[1:])
        return out + g[:, None] * glu(u, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (gate.T,) + tuple(experts_w))
    ns = int(cfg.get("num_shared_experts", 0))
    if ns:
        out = out + shared_experts(u, w, ns) / ns  # "average": the mean of the shared experts
    best_left = jnp.where(chosen, -jnp.inf, logits).max(-1)
    worst_taken = jnp.where(chosen, logits, jnp.inf).min(-1)
    return out, (best_left - worst_taken) / logits.std(-1)


LARGE_LEAVES = ("wq", "wk", "wv", "wo", "shared_gate", "shared_up", "shared_down")  # cast up where they are used


def layer(x, w, kind, cfg, picks):
    experts_w = tuple(w[k] for k in EXPERT_LEAVES)
    w = {k: a if k in LARGE_LEAVES else a.astype(F32) for k, a in w.items() if k not in EXPERT_LEAVES}
    h = layer_norm(x, w["norm"], cfg["layer_norm_eps"])
    mlp, shortfall = routed_and_shared(h, w, experts_w, cfg, picks)
    return x + attention(h, w, cfg, kind) + mlp, shortfall


def _row(weights, cfg, tokens, picks):
    kinds = period_of(layer_kinds(cfg))
    P = len(kinds)
    x = jnp.take(weights["embed"], tokens, axis=0).astype(F32)
    # picks [S, layers, k] -> [periods, P, S, k]
    by_layer = None if picks is None else jnp.moveaxis(picks, 1, 0).reshape((-1, P) + (picks.shape[0], picks.shape[2]))

    def period(x, xs):
        w, p = xs if picks is not None else (xs, None)
        short = []
        for j, kind in enumerate(kinds):
            x, s = layer(x, w[j], kind, cfg, None if p is None else p[j])
            short.append(s)
        return x, jnp.stack(short)

    x, shortfall = jax.lax.scan(period, x, weights["period"] if picks is None else (weights["period"], by_layer))
    x = layer_norm(x, weights["final_norm"].astype(F32), cfg["layer_norm_eps"])
    logits = (x @ weights["embed"].astype(F32).T) * float(cfg.get("logit_scale", 1.0))
    return logits, shortfall.reshape(-1, shortfall.shape[-1]).T  # [S, V], [S, layers]


def _off_the_device(logits):
    """A row's logits into the host's memory, where the device is a TPU with
    such a memory space (every row's together would crowd the program out of
    the chip); elsewhere as they are."""
    device = jax.devices()[0]
    if device.platform != "tpu" or "pinned_host" not in {m.kind for m in device.addressable_memories()}:
        return logits
    return jax.device_put(logits, jax.sharding.SingleDeviceSharding(device, memory_kind="pinned_host"))


def _run(weights, cfg, tokens, picks):
    tokens = jnp.asarray(tokens)
    large = tokens.size * cfg["vocab_size"] * 4 > HOST_LOGITS_BYTES

    def row(t, p):
        logits, shortfall = _row(weights, cfg, t, p)
        return (_off_the_device(logits) if large else logits), shortfall

    with jax.default_matmul_precision("highest"):
        if picks is None:
            return jax.lax.map(lambda t: row(t, None), tokens)
        return jax.lax.map(lambda tp: row(tp[0], tp[1]), (tokens, jnp.asarray(picks)))


def forward(weights, cfg, tokens, picks=None):
    """tokens [B, S] int -> logits [B, S, V] float32."""
    return _run(weights, cfg, tokens, picks)[0]


def route_shortfall(weights, cfg, tokens, picks):
    """float32 [B, S, layers]: along the pass pinned to ``picks``, the best
    router logit among the experts NOT picked minus the worst among those
    picked, in units of the logits' standard deviation over the experts at that
    position. Zero or less where the picks are this router's own top-k;
    positive by how far a pick is from one it could have made. (The sigmoid is
    monotone: the logits rank as the scores the published router takes its
    top-k of.)"""
    return _run(weights, cfg, tokens, picks)[1]
