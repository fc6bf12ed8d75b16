"""``glm_moe_dsa`` (GLM-5) the plain way: ``glm4_moe_lite``'s decoder (latent
attention, leading dense layers, then routed layers of silu-GLU experts under a
sigmoid router with a correction bias, beside a shared expert) with, in EVERY
layer, a learned sparse-attention indexer (DeepSeek sparse attention) that
keeps ``index_topk`` cached tokens a query. Float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no cache, no kernels, attention
NOT absorbed, the selection a mask on the plain scores; nothing imported from
the system under test. The router's and the experts' arithmetic is
``benchmarks/reference/glm4_moe_lite.py``'s (``rms_norm``, ``rotary``, ``glu``
are loaded from that file; the routed sum is written here because of the share).

A layer, for ``h`` the residual stream, ``t`` a query's position, ``s <= t`` a
cached one, every norm an RMSNorm with ``rms_norm_eps`` but the index key's:

    u    = norm1(h_t)
    c_q  = rmsnorm_q(u W_qa)
    q    = c_q W_qb          -> [H, nope | rope]      the last rope columns rotated by RoPE(t)
    [c | k_r] = u W_kva;  c = rmsnorm_kv(c);  k_r rotated by RoPE(t)
    qI   = c_q W_Iq          -> [Hi, Di]              the FIRST rope columns of each head rotated by RoPE(t)
    kI_s = layernorm(u_s W_Ik)  (weight and bias, eps 1e-6), its first rope columns rotated by RoPE(s)
    w    = (u W_Iw) * Hi^-1/2 * Di^-1/2
    I[t, s] = sum_j w_j relu(qI_j . kI_s)
    S_t  = the index_topk positions s <= t of largest I[t, s]  (all of them while t + 1 <= index_topk;
           ties to the lower s)
    k_s,j = [c_s W_UK,j | k_r,s],  v_s,j = c_s W_UV,j
    o_j  = sum_{s in S_t} softmax_{s in S_t}(q_j . k_s,j (nope + rope)^-1/2) v_s,j
    h    = h + concat_j(o_j) W_o

then the dense MLP or the routed layer as ``glm4_moe_lite.py`` has them. RoPE
turns ADJACENT column pairs (``rope_interleave`` and ``indexer_rope_interleave``
both true) by ``rope_parameters.rope_theta``, no scaling.

**A chip's share** (``expert_parallel: {size, rank}`` in the config): the
router scores ``size * n_routed_experts`` experts, picks ``num_experts_per_tok``
among them all, weighs over all the picks; of the sum the terms of experts
``rank * n_routed_experts ...`` (the ones held: the leaves hold those alone)
and the shared expert are added. What the other chips' experts would add is
left out, here as in the program.

**Departures from the published description**, each also under ``assumed`` in
the configuration's file: the published inference code rotates ``qI`` and ``kI``
by a Hadamard matrix before it quantizes them to fp8: an orthogonal map of
both sides of a dot product, left out with the quantization (scores from float32
``qI`` and ``kI`` here); the multi-token-prediction layer is not built;
``kv_b_proj`` is one ``[rank, H, nope + v]`` matrix.

``forward(weights, cfg, tokens, picks=None, selected=None)``: ``picks`` as a
routed reference takes them (PERF.md, section 7); ``selected`` int32 ``[B, S,
layers, ceil(S / 32)]``, a query's kept positions packed 32 a word (bit ``s %
32`` of word ``s // 32``): it attends THERE instead of where its own scores
point. ``route_shortfall`` as the harness calls it. ``select_shortfall(weights,
cfg, tokens, picks, selected)`` float32 ``[B, S, layers]``: how far the smallest
of this file's own scores among a query's ``selected`` positions lies under its
own ``index_topk``-th largest, in standard deviations of that query's scores
over its candidates; 0 where the selection is one these scores could have made.

**In blocks, so that it fits beside the engine**: the weights come in as the
program's own bf16 arrays relabelled, cast up a layer at a time and within a
routed one an expert at a time; the sequences go ONE ROW AT A TIME, a row's
index scores and attention ``QUERY_BLOCK`` queries at a time (64 heads' scores
of 8,202 queries against 8,202 keys would be 17 GB), and each row's logits go
to the HOST's memory as they are made.

    embed_in [V, h]   embed_out [h, V]   final_norm [h]
    dense, routed: every entry stacked over that group's layers
      glm4_moe_lite.py's, and the indexer's
      idx_wq [L, rq, Hi, Di]   idx_wk [L, h, Di]   idx_k_scale idx_k_bias [L, Di]   idx_w [L, h, Hi]
    routed: router [L, h, size * E]   router_bias [L, size * E]   w_gate w_up [L, E, h, f]   w_down [L, E, f, h]
"""

from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "benchmarks_reference_glm4_moe_lite", os.path.join(os.path.dirname(__file__), "glm4_moe_lite.py"))
_lite = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_lite)
rms_norm, glu = _lite.rms_norm, _lite.glu

F32 = jnp.float32
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
QUERY_BLOCK = 128
INDEX_KEY_EPS = 1e-6


def rotary(x, positions, theta):
    """[S, heads, d]: adjacent pairs rotate together, over all of d."""
    return _lite.rotary(x[None], positions, theta)[0]


def layer_norm(x, scale, bias, eps):
    centred = x - x.mean(-1, keepdims=True)
    return centred / jnp.sqrt((centred * centred).mean(-1, keepdims=True) + eps) * scale + bias


def theta(cfg) -> float:
    return float((cfg.get("rope_parameters") or {}).get("rope_theta", cfg.get("rope_theta", 10000.0)))


def _blocks(fn, queries, S):
    """``fn`` over ``QUERY_BLOCK`` queries at a time: ``queries`` a tuple of [S, ...] arrays -> [S, ...]."""
    n = -(-S // QUERY_BLOCK)
    padded = tuple(jnp.pad(a, ((0, n * QUERY_BLOCK - S),) + ((0, 0),) * (a.ndim - 1)) for a in queries)
    out = jax.lax.map(fn, tuple(a.reshape((n, QUERY_BLOCK) + a.shape[1:]) for a in padded))
    return jax.tree_util.tree_map(lambda a: a.reshape((n * QUERY_BLOCK,) + a.shape[2:])[:S], out)


def index_scores(u, c_q, w, cfg):
    """``I`` [S, S] float32, ``-inf`` at ``s > t``."""
    S = u.shape[0]
    Hi, Di, rope = cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
    pos = jnp.arange(S)
    q = jnp.einsum("sr,rnd->snd", c_q, w["idx_wq"])
    q = jnp.concatenate([rotary(q[..., :rope], pos, theta(cfg)), q[..., rope:]], axis=-1)
    k = layer_norm(u @ w["idx_wk"], w["idx_k_scale"], w["idx_k_bias"], INDEX_KEY_EPS)
    k = jnp.concatenate([rotary(k[:, None, :rope], pos, theta(cfg))[:, 0], k[:, rope:]], axis=-1)
    weights = (u @ w["idx_w"]) * (Hi ** -0.5 * Di ** -0.5)

    def block(args):
        q, weights, t = args
        scores = (weights[:, :, None] * jnp.maximum(jnp.einsum("qnd,kd->qnk", q, k), 0.0)).sum(1)
        return jnp.where(pos[None, :] <= t[:, None], scores, -jnp.inf)

    return _blocks(block, (q, weights, pos), S)


def choose(scores, topk):
    """bool [S, S]: each query's ``topk`` largest scores, all its candidates while it has no more, ties to
    the lower position; and the ``topk``-th largest score itself [S] (-inf where a query has fewer)."""
    scores = jnp.where(scores == 0, 0.0, scores)  # (-0.0 is 0.0)
    if scores.shape[-1] <= topk:
        return scores > -jnp.inf, jnp.full(scores.shape[:1], -jnp.inf)
    kth = jax.lax.top_k(scores, topk)[0][:, -1:]
    over, tied = scores > kth, scores == kth
    left = topk - over.sum(-1, keepdims=True)
    return (over | (tied & (jnp.cumsum(tied, axis=-1) <= left))) & (scores > -jnp.inf), kth[:, 0]


def unpack(words, S):
    """int32 [S, ceil(S / 32)] -> bool [S, S], bit ``s % 32`` of word ``s // 32``."""
    bits = (words[:, :, None] >> jnp.arange(32, dtype=jnp.int32)) & 1
    return bits.reshape(words.shape[0], -1)[:, :S] > 0


def attention(u, w, cfg, selected):
    """u [S, hidden], one sequence from position 0 -> (the attention's output [S, hidden], the shortfall [S]
    of ``selected`` [S, words] (this indexer's own choice where it is None)."""
    nope, rank, eps = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    S = u.shape[0]
    pos = jnp.arange(S)
    c_q = rms_norm(u @ w["wq_a"], w["q_norm"], eps)
    q = jnp.einsum("sr,rnd->snd", c_q, w["wq_b"])
    kv = u @ w["wkv_a"]
    c_kv = rms_norm(kv[..., :rank], w["kv_norm"], eps)
    k_rope = rotary(kv[:, None, rank:], pos, theta(cfg))[:, 0]  # one head for all
    q_rope = rotary(q[..., nope:], pos, theta(cfg))
    up = jnp.einsum("sr,rnd->snd", c_kv, w["wkv_b"])
    k_nope, v = up[..., :nope], up[..., nope:]

    scores = index_scores(u, c_q, w, cfg)
    own, kth = choose(scores, cfg["index_topk"])
    if selected is None:
        kept, shortfall = own, jnp.zeros((S,), F32)
    else:
        kept = unpack(selected, S) & (pos[None, :] <= pos[:, None])
        candidate = scores > -jnp.inf
        n = candidate.sum(-1)
        mean = jnp.where(candidate, scores, 0.0).sum(-1) / n
        std = jnp.sqrt(jnp.where(candidate, (scores - mean[:, None]) ** 2, 0.0).sum(-1) / n)
        worst = jnp.where(kept, scores, jnp.inf).min(-1)
        shortfall = jnp.where(jnp.isfinite(kth), jnp.maximum(kth - worst, 0.0) / jnp.maximum(std, 1e-30), 0.0)

    scale = 1.0 / math.sqrt(q.shape[-1])

    def block(args):
        q_nope, q_rope, kept = args  # [b, H, nope], [b, H, rope], [b, S]
        s = (jnp.einsum("qnd,knd->nqk", q_nope, k_nope) + jnp.einsum("qnd,kd->nqk", q_rope, k_rope)) * scale
        s = jnp.where(kept[None], s, -jnp.inf)
        # (a pad query of the last block keeps nothing: its row is not read)
        p = jax.nn.softmax(jnp.where(kept.any(-1)[None, :, None], s, 0.0), axis=-1)
        return jnp.einsum("nqk,knd->qnd", p, v)

    ctx = _blocks(block, (q[..., :nope], q_rope, kept), S)
    return jnp.einsum("qnd,ndh->qh", ctx, w["wo"]), shortfall


def experts(h, w, experts_w, cfg, picks):
    """[S, h] -> the held routed experts' and the shared expert's output, and the shortfall [S] of ``picks``
    [S, k] (this router's own top-k where ``picks`` is None). ``experts_w``: the HELD experts' three stacked
    leaves, not yet cast; the router's columns are every chip's experts."""
    scores = jax.nn.sigmoid(h @ w["router"])  # [S, size * E]
    select = scores + w["router_bias"]  # what the top-k is taken over; the weights are not
    if picks is None:
        picks = jax.lax.top_k(select, cfg["num_experts_per_tok"])[1]
    chosen = jax.nn.one_hot(picks, scores.shape[-1], dtype=F32).sum(-2) > 0
    gate = jnp.where(chosen, scores, 0.0)
    if cfg["norm_topk_prob"]:
        gate = gate / (gate.sum(-1, keepdims=True) + 1e-20)
    gate = gate * cfg["routed_scaling_factor"]
    held = experts_w[0].shape[0]
    first = int((cfg.get("expert_parallel") or {}).get("rank", 0)) * held
    gate = gate[:, first:first + held]  # the terms of the experts held here

    def one(out, ew):  # one expert cast up at a time, every token through it
        g, (w_gate, w_up, w_down) = ew[0], (a.astype(F32) for a in ew[1:])
        return out + g[..., None] * glu(h, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (jnp.moveaxis(gate, -1, 0),) + tuple(experts_w))
    out = out + glu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
    best_left = jnp.where(chosen, -jnp.inf, select).max(-1)
    worst_taken = jnp.where(chosen, select, jnp.inf).min(-1)
    return out, (best_left - worst_taken) / select.std(-1)


def _cast(w):
    return {k: a.astype(F32) for k, a in w.items()}


def dense_layer(x, w, cfg, selected):
    w = _cast(w)
    out, select_short = attention(rms_norm(x, w["norm1"], cfg["rms_norm_eps"]), w, cfg, selected)
    x = x + out
    return x + glu(rms_norm(x, w["norm2"], cfg["rms_norm_eps"]), w["w_gate"], w["w_up"], w["w_down"]), select_short


def routed_layer(x, w, cfg, picks, selected):
    routed = tuple(w[k] for k in EXPERT_LEAVES)
    w = _cast({k: a for k, a in w.items() if k not in EXPERT_LEAVES})
    out, select_short = attention(rms_norm(x, w["norm1"], cfg["rms_norm_eps"]), w, cfg, selected)
    x = x + out
    out, route_short = experts(rms_norm(x, w["norm2"], cfg["rms_norm_eps"]), w, routed, cfg, picks)
    return x + out, (route_short, select_short)


def _row(weights, cfg, tokens, picks, selected):
    """One sequence [S] (picks [S, L, k] or None, selected [S, layers, words] or None) -> (logits [S, V] in
    the host's memory, route shortfall [S, routed layers], select shortfall [S, layers])."""
    x = weights["embed_in"][tokens].astype(F32)
    D = weights["dense"]["norm1"].shape[0]
    by_layer = None if selected is None else jnp.moveaxis(selected, 1, 0)  # [layers, S, words]

    def dense(x, ws):
        return dense_layer(x, ws[0], cfg, ws[1] if by_layer is not None else None)

    x, dense_short = jax.lax.scan(dense, x, (weights["dense"], by_layer[:D] if by_layer is not None else
                                             jnp.zeros((D,), jnp.int32)))

    def routed(x, ws):
        w, p, s = ws
        return routed_layer(x, w, cfg, p if picks is not None else None, s if by_layer is not None else None)

    L = weights["routed"]["norm1"].shape[0]
    blank = jnp.zeros((L,), jnp.int32)
    x, (route_short, routed_short) = jax.lax.scan(
        routed, x, (weights["routed"], jnp.moveaxis(picks, 1, 0) if picks is not None else blank,
                    by_layer[D:] if by_layer is not None else blank))
    x = rms_norm(x, weights["final_norm"].astype(F32), cfg["rms_norm_eps"])
    # the bf16 head as it is: float32 activations, every pass, float32 sums
    logits = jax.lax.dot_general(x, weights["embed_out"], (((1,), (0,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32)
    select_short = jnp.concatenate([dense_short, routed_short])  # [layers, S]
    return (jax.device_put(logits, jax.memory.Space.Host), jnp.moveaxis(route_short, 0, -1),
            jnp.moveaxis(select_short, 0, -1))


_asked = []  # what the newest traced pass was asked of, and what it gave


def _run(weights, cfg, tokens, picks, selected=None):
    """(logits, route shortfall, select shortfall) of one pass. ``forward`` and ``route_shortfall`` asked of
    the SAME traced arrays inside one jitted function, as the benchmark's check asks them, share it (XLA does
    not merge the two scans: ``xing4_0.py``)."""
    asked = (weights, cfg, tokens, picks, selected)
    if isinstance(tokens, jax.core.Tracer) and _asked and all(a is b for a, b in zip(_asked[0], asked)):
        return _asked[1]
    with jax.default_matmul_precision("highest"):
        rows = (jnp.asarray(tokens),) + tuple(None if a is None else jnp.asarray(a) for a in (picks, selected))
        given = [a is not None for a in rows]

        def row(_, args):
            args = iter(args)
            return None, _row(weights, cfg, *(next(args) if g else None for g in given))

        out = jax.lax.scan(row, None, tuple(a for a in rows if a is not None))[1]
    _asked[:] = [asked, out] if isinstance(tokens, jax.core.Tracer) else []
    return out


def forward(weights, cfg, tokens, picks=None, selected=None):
    """tokens [B, S] int -> logits [B, S, V] float32."""
    return _run(weights, cfg, tokens, picks, selected)[0]


def route_shortfall(weights, cfg, tokens, picks):
    """float32 [B, S, routed layers]: along the pass pinned to ``picks``, the
    best selection score ``s + b`` among the experts NOT picked minus the
    worst among those picked, in units of that position's standard deviation
    of the selection score over the experts. Zero or less where the picks are
    this router's own top-k; positive by how far a pick is from one it could
    have made."""
    return _run(weights, cfg, tokens, picks)[1]


def select_shortfall(weights, cfg, tokens, picks, selected):
    """float32 [B, S, layers]: along the pass pinned to ``picks`` and to ``selected``, how far the smallest of
    this file's own index scores among a query's selected positions lies under its own ``index_topk``-th
    largest, in standard deviations of that query's scores over its candidates."""
    return _run(weights, cfg, tokens, picks, selected)[2]
