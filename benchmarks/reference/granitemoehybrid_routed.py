"""``granitemoehybrid`` with routed experts (``num_local_experts`` > 0) the
plain way: the dense member's decoder (``benchmarks/reference/
granitemoehybrid.py``, loaded from beside this file for the two mixers, the
norm and the head it shares: a layer PATTERN of Mamba-2 state-space mixers as
the SEQUENTIAL recurrence beside grouped-query attention with no positional
term) whose every mixer is followed by a ROUTED layer beside one shared MLP in
place of the dense MLP. Float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no cache, no kernels, no chunks,
no batching (one sequence after another); nothing imported from the system
under test.

With ``h`` the residual stream [S, hidden], ``m = residual_multiplier`` and
``E`` the tied embedding:

- ``h = embedding_multiplier * E[token]``. Every layer: ``h = h + m *
  mixer(rmsnorm(h))`` (the dense reference's ``attention`` or ``mamba``), then
  ``u = rmsnorm(h)`` and ``h = h + m * (r + s)``; ``logits = rmsnorm(h) E^T /
  logits_scaling``.
- the router: ``g = u W_router`` (one logit an expert of ALL the chips'),
  ``I`` the ``num_experts_per_tok`` largest, ``p = softmax(g[I])`` over the
  PICKED logits alone, ``r = sum_{e in I} p_e glu_e(u)``, ``glu_e(u) = (silu(u
  W_gate_e) * (u W_up_e)) W_down_e`` at width ``intermediate_size``.
- the shared MLP: ``s = glu(u)`` at width ``shared_intermediate_size``, every
  token, no gate, added unweighted.

Departures from the published description, each in form alone:

- the router is written as the published code has it, top-k of the logits THEN
  softmax over the picked; that is the softmax over all the logits renormalised
  over the picks (``exp(g_e) / sum_{i in I} exp(g_i)`` either way), which is how
  the program's ``route(kind="softmax", renormalize=True)`` computes it.
- the published ``input_linear`` [E, 2 x intermediate_size, hidden] is kept as
  its two halves ``w_gate`` and ``w_up`` [E, hidden, intermediate_size], and
  the shared MLP's likewise: one silu-GLU of width ``shared_intermediate_size``.

ONE CHIP'S SHARE (``expert_parallel: {size, rank}`` in the config): the
weights hold ``num_local_experts`` experts, numbers ``rank * num_local_experts
...`` of the router's ``size * num_local_experts``; the router scores and picks
among all and weighs over ALL its picks; ``r`` sums the terms whose expert is
held, the others are left out, and that partial result goes on.

``picks`` [B, S, layers, k] pins the experts (the program's own, PERF.md
section 2); ``route_shortfall`` audits them against this router's logits.

The weights come in as the program's own arrays relabelled, bf16 at the size
of the benchmark's cell; they are cast up a layer at a time, a routed layer's
experts one at a time, and a sequence's logits are handed to the HOST's memory
as they are made, so that the pass fits beside the engine:

    embed [V, h]   final_norm [h]
    period: one entry a layer of ONE period, its leaves stacked over the periods
      attention: norm1 [n, h]   wq [n, h, H, d]   wk wv [n, h, Hkv, d]   wo [n, H, d, h]
      mamba:     norm1 [n, h]   w_in [n, h, 2 H P + 2 G N + H]   conv_w [n, K, X]   conv_b [n, X]
                 A_log dt_bias D [n, H]   norm_w [n, H P]   w_out [n, H P, h]
      both:      norm2 [n, h]   router [n, h, E_all]   w_gate w_up [n, E, h, f]   w_down [n, E, f, h]
                 shared_gate shared_up [n, h, fs]   shared_down [n, fs, h]
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp


def _beside(name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + name, os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


dense = _beside("granitemoehybrid")  # attention, mamba, rms_norm, head, period_of: read, never edited
period_of = dense.period_of

F32 = jnp.float32
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def glu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def held(cfg, experts_all: int):
    """(first, count) of the router's numbering that the weights hold."""
    share = cfg.get("expert_parallel")
    if not share:
        return 0, experts_all
    return int(share.get("rank", 0)) * cfg["num_local_experts"], cfg["num_local_experts"]


def gates(logits, picks):
    """[S, E_all]: softmax over each token's PICKED logits, at the picked experts; 0 elsewhere."""
    p = jax.nn.softmax(jnp.take_along_axis(logits, picks, axis=-1), axis=-1)  # [S, k]
    return (jax.nn.one_hot(picks, logits.shape[-1], dtype=F32) * p[..., None]).sum(-2)


def routed(u, w, experts_w, cfg, picks):
    """[S, h] -> ``r + s``: the held routed experts' terms and the shared
    MLP's output, and the shortfall [S] of ``picks`` (this router's own top-k
    where ``picks`` is None). ``experts_w``: the held experts' three stacked
    leaves, not yet cast."""
    logits = u @ w["router"]  # [S, E_all]
    if picks is None:
        picks = jax.lax.top_k(logits, cfg["num_experts_per_tok"])[1]
    gate = gates(logits, picks)  # over ALL the picks, wherever their experts live
    first, count = held(cfg, logits.shape[-1])
    gate = gate[:, first:first + count]

    def one(out, ew):  # one expert cast up at a time, every token through it
        g, (w_gate, w_up, w_down) = ew[0], (a.astype(F32) for a in ew[1:])
        return out + g[:, None] * glu(u, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (gate.T,) + tuple(experts_w))
    out = out + glu(u, w["shared_gate"], w["shared_up"], w["shared_down"])
    chosen = jax.nn.one_hot(picks, logits.shape[-1], dtype=F32).sum(-2) > 0
    best_left = jnp.where(chosen, -jnp.inf, logits).max(-1)
    worst_taken = jnp.where(chosen, logits, jnp.inf).min(-1)
    return out, (best_left - worst_taken) / logits.std(-1)


def layer(h, w, kind, cfg, picks):
    eps, m = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    experts_w = tuple(w[k] for k in EXPERT_LEAVES)
    w = {k: a.astype(F32) for k, a in w.items() if k not in EXPERT_LEAVES}
    h = h + m * dense.MIXERS[kind](dense.rms_norm(h, w["norm1"], eps), w, cfg)
    out, shortfall = routed(dense.rms_norm(h, w["norm2"], eps), w, experts_w, cfg, picks)
    return h + m * out, shortfall


def _row(weights, cfg, tokens, picks):
    """One sequence [S] (picks [S, layers, k] or None) -> (logits [S, V] in the host's memory, shortfall [S, layers])."""
    kinds = period_of(cfg["layer_types"])
    P = len(kinds)
    h = cfg["embedding_multiplier"] * jnp.take(weights["embed"], tokens, axis=0).astype(F32)
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
    x = dense.rms_norm(h, weights["final_norm"].astype(F32), cfg["rms_norm_eps"])
    logits = dense.head(x, weights["embed"]) / cfg["logits_scaling"]
    return jax.device_put(logits, jax.memory.Space.Host), shortfall.reshape(-1, shortfall.shape[-1]).T


_asked = []  # what the newest traced pass was asked of, and what it gave


def _run(weights, cfg, tokens, picks):
    """(logits, shortfall) of one pass. ``forward`` and ``route_shortfall`` asked
    of the SAME traced arrays inside one jitted function, as the benchmark's
    check asks them, share it: XLA does not merge two scans of one body
    (``benchmarks/reference/xing4_0.py`` says what that cost)."""
    asked = (weights, cfg, tokens, picks)
    if isinstance(tokens, jax.core.Tracer) and _asked and all(a is b for a, b in zip(_asked[0], asked)):
        return _asked[1]
    with jax.default_matmul_precision("highest"):
        rows = jnp.asarray(tokens)
        if picks is None:
            out = jax.lax.scan(lambda _, t: (None, _row(weights, cfg, t, None)), None, rows)[1]
        else:
            out = jax.lax.scan(lambda _, tp: (None, _row(weights, cfg, *tp)), None, (rows, jnp.asarray(picks)))[1]
    _asked[:] = [asked, out] if isinstance(tokens, jax.core.Tracer) else []
    return out


def forward(weights, cfg, tokens, picks=None):
    """tokens [B, S] int -> logits [B, S, V] float32 (the held slice of the
    vocabulary), at this router's own picks or pinned to ``picks``."""
    return _run(weights, cfg, tokens, picks)[0]


def route_shortfall(weights, cfg, tokens, picks):
    """float32 [B, S, layers]: along the pass pinned to ``picks``, the best
    router logit among the experts NOT picked minus the worst among those
    picked, in units of the logits' standard deviation over the experts at that
    position. Zero or less where the picks are this router's own top-k;
    positive by how far a pick is from one it could have made."""
    return _run(weights, cfg, tokens, picks)[1]
