"""Mixture-of-Experts with expert parallelism.

TPU-native re-design of the reference MoE stack (``deepspeed/moe/``):
``TopKGate`` (``moe/sharded_moe.py:449``; top1/top2/topk gating fns :183, :290,
:374 with capacity factor, load-balancing aux loss, random token priority),
``Experts`` (``moe/experts.py:13``) and ``MOELayer`` (``sharded_moe.py:533``)
whose einsum dispatch/combine the TPU version keeps, replacing the explicit
``_AllToAll`` autograd op (:96) with sharding constraints over the ``ep`` mesh
axis that XLA lowers to all-to-all on ICI.

Data layout: tokens [T, M] -> dispatch einsum -> [E, C, M] (expert, capacity,
model). Expert weights are stacked [E, M, H]/[E, H, M] and sharded over ``ep``,
so the [E, C, M] activation resharding onto ``ep`` IS the dispatch all-to-all.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.topology.mesh import get_mesh, has_mesh
from deepspeed_tpu.utils.logging import logger


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None  # None | 'RSample' | 'Jitter'
    drop_tokens: bool = True
    use_rts: bool = True  # random token selection for priority under drops
    aux_loss_weight: float = 0.01
    # token distribution follows the reference's expert-data decomposition:
    # experts shard over 'ep'; dp ranks inside an ep group replicate experts.
    # Emit device-computed dispatch stats (MOE_STAT_KEYS) alongside the aux
    # loss — the telemetry moe/* gauges. Changes the layer's return arity.
    collect_metrics: bool = False
    # How the [E, C, M] dispatch/combine reshards onto the ep axis (ISSUE 15):
    #   "auto"       — explicit collective dispatch on ep x tp meshes (where
    #                  constraint-based resharding is the unverified path the
    #                  engine used to refuse), GSPMD constraints elsewhere
    #   "collective" — force the shard_map + facade all_to_all dispatch on
    #                  any ep>1 mesh
    #   "gspmd"      — force constraint-based resharding everywhere
    # The collective path is the reference moe/mappings.py shape: tokens are
    # gathered across the tp group at region entry (token specs never name
    # tp, so tp ranks see the full token set) and the duplicate outputs are
    # dropped at region exit; the dispatch and combine each cross the wire
    # as ONE facade all_to_all over ep, recorded like any stated collective.
    dispatch: str = "auto"
    # Capacity-factor autotuning support (runtime moe_autotune block): when
    # set, the capacity ARRAYS are sized by this ceiling factor and the
    # factor actually enforced is a traced scalar clipped into
    # [capacity_factor bounds, ceiling] — so the host-side controller can
    # move the effective capacity between steps without a recompile.
    max_capacity_factor: Optional[float] = None


# Dispatch-health gauges the gating math can compute for free (ROADMAP item
# 4's instrumentation). All fp32 scalars, device-computed, fetched only at
# the engine's existing monitor sync points:
#   moe/capacity_factor     realized capacity demand — the factor that would
#                           have kept every token (busiest expert's pre-drop
#                           load x E / (T*k)); above the configured
#                           capacity_factor means tokens dropped
#   moe/token_drop_rate     fraction of (token, choice) slots dropped at the
#                           capacity cutoff
#   moe/expert_load_balance E * sum_e(share_e^2) of pre-drop routing: 1.0 =
#                           perfectly uniform, E = total collapse onto one
MOE_STAT_KEYS = ("moe/capacity_factor", "moe/token_drop_rate",
                 "moe/expert_load_balance")

# With dynamic capacity (``MoEConfig.max_capacity_factor``) the gate also
# reports the factor it actually ENFORCED this step — the autotuning
# controller's feedback that its knob reached the program:
#   moe/capacity_factor_applied   effective_capacity * E / (T * k)
MOE_DYNAMIC_STAT_KEYS = MOE_STAT_KEYS + ("moe/capacity_factor_applied",)


def _ep_constrain(x: jax.Array, spec: P) -> jax.Array:
    if not has_mesh():
        return x
    mesh = get_mesh()
    if mesh.shape["ep"] <= 1:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _capacity(num_tokens: int, num_experts: int, factor: float, min_capacity: int, top_k: int) -> int:
    import math

    # ceil, matching the reference's _capacity (sharded_moe.py ceil semantics)
    cap = math.ceil(num_tokens * top_k * factor / num_experts)
    return max(cap, min_capacity)


def route(logits: jax.Array, top_k: int, *, kind: str = "softmax",
          bias: Optional[jax.Array] = None, renormalize: bool = True,
          scale: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """THE router of a drop-free routed layer: ``logits`` [T, E] ->
    ``(weights [T, k] fp32, picks [T, k] int32)``, one definition for the flax
    layer below (:class:`DropFreeMoE`) and the serving twin
    (``inference/model.py::_moe``).

    ``softmax``: the top-k of the softmax, renormalised over the k.
    ``sigmoid``: scores are sigmoids; the experts are CHOSEN by score plus the
    per-expert correction ``bias`` and WEIGHED by the unbiased scores, which
    are renormalised over the k if ``renormalize``; ``scale`` multiplies the
    weights. The bias picks, it never weighs. fp32 throughout."""
    logits = logits.astype(jnp.float32)
    if kind == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        select = scores if bias is None else scores + bias.astype(jnp.float32)
        picks = jax.lax.top_k(select, top_k)[1]
        weights = jnp.take_along_axis(scores, picks, axis=-1)
        if renormalize:
            weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    elif kind == "softmax":
        weights, picks = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        if renormalize:
            weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    else:
        raise ValueError(f"unknown router kind {kind!r} (softmax | sigmoid)")
    return weights * scale, picks.astype(jnp.int32)


def top_k_gating(
    logits: jax.Array,  # [T, E]
    top_k: int,
    capacity: int,
    rng: Optional[jax.Array] = None,
    use_rts: bool = True,
    drop_tokens: bool = True,
    collect_stats: bool = False,
    effective_capacity: Optional[jax.Array] = None,
) -> Tuple[jax.Array, ...]:
    """Generic top-k gating (covers the reference's top1/top2/topk gates).

    Returns (l_aux, combine_weights [T, E, C], dispatch_mask [T, E, C], exp_counts [E]).
    Load-balancing aux loss is the standard me*ce formulation
    (``sharded_moe.py`` top1gating): E * sum_e mean_prob_e * frac_tokens_e.
    With ``collect_stats`` a fifth element is appended: a ``MOE_STAT_KEYS``
    dict of fp32 scalar dispatch-health gauges (see the key docs above) —
    a handful of reductions over masks the gate already built.

    ``effective_capacity`` (int scalar, traced or static, <= ``capacity``)
    makes the drop cutoff dynamic while the array dims stay padded to the
    static ``capacity`` bound — the capacity-autotuning contract: one
    compiled program, a data-dependent cutoff. Adds the
    ``moe/capacity_factor_applied`` stat when stats are collected.
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    topk_vals, topk_idx = jax.lax.top_k(probs, top_k)  # [T, k]
    masks = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)  # [T, k, E]

    # aux loss from the top-1 assignment (reference top1gating/top2gating)
    me = probs.mean(axis=0)  # [E]
    ce = masks[:, 0, :].mean(axis=0)  # fraction routed per expert (1st choice)
    l_aux = jnp.sum(me * ce) * E

    # Without drops, capacity must still be static under jit: the worst case
    # is every (token, choice) slot routed to one expert. (The reference grows
    # capacity to max(exp_counts) dynamically — impossible in XLA.)
    if not drop_tokens:
        capacity = T * top_k
        effective_capacity = None

    # position of each token within its expert's capacity, priority by order
    # (optionally randomized: random token selection, ``use_rts``)
    if use_rts and rng is not None:
        priority = jax.random.uniform(rng, (T,))
        order = jnp.argsort(priority)
        inv_order = jnp.argsort(order)
        masks = masks[order]
    # cumulative count per expert across (choice, token) slots — second
    # choices queue behind first choices for the same expert (reference top2)
    flat = jnp.concatenate([masks[:, j, :] for j in range(top_k)], axis=0)  # [k*T, E]
    route_counts = flat.sum(axis=0)  # [E] pre-drop demand per expert
    positions = jnp.cumsum(flat, axis=0) - flat  # [k*T, E]
    pos_in_expert = (positions * flat).sum(axis=-1)  # [k*T]
    cutoff = capacity if effective_capacity is None else effective_capacity
    keep = pos_in_expert < cutoff
    flat = flat * keep[:, None]

    # back to [T, k, E]
    per_k = jnp.stack(jnp.split(flat, top_k, axis=0), axis=1)  # [T, k, E]
    per_k_pos = jnp.stack(jnp.split(pos_in_expert, top_k, axis=0), axis=1)  # [T, k]
    if use_rts and rng is not None:
        per_k = per_k[inv_order]
        per_k_pos = per_k_pos[inv_order]

    # renormalize kept gate values over k (reference top2: normalize by sum)
    kept_gate = (per_k.sum(axis=-1) * topk_vals).astype(jnp.float32)  # [T, k]
    denom = jnp.clip(kept_gate.sum(axis=-1, keepdims=True), 1e-9, None)
    gate_w = kept_gate / denom

    cap_oh = jax.nn.one_hot(per_k_pos.astype(jnp.int32), capacity, dtype=jnp.float32)  # [T,k,C]
    combine = jnp.einsum("tk,tke,tkc->tec", gate_w, per_k, cap_oh)
    dispatch = (combine > 0).astype(logits.dtype)
    exp_counts = flat.sum(axis=0).astype(jnp.int32)
    out = (l_aux.astype(jnp.float32), combine.astype(logits.dtype), dispatch, exp_counts)
    if not collect_stats:
        return out
    slots = jnp.float32(T * top_k)  # every (token, choice) routes somewhere
    share = route_counts / slots  # [E], sums to 1
    stats = {
        "moe/capacity_factor": route_counts.max() * E / slots,
        "moe/token_drop_rate": 1.0 - exp_counts.sum() / slots,
        "moe/expert_load_balance": E * jnp.sum(share * share),
    }
    if effective_capacity is not None:
        # the factor the cutoff actually enforced — the controller's
        # feedback that its between-steps knob reached the program
        stats["moe/capacity_factor_applied"] = (
            jnp.asarray(effective_capacity, jnp.float32) * E / slots)
    return out + ({k: v.astype(jnp.float32) for k, v in stats.items()},)


class TopKGate(nn.Module):
    """Gate module (reference ``TopKGate`` sharded_moe.py:449)."""

    config: MoEConfig
    model_dim: int

    @nn.compact
    def __call__(self, x: jax.Array, train: bool,
                 capacity_scale: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        cfg = self.config
        T = x.shape[0]
        if cfg.noisy_gate_policy not in (None, "RSample", "Jitter"):
            raise ValueError(f"unknown noisy_gate_policy {cfg.noisy_gate_policy!r}")
        gate_in = x.astype(jnp.float32)
        noisy = train and self.has_rng("dropout")
        if noisy and cfg.noisy_gate_policy == "Jitter":
            # multiplicative uniform jitter on the gate input (reference
            # ``multiplicative_jitter`` in sharded_moe.py)
            eps = 1e-2
            gate_in = gate_in * jax.random.uniform(
                self.make_rng("dropout"), gate_in.shape, minval=1.0 - eps, maxval=1.0 + eps
            )
        # gate math in fp32 (reference casts wg to fp32)
        logits = nn.Dense(cfg.num_experts, use_bias=False, dtype=jnp.float32, name="wg")(gate_in)
        if noisy and cfg.noisy_gate_policy == "RSample":
            noise = jax.random.normal(self.make_rng("dropout"), logits.shape)
            logits = logits + noise / cfg.num_experts
        factor = cfg.capacity_factor if train else cfg.eval_capacity_factor
        effective = None
        if train and cfg.max_capacity_factor is not None and cfg.drop_tokens:
            # dynamic capacity: the arrays are padded to the CEILING bound
            # (jit-cache-stable), the enforced cutoff follows the traced
            # factor scalar the engine's autotuning controller threads in
            capacity = _capacity(T, cfg.num_experts, cfg.max_capacity_factor,
                                 cfg.min_capacity, cfg.top_k)
            f = (jnp.float32(factor) if capacity_scale is None
                 else jnp.asarray(capacity_scale, jnp.float32))
            effective = jnp.clip(
                jnp.ceil(T * cfg.top_k * f / cfg.num_experts),
                cfg.min_capacity, capacity).astype(jnp.int32)
        else:
            capacity = _capacity(T, cfg.num_experts, factor, cfg.min_capacity, cfg.top_k)
        rng = self.make_rng("dropout") if (train and cfg.use_rts and self.has_rng("dropout")) else None
        gated = top_k_gating(
            logits, cfg.top_k, capacity, rng=rng, use_rts=cfg.use_rts and train,
            drop_tokens=cfg.drop_tokens, collect_stats=cfg.collect_metrics,
            effective_capacity=effective,
        )
        l_aux, combine, dispatch = gated[0], gated[1], gated[2]
        if cfg.collect_metrics:
            return l_aux, combine, dispatch, gated[4]
        return l_aux, combine, dispatch


def experts_ffn(x: jax.Array, w_gate: Optional[jax.Array], w_up: jax.Array,
                w_down: jax.Array, activation: str, dtype) -> jax.Array:
    """The stacked-expert FFN math on ``[E, C, M]`` slots — ONE definition
    shared by the :class:`Experts` module and the collective dispatch path
    (which runs it on the LOCAL expert shard inside shard_map). Biasless by
    construction: an all-zero capacity slot maps to an all-zero output,
    the invariant the partial-sum dispatch relies on."""
    if activation == "silu_glu":
        h = jax.nn.silu(jnp.einsum("ecm,emh->ech", x, w_gate.astype(dtype)))
        h = h * jnp.einsum("ecm,emh->ech", x, w_up.astype(dtype))
    else:
        from deepspeed_tpu.models.transformer import act_fn

        h = act_fn(activation)(jnp.einsum("ecm,emh->ech", x, w_up.astype(dtype)))
    return jnp.einsum("ech,ehm->ecm", h, w_down.astype(dtype))


class Experts(nn.Module):
    """Stacked expert FFNs (reference ``Experts`` moe/experts.py:13).

    Weights: [E, M, H] / [E, H, M], sharded over the ``ep`` mesh axis via the
    partition rules below — grouped matmul over experts maps to one einsum.
    Declared in ``setup`` (not compact) so the collective dispatch path can
    read the kernels via :meth:`kernels` and run :func:`experts_ffn` on the
    LOCAL expert shard inside its shard_map region.
    """

    num_experts: int
    model_dim: int
    hidden_dim: int
    activation: str = "silu_glu"
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    # lecun_normal on [E, M, H] counts the experts into the fan-in (std
    # 1/sqrt(E*M)); True draws each expert as a matrix of its own (1/sqrt(M))
    per_expert_init: bool = False

    def setup(self):
        E, M, H = self.num_experts, self.model_dim, self.hidden_dim
        init = nn.initializers.lecun_normal(batch_axis=(0,) if self.per_expert_init else ())
        if self.activation == "silu_glu":
            self.w_gate = self.param("w_gate", init, (E, M, H), self.param_dtype)
        self.w_up = self.param("w_up", init, (E, M, H), self.param_dtype)
        self.w_down = self.param("w_down", init, (E, H, M), self.param_dtype)

    def kernels(self) -> Tuple[Optional[jax.Array], jax.Array, jax.Array]:
        """(w_gate | None, w_up, w_down) — raw stacked kernels."""
        return (getattr(self, "w_gate", None) if self.activation == "silu_glu" else None,
                self.w_up, self.w_down)

    def __call__(self, x: jax.Array) -> jax.Array:  # x: [E, C, M]
        w_gate, w_up, w_down = self.kernels()
        return experts_ffn(x, w_gate, w_up, w_down, self.activation, self.dtype)


# ------------------------------------------------- collective token dispatch


def _token_axes(mesh) -> Tuple[str, ...]:
    """The mesh axes token shards split over inside the collective dispatch
    region: the batch axes, ep (the expert-data decomposition) AND tp.

    The cross-tp token mapping (reference ``moe/mappings.py``
    gather_tokens/drop_tokens): the gate runs on the full GATHERED token
    set outside the region, and inside it the token dim shards across tp
    too — each tp rank dispatches a distinct token slice, so the duplicate
    work (and the duplicate outputs the reference drops) never exists.
    Naming EVERY >1 mesh axis in the token specs is also deliberate
    hygiene: on this jax 0.4.37, a shard_map output spec that leaves a >1
    manual axis unmentioned (replication-assumed) mis-assembles the global
    result when the region's inputs are traced intermediates — observed as
    deterministic garbage on ep x tp meshes; fully-named specs sidestep
    the bug entirely (sp rides along for the same reason — a flattened
    [B*S] token dim slices over it like any other)."""
    return tuple(a for a in ("dp", "fsdp", "ep", "sp", "tp")
                 if mesh.shape[a] > 1) or ("ep",)


def collective_dispatch_blocker(cfg: MoEConfig, mesh, num_tokens: int) -> Optional[str]:
    """Why the collective dispatch CANNOT serve this (mesh, shape) — None
    when it can. Static trace-time answer."""
    ep = mesh.shape["ep"]
    if cfg.num_experts % ep:
        return f"num_experts {cfg.num_experts} not divisible by ep={ep}"
    shards = 1
    for a in _token_axes(mesh):
        shards *= mesh.shape[a]
    if num_tokens % shards:
        return (f"{num_tokens} tokens not divisible by the "
                f"{shards} token shards (dp x fsdp x ep x sp x tp)")
    if mesh.shape["pp"] > 1:
        return "pp>1 runs layers inside the pipeline's own shard_map regions"
    return None


def resolve_dispatch_mode(cfg: MoEConfig, num_tokens: int) -> str:
    """'collective' | 'gspmd' for this trace (see ``MoEConfig.dispatch``).

    "auto" routes collective whenever tp > 1 — ep present or not: driving
    the constraint path end-to-end on tp meshes showed its MoE einsum
    lowering deviating from the global math on this jax/XLA (step-1 loss
    off by ~0.5% on a dp2 x ep2 x tp2 CPU mesh, ep=1 x tp=2 likewise —
    the "silent mis-routing" the engine's old ep x tp refusal guarded
    against, now reproduced). The collective region matches the global
    math to fp rounding."""
    if not has_mesh():
        return "gspmd"
    mesh = get_mesh()
    ep, tp = mesh.shape["ep"], mesh.shape["tp"]
    if cfg.dispatch == "gspmd":
        return "gspmd"
    if cfg.dispatch not in ("auto", "collective"):
        raise ValueError(
            f"MoEConfig.dispatch must be auto|collective|gspmd, got {cfg.dispatch!r}")
    if cfg.dispatch == "auto" and tp <= 1:
        return "gspmd"
    if cfg.dispatch == "collective" and ep <= 1 and tp <= 1:
        return "gspmd"  # nothing to dispatch over — the region would be a no-op
    reason = collective_dispatch_blocker(cfg, mesh, num_tokens)
    if reason is None:
        return "collective"
    if mesh.shape["pp"] > 1:
        # a pipeline mesh can NEVER host the collective region (layers run
        # inside the pipeline's own shard_map) — raising would leave
        # pipelined MoE no path at all, so keep the pre-PR GSPMD behavior
        # and say loudly what that means on tp meshes
        logger.warning(
            f"moe: collective dispatch unavailable ({reason}); falling back "
            "to GSPMD constraint resharding"
            + (" — KNOWN to deviate ~0.5% from global math on tp>1 meshes "
               "(set moe_dispatch='gspmd' to acknowledge and silence)"
               if tp > 1 else ""))
        return "gspmd"
    if tp > 1:
        # tp meshes NEED the explicit dispatch — the GSPMD constraint path
        # mis-routes there (~0.5% loss deviation, ep present or not; the
        # corruption the engine's old ep x tp refusal guarded against) —
        # so an unservable shape must fail loudly, never silently fall
        # back onto the known-bad lowering
        raise ValueError(
            f"ep={ep} x tp={tp} MoE requires the collective token dispatch, "
            f"which cannot serve this shape: {reason}")
    # ep-only meshes: the GSPMD resharding is the verified path there
    logger.warning(f"moe: collective dispatch unavailable ({reason}); "
                   "falling back to GSPMD constraint resharding")
    return "gspmd"


def collective_moe_apply(tokens: jax.Array, combine: jax.Array,
                         dispatch: jax.Array, kernels, *, activation: str,
                         dtype) -> jax.Array:
    """The explicit expert-parallel dispatch (reference ``moe/mappings.py``
    + ``_AllToAll``): one full-manual shard_map region where

    1. each token shard (dp x fsdp x ep x sp x tp — the gate saw the full
       GATHERED token set outside; inside, every rank dispatches a distinct
       slice, so the reference's post-combine duplicate drop never exists)
       builds its PARTIAL ``[E, C, M]`` dispatch einsum — global capacity
       slots, so shard contributions are disjoint and all-zero elsewhere;
    2. ONE facade ``all_to_all`` over ep (split E, concat C) lands every
       shard's slots on the owning expert rank;
    3. the local expert FFN runs on ``[E/ep, ep*C, M]`` (biasless: zero
       slots stay zero, so disjoint partials stay disjoint);
    4. the reverse ``all_to_all`` returns each shard its slots' outputs;
    5. the local combine einsum reads only the shard's own tokens' slots.
    """
    from deepspeed_tpu.comm import comm as dist
    from deepspeed_tpu.utils.compat import shard_map

    mesh = get_mesh()
    w_gate, w_up, w_down = kernels
    tok = _token_axes(mesh)
    tok_entry = tok if len(tok) > 1 else tok[0]
    n_ws = 3 if w_gate is not None else 2
    ws = [w for w in (w_gate, w_up, w_down) if w is not None]

    def shard_fn(tok_l, comb_l, disp_l, *ws_l):
        wg, wu, wd = ws_l if n_ws == 3 else (None,) + ws_l
        expert_in = jnp.einsum("tec,tm->ecm", disp_l, tok_l)  # partial [E, C, M]
        expert_in = dist.all_to_all(expert_in, "ep", split_axis=0, concat_axis=1)
        h = experts_ffn(expert_in, wg, wu, wd, activation, dtype)  # [E/ep, ep*C, M]
        expert_out = dist.all_to_all(h, "ep", split_axis=1, concat_axis=0)
        return jnp.einsum("tec,ecm->tm", comb_l, expert_out)  # [T_l, M]

    f = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(tok_entry, None), P(tok_entry, None, None),
                  P(tok_entry, None, None)) + tuple(P("ep", None, None) for _ in ws),
        out_specs=P(tok_entry, None), check_vma=False)
    return f(tokens, combine, dispatch, *ws)


class MoELayer(nn.Module):
    """MoE feed-forward layer (reference ``MoE`` moe/layer.py:17 + ``MOELayer``).

    Input [B, S, M] -> (l_aux, output [B, S, M]). The einsum dispatch/combine
    masks follow the reference; the all-to-all is the ``ep`` resharding of the
    [E, C, M] activations.
    """

    config: MoEConfig
    model_dim: int
    hidden_dim: int
    activation: str = "silu_glu"
    dtype: jnp.dtype = jnp.float32
    train: bool = False
    # PR-MoE (reference moe/layer.py use_residual + the DeepSpeed-MoE paper's
    # Pyramid-Residual design): a dense residual MLP acts as a shared expert,
    # mixed with the routed output by a learned per-token coefficient.
    use_residual: bool = False

    @nn.compact
    def __call__(self, x: jax.Array,
                 capacity_scale: Optional[jax.Array] = None) -> Tuple[jax.Array, ...]:
        B, S, M = x.shape
        tokens = x.reshape(B * S, M)
        gated = TopKGate(self.config, M, name="gate")(tokens, self.train, capacity_scale)
        if self.config.collect_metrics:
            l_aux, combine, dispatch, stats = gated
        else:
            (l_aux, combine, dispatch), stats = gated, None
        experts = Experts(
            self.config.num_experts, M, self.hidden_dim, self.activation,
            self.dtype, name="experts")
        mode = resolve_dispatch_mode(self.config, B * S)
        if mode == "collective":
            # explicit expert-parallel dispatch: cross-tp token gather/drop
            # + facade all_to_all over ep
            out = collective_moe_apply(
                tokens, combine.astype(self.dtype), dispatch.astype(self.dtype),
                experts.kernels(), activation=self.activation, dtype=self.dtype)
        else:
            # dispatch: [T, E, C] x [T, M] -> [E, C, M], then shard E over ep
            expert_in = jnp.einsum("tec,tm->ecm", dispatch.astype(self.dtype), tokens)
            expert_in = _ep_constrain(expert_in, P("ep", None, None))  # all-to-all in
            expert_out = experts(expert_in)
            expert_out = _ep_constrain(expert_out, P("ep", None, None))
            out = jnp.einsum("tec,ecm->tm", combine.astype(self.dtype), expert_out)
        if self.use_residual:
            # residual expert: a dense FFN every token takes; the 2-way
            # coefficient gate decides the routed/residual mix per token
            res = Experts(1, M, self.hidden_dim, self.activation, self.dtype,
                          name="residual_mlp")(tokens[None])[0]
            coef = nn.Dense(2, use_bias=True, dtype=jnp.float32, name="coefficient")(
                tokens.astype(jnp.float32))
            c = jax.nn.softmax(coef, axis=-1).astype(self.dtype)
            out = out * c[:, 0:1] + res * c[:, 1:2]
        # returned aux loss is already weighted — callers add it to their loss
        weighted = self.config.aux_loss_weight * l_aux
        if self.config.collect_metrics:
            return weighted, out.reshape(B, S, M), stats
        return weighted, out.reshape(B, S, M)


def _nonzero_normal(stddev: float):
    """Normal around 0 with every draw pushed off it: a routing leaf left at
    zero is a leaf no check can see (a bias of zero picks what no bias picks)."""

    def init(key, shape, dtype=jnp.float32):
        x = stddev * jax.random.normal(key, shape, jnp.float32)
        return (x + jnp.where(x >= 0, 0.1 * stddev, -0.1 * stddev)).astype(dtype)

    return init


class _Kernel(nn.Module):
    """One matrix under ``<name>/kernel``, where ``nn.Dense`` would put it,
    declared without the product: the math is ``_moe``'s."""

    shape: Tuple[int, ...]
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param("kernel", nn.initializers.lecun_normal(), self.shape, self.param_dtype)


class _Router(nn.Module):
    """``gate/wg/kernel`` as :class:`TopKGate` names it, and for a sigmoid
    router the correction bias ``gate/e_bias``."""

    config: Any

    @nn.compact
    def __call__(self) -> dict:
        cfg = self.config
        # (a chip's share of the layer scores every chip's experts: ``router_experts``)
        out = {"wg": {"kernel": _Kernel((cfg.hidden_size, cfg.router_experts), cfg.param_dtype,
                                        name="wg")()}}
        if cfg.moe_router == "sigmoid" and cfg.moe_router_bias:
            # sigmoid scores of lecun-normal logits spread by about a quarter
            # over the experts: a bias of a fifth of that changes picks
            # without taking the choice over, among 64 experts or fewer. The
            # gap between neighbouring scores at the cut shrinks as 1/experts,
            # and the bias with it: at 256 experts and 8 picks one of 0.05 makes
            # an expert 2.4 times or a third as popular as its neighbour, where
            # a trained correction bias is what BALANCES them, and a chip that
            # holds 16 of them carries a load that follows the draw (a third
            # more rows in its grouped products from one seed to the next)
            spread = min(0.05, 3.2 / cfg.router_experts)
            out["e_bias"] = self.param("e_bias", _nonzero_normal(spread), (cfg.router_experts,),
                                       cfg.param_dtype)
        return out


class _SharedExpert(nn.Module):
    """The shared expert's matrices, named as the dense ``MLP`` names its own."""

    config: Any

    @nn.compact
    def __call__(self) -> dict:
        cfg = self.config
        M, H = cfg.hidden_size, cfg.expert_width * cfg.moe_shared_experts
        names = {"w_up": (M, H), "w_down": (H, M)}
        if cfg.activation == "silu_glu":
            names["w_gate"] = (M, H)
        return {n: {"kernel": _Kernel(shape, cfg.param_dtype, name=n)()}
                for n, shape in names.items()}


class DropFreeMoE(nn.Module):
    """A routed feed-forward layer with no capacity and no drops: every token
    reaches its ``moe_top_k`` experts (:func:`route`) and the shared expert,
    if any. The flax twin of ``inference/model.py::_moe``: it declares the
    parameters under the names :class:`MoELayer` gives them (``gate/wg``,
    ``experts/w_*``; beside them ``gate/e_bias``, ``shared/w_*`` and
    ``shared_gate``), every
    routing leaf drawn nonzero, and runs that one definition of the math, so
    the full-sequence forward and serving cannot drift. ``config`` is a
    TransformerConfig."""

    config: Any

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from deepspeed_tpu.inference.model import _moe

        cfg = self.config
        experts = Experts(cfg.num_experts, cfg.hidden_size, cfg.expert_width, cfg.activation,
                          cfg.dtype, cfg.param_dtype, per_expert_init=True, name="experts")
        w_gate, w_up, w_down = experts.kernels()
        lp = {"gate": _Router(cfg, name="gate")(),
              "experts": {"w_up": w_up, "w_down": w_down}}
        if w_gate is not None:
            lp["experts"]["w_gate"] = w_gate
        if cfg.moe_shared_experts:
            lp["shared"] = _SharedExpert(cfg, name="shared")()
            if cfg.moe_shared_gate:  # sigmoid(x . w) a token, times the shared expert's output
                lp["shared_gate"] = {"kernel": _Kernel((cfg.hidden_size, 1), cfg.param_dtype, name="shared_gate")()}
        return _moe(lp, cfg, x)


def moe_partition_rules(path: str, shape: tuple) -> Optional[P]:
    """Expert weights shard over 'ep'; gate stays replicated."""

    def has(token: str) -> bool:
        return f"'{token}'" in path

    if has("experts") and (has("w_gate") or has("w_up") or has("w_down")):
        pad = len(shape) - 3
        return P(*([None] * pad + ["ep", None, None])) if pad >= 0 else None
    return None
