"""Functional KV-cache decoding for the CausalLM family.

TPU-native analog of the reference's inference model implementations
(``deepspeed/ops/transformer/inference/ds_attention.py``,
``model_implementations/transformers/ds_transformer.py``): instead of swapping
nn.Modules for fused-kernel modules, we provide *functional twins* of the
training model that thread an explicit KV cache through the layer stack, so
prefill and decode compile to single XLA programs over the same parameter
pytree the training engine produced (no weight transpose/fusion step needed).

Layout decisions (TPU-first):
  - cache K/V are ``[L, B, maxS, kvH, hd]`` — stacked over layers so the layer
    loop is one ``lax.scan`` (same stacked-params layout as ``nn.scan`` in
    ``models/transformer.py``), heads shardable over ``tp``, batch over ``dp``
  - per-row sequence lengths (ragged prompts via right-padding + masks), so a
    batch of uneven prompts is one compiled program
  - attention over the cache is einsum + masking (flash-decode Pallas kernel
    plugs in via the ops registry for long contexts, v2 paged path)
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.transformer import (
    TransformerConfig,
    _apply_norm,
    _embed_tokens,
    act_fn,
)


class KVCache(NamedTuple):
    """Decoder state for one batch of sequences.

    k/v: ``[L, B, maxS, kvH, hd]`` in ``cache_dtype``; ``kv_mask``: ``[B, maxS]``
    marks valid (non-pad) cache slots; ``lengths``: ``[B]`` tokens written per
    row (== next write position).
    """

    k: jax.Array
    v: jax.Array
    kv_mask: jax.Array
    lengths: jax.Array

    @property
    def max_len(self) -> int:
        return self.k.shape[2]


def init_cache(
    cfg: TransformerConfig,
    batch_size: int,
    max_len: int,
    dtype: Any = jnp.bfloat16,
) -> KVCache:
    """Allocate an empty cache (reference ``InferenceContext`` workspace,
    ``csrc/transformer/inference/includes/inference_context.h`` — here it is
    just a pytree of preallocated arrays XLA can donate/alias)."""
    hd = cfg.dims_per_head
    shape = (cfg.num_layers, batch_size, max_len, cfg.kv_heads, hd)
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        kv_mask=jnp.zeros((batch_size, max_len), jnp.bool_),
        lengths=jnp.zeros((batch_size,), jnp.int32),
    )


# ------------------------------------------------------------------ layers
def _qkv(lp, cfg: TransformerConfig, x):
    """Project hidden states to q/k/v using the training params.

    Matches ``nn.DenseGeneral`` in ``models/transformer.py:142-147``:
    kernel shapes wq [E,H,hd], wk/wv [E,kvH,hd]; bias present iff layernorm
    family (GPT-2 style).
    """
    q = jnp.einsum("bse,ehd->bshd", x, lp["wq"]["kernel"].astype(cfg.dtype))
    k = jnp.einsum("bse,ehd->bshd", x, lp["wk"]["kernel"].astype(cfg.dtype))
    v = jnp.einsum("bse,ehd->bshd", x, lp["wv"]["kernel"].astype(cfg.dtype))
    if "bias" in lp["wq"]:
        q = q + lp["wq"]["bias"].astype(cfg.dtype)
        k = k + lp["wk"]["bias"].astype(cfg.dtype)
        v = v + lp["wv"]["bias"].astype(cfg.dtype)
    return q, k, v


def _attn_out(lp, cfg: TransformerConfig, ctx):
    out = jnp.einsum("bshd,hde->bse", ctx, lp["wo"]["kernel"].astype(cfg.dtype))
    if "bias" in lp["wo"]:
        out = out + lp["wo"]["bias"].astype(cfg.dtype)
    return out


def _mlp(lp, cfg: TransformerConfig, x):
    def dense(p, y):
        o = y @ p["kernel"].astype(cfg.dtype)
        if "bias" in p:
            o = o + p["bias"].astype(cfg.dtype)
        return o

    if cfg.activation == "silu_glu":
        h = jax.nn.silu(dense(lp["w_gate"], x)) * dense(lp["w_up"], x)
    else:
        h = act_fn(cfg.activation)(dense(lp["w_up"], x))
    return dense(lp["w_down"], h)


def _moe(lp, cfg: TransformerConfig, x):
    """The routed feed-forward layer's output alone (``_moe_with_picks``)."""
    return _moe_with_picks(lp, cfg, x)[0]


def _moe_with_picks(lp, cfg: TransformerConfig, x):
    """Routed feed-forward layer, drop-free: every token reaches its top-k
    experts. Returns ``(out [B, S, M], picks [B*S, k] int32)``, the picks by
    the experts' own numbers 0..E-1, in the order the tokens were given.

    The router is ``parallel/moe.py::route`` (the one the flax layer
    :class:`DropFreeMoE` runs, through this very function): softmax top-k
    renormalised, or sigmoid scores chosen by score + ``gate/e_bias`` and
    weighed by the unbiased scores, renormalised and scaled, as the config
    says. A shared expert (``lp["shared"]``), where the layer has one, takes
    every token and is added unweighted.

    Two dispatch regimes, chosen by the (static) token count:

    - decode (few tokens): compute every expert and combine with the gate
      weights — one einsum over the stacked expert params (reference
      ``moe/sharded_moe.py`` combine). At T ~ batch size, gathering by
      expert costs more than the E/top_k extra FLOPs it saves, and the
      weights of nearly every expert are read either way.
    - prefill (T >= 2E tokens): RAGGED dispatch (round 5; reference FastGen's
      ``inference/v2/kernels/ragged_ops`` moe_gather/moe_scatter +
      ``cutlass_ops`` grouped GEMM) — sort the (token, expert) pairs by
      expert and run grouped matmuls via ``lax.ragged_dot``, so prompt FFN
      FLOPs scale with top_k, not E (8x2 Mixtral-style: 4x fewer).

    Device-trace scopes: ``moe_router``, ``moe_experts``, ``moe_shared``
    (the caller opens ``moe`` around the layer).
    """
    from deepspeed_tpu.parallel.moe import route

    B, S, M = x.shape
    tokens = x.reshape(B * S, M)
    T, E, k = tokens.shape[0], cfg.num_experts, cfg.moe_top_k
    with jax.named_scope("moe_router"):
        logits = tokens.astype(jnp.float32) @ lp["gate"]["wg"]["kernel"].astype(jnp.float32)
        top_p, top_i = route(logits, k, kind=cfg.moe_router, bias=lp["gate"].get("e_bias"),
                             renormalize=cfg.moe_renormalize, scale=cfg.moe_routed_scale)
    with jax.named_scope("moe_experts"):
        out = _experts(lp["experts"], cfg, tokens, top_p, top_i)
    if "shared" in lp:
        with jax.named_scope("moe_shared"):
            out = out + _mlp(lp["shared"], cfg, tokens)
    return out.reshape(B, S, M), top_i


def _experts(ep, cfg: TransformerConfig, tokens, top_p, top_i):
    """The picked experts' weighted sum for ``tokens`` [T, M]."""
    T, E = tokens.shape[0], cfg.num_experts
    if _moe_ep_size() > 1:
        # expert-parallel serving (ISSUE 15): the ep-sharded experts are
        # reached through the explicit collective dispatch — the SAME
        # facade all_to_all the training path rides, so quantized token
        # routing, hop spans and observatory signatures apply to serving
        # MoE traffic too. Falls back to the replicated paths below (GSPMD
        # reshards the ep-sharded kernels) only on non-divisible shapes.
        out = _moe_ep_collective(cfg, ep, tokens, top_p, top_i)
        if out is not None:
            return out
    if T >= 2 * E:
        return _moe_ragged(cfg, ep, tokens, top_p, top_i)

    gate = jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], top_i].set(top_p)
    h1 = jnp.einsum("tm,emh->teh", tokens, ep["w_up"].astype(cfg.dtype))
    if cfg.activation == "silu_glu":
        h1 = jax.nn.silu(jnp.einsum("tm,emh->teh", tokens, ep["w_gate"].astype(cfg.dtype))) * h1
    else:
        h1 = act_fn(cfg.activation)(h1)
    out_e = jnp.einsum("teh,ehm->tem", h1, ep["w_down"].astype(cfg.dtype))
    return jnp.einsum("te,tem->tm", gate.astype(cfg.dtype), out_e)


# The no-drop collective dispatch materializes [T*k, E, T*k] routing
# one-hots (capacity = T*k for exactness) — quadratic in the token count —
# from the shared router's picks and weights, whatever kind it is.
# Fine at decode/short-prefill shapes; a long prefill would OOM on the
# one-hots alone, so beyond this bound the ep>1 engine falls back to the
# replicated ragged/dense paths (GSPMD reshards the ep-sharded kernels —
# same math, no collective wire).
_MOE_EP_COLLECTIVE_MAX_TOKENS = 1024


def _moe_ep_size() -> int:
    """Expert-parallel width of the active mesh (1 = no ep sharding)."""
    from deepspeed_tpu.topology.mesh import get_mesh, has_mesh

    if not has_mesh():
        return 1
    return int(get_mesh().shape.get("ep", 1))


def _moe_ep_collective(cfg: TransformerConfig, ep, tokens, top_p, top_i):
    """Expert-parallel inference dispatch through the facade all-to-all.

    Builds NO-DROP dispatch/combine one-hots (capacity = T*k: every
    (token, expert) pair owns a globally unique slot, so routing is exact —
    token-identity with the ep=1 paths is a sum reordering, never a drop)
    and runs the training layer's :func:`collective_moe_apply`: one
    shard_map region, the [E, C, M] reshard as ONE facade ``all_to_all``
    over ep each way, the expert FFN on the LOCAL ep shard. Returns None
    when the (mesh, shape) cannot be served (caller falls back to the
    replicated compute with GSPMD resharding)."""
    from deepspeed_tpu.parallel.moe import _token_axes, collective_moe_apply
    from deepspeed_tpu.topology.mesh import get_mesh
    from deepspeed_tpu.utils.logging import logger

    mesh = get_mesh()
    T, M = tokens.shape
    E, k = cfg.num_experts, cfg.moe_top_k
    shards = 1
    for a in _token_axes(mesh):
        shards *= mesh.shape[a]
    if E % mesh.shape["ep"] or T % shards:
        # trace-time, so this fires once per compiled program shape — the
        # operator's signal that wire codec / hop spans will NOT engage
        logger.warning(
            f"moe ep dispatch: shape unservable ({T} tokens vs {shards} "
            f"token shards, E={E} vs ep={mesh.shape['ep']}); falling back "
            "to replicated compute (GSPMD reshards the ep-sharded kernels)")
        return None
    if T > _MOE_EP_COLLECTIVE_MAX_TOKENS:
        logger.warning(
            f"moe ep dispatch: {T} tokens exceeds the "
            f"{_MOE_EP_COLLECTIVE_MAX_TOKENS}-token collective bound "
            "(no-drop one-hots are quadratic); falling back to replicated "
            "compute for this program")
        return None
    C = T * k  # the no-drop static bound: capacity can never overflow
    flat_e = top_i.reshape(-1)  # [T*k] token-major expert choices
    onehot = (flat_e[:, None] == jnp.arange(E)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # slot within expert, global
    pos_in_e = (pos * onehot).sum(-1)  # [T*k]
    slot = (pos_in_e[:, None] == jnp.arange(C)[None, :])  # [T*k, C] one-hot
    pair = onehot.astype(bool)[:, :, None] & slot[:, None, :]  # [T*k, E, C]
    dispatch = pair.reshape(T, k, E, C).sum(1).astype(cfg.dtype)
    combine = (pair.reshape(T, k, E, C)
               * top_p.reshape(T, k, 1, 1)).sum(1).astype(cfg.dtype)
    w_gate = (ep["w_gate"].astype(cfg.dtype)
              if cfg.activation == "silu_glu" else None)
    kernels = (w_gate, ep["w_up"].astype(cfg.dtype),
               ep["w_down"].astype(cfg.dtype))
    return collective_moe_apply(
        tokens, combine, dispatch, kernels, activation=cfg.activation,
        dtype=cfg.dtype, algorithm=cfg.moe_dispatch_algorithm,
        codec=cfg.moe_wire_codec)


def _gmm_padded(lhs, rhs, group_sizes, interpret: bool = False):
    """megablox ``gmm`` with the row count padded to the m-tile: gmm requires
    ``m % tm == 0``, so pad lhs with zero rows credited to the LAST group
    (zero rows produce zero outputs, sliced off after)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, K = lhs.shape
    tm = min(128, -(-m // 8) * 8)  # sublane-aligned tile, capped at 128
    m_p = -(-m // tm) * tm
    if m_p != m:
        lhs = jnp.pad(lhs, ((0, m_p - m), (0, 0)))
        group_sizes = group_sizes.at[-1].add(m_p - m)
    out = gmm(lhs, rhs, group_sizes.astype(jnp.int32),
              preferred_element_type=lhs.dtype,
              tiling=(tm, min(128, K), min(128, rhs.shape[-1])),
              interpret=interpret)
    return out[:m]


def _grouped_matmul(lhs, rhs, group_sizes):
    """``lhs[rows of group g] @ rhs[g]`` for expert-contiguous rows.

    TPU (dims permitting): the megablox Pallas grouped-matmul kernel
    (tile-skips at group boundaries — the reference's ``cutlass_ops`` grouped
    GEMM analog). Elsewhere: ``lax.ragged_dot`` (XLA-CPU lowers it densely
    over groups; correct, and only the fallback)."""
    K, N = lhs.shape[1], rhs.shape[-1]
    if jax.default_backend() == "tpu" and K % 128 == 0 and N % 128 == 0:
        return _gmm_padded(lhs, rhs, group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def _moe_ragged(cfg: TransformerConfig, ep, tokens, top_p, top_i):
    """Grouped-GEMM expert dispatch: [T*k] (token, expert) pairs sorted by
    expert, expert-contiguous matmuls via :func:`_grouped_matmul`, weighted
    scatter-add combine. Exact same math as the dense-combine path (sum
    reordering only)."""
    T, M = tokens.shape
    E, k = cfg.num_experts, cfg.moe_top_k
    e_flat = top_i.reshape(-1)                       # [T*k]
    order = jnp.argsort(e_flat, stable=True)
    tok_idx = (jnp.arange(T * k) // k)[order]        # source token per pair
    gates = top_p.reshape(-1)[order].astype(cfg.dtype)
    group_sizes = jnp.bincount(e_flat, length=E)

    xg = tokens[tok_idx]                             # [T*k, M] gather
    up = _grouped_matmul(xg, ep["w_up"].astype(cfg.dtype), group_sizes)
    if cfg.activation == "silu_glu":
        h = jax.nn.silu(_grouped_matmul(
            xg, ep["w_gate"].astype(cfg.dtype), group_sizes)) * up
    else:
        h = act_fn(cfg.activation)(up)
    out_g = _grouped_matmul(h, ep["w_down"].astype(cfg.dtype), group_sizes)
    out = jnp.zeros((T, M), out_g.dtype)
    return out.at[tok_idx].add(out_g * gates[:, None])


def _cached_attention(q, ck, cv, kv_mask, q_positions, alibi=None):
    """GQA attention of new queries against the full cache.

    q: [B,S,H,hd]; ck/cv: [B,maxS,kvH,hd]; kv_mask: [B,maxS] valid slots;
    q_positions: [B,S] global position of each query. Causality: query at
    position p sees cache slot t iff slot_pos(t) <= p; because slots are
    written in position order, slot index == position, so the mask is
    ``t <= q_positions`` ∧ kv_mask. ``alibi``: per-head slopes [H]; slot
    index == position, so the bias is slopes * t (HF bloom convention —
    softmax cancels the per-row offset vs slopes*(t-p)).
    """
    B, S, H, hd = q.shape
    kvH = ck.shape[2]
    G = H // kvH
    qg = q.reshape(B, S, kvH, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, ck).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    t_idx = jnp.arange(ck.shape[1])
    if alibi is not None:
        scores = scores + (alibi.reshape(kvH, G)[None, :, :, None, None]
                           * t_idx.astype(jnp.float32)[None, None, None, None, :])
    ok = (t_idx[None, None, :] <= q_positions[:, :, None]) & kv_mask[:, None, :]
    scores = jnp.where(ok[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    ctx = jnp.einsum("bkgst,btkd->bskgd", probs, cv)
    return ctx.reshape(B, S, H, hd)


def _block_step(lp, cfg: TransformerConfig, x, ck, cv, kv_mask, positions, write_start):
    """One decoder block over S new tokens with cache read/write.

    Returns (x_out, new_k_slab, new_v_slab) where the slabs are the K/V of the
    new tokens (caller merges into the cache — keeps this fn scan-friendly).
    """
    h = _apply_norm(lp["attn_norm"], cfg, x)
    q, k, v = _qkv(lp["attn"], cfg, h)
    alibi = None
    if cfg.position == "rope":
        from deepspeed_tpu.models.transformer import apply_qk_rope

        q, k = apply_qk_rope(cfg, q, k, positions)
    elif cfg.position == "alibi":
        from deepspeed_tpu.models.transformer import alibi_slopes

        alibi = alibi_slopes(cfg.num_heads)

    # merge new K/V into cache at per-row write offsets
    ck = _write_cache(ck, k.astype(ck.dtype), write_start)
    cv = _write_cache(cv, v.astype(cv.dtype), write_start)
    ctx = _cached_attention(q, ck, cv, kv_mask, positions, alibi=alibi)
    attn_out = _attn_out(lp["attn"], cfg, ctx)

    if cfg.parallel_block:
        # falcon-style: attn and FFN both read the shared input norm `h`;
        # gpt-neox-style (parallel_mlp_norm): FFN reads its own norm of x
        if cfg.parallel_mlp_norm:
            h = _apply_norm(lp["mlp_norm"], cfg, x)
        ffn = _moe(lp["moe"], cfg, h) if cfg.num_experts > 0 else _mlp(lp["mlp"], cfg, h)
        return x + attn_out + ffn, ck, cv
    x = x + attn_out
    h = _apply_norm(lp["mlp_norm"], cfg, x)
    if cfg.num_experts > 0:
        x = x + _moe(lp["moe"], cfg, h)
    else:
        x = x + _mlp(lp["mlp"], cfg, h)
    return x, ck, cv


def _write_cache(cache: jax.Array, new: jax.Array, start: jax.Array) -> jax.Array:
    """Write ``new`` [B,S,kvH,hd] into ``cache`` [B,maxS,kvH,hd] at per-row
    offsets ``start`` [B] (vmapped dynamic_update_slice — one fused scatter)."""

    def row(c, n, s):
        return jax.lax.dynamic_update_slice(c, n, (s, 0, 0))

    return jax.vmap(row)(cache, new, start)


def _layer_stack(params, cfg, x, cache: KVCache, positions, write_start, kv_mask):
    """Run all layers via lax.scan over stacked layer params + cache slabs."""
    if "layers" not in params:
        raise ValueError("inference requires scan_layers=True stacked params ('layers')")

    def body(carry, xs):
        x = carry
        lp, ck, cv = xs
        x, ck, cv = _block_step(lp, cfg, x, ck, cv, kv_mask, positions, write_start)
        return x, (ck, cv)

    x, (k_new, v_new) = jax.lax.scan(body, x, (params["layers"], cache.k, cache.v))
    return x, cache._replace(k=k_new, v=v_new)


@jax.named_scope("lm_head")  # names the serving head in a device trace
def _logits(params, cfg: TransformerConfig, x):
    x = _apply_norm(params["final_norm"], cfg, x)
    if cfg.tie_embeddings:
        return x @ params["embed"]["embedding"].T.astype(cfg.dtype)
    logits = x @ params["lm_head"]["kernel"].astype(cfg.dtype)
    if "bias" in params["lm_head"]:
        logits = logits + params["lm_head"]["bias"].astype(cfg.dtype)
    return logits


# ------------------------------------------------------------------ api
def prefill_inputs(params, cfg: TransformerConfig, input_ids, prompt_mask):
    """Shared pre-layer computation of the prefill path: embeddings, per-row
    positions, and lengths (used by both the scan forward below and the
    NVMe layer-streamed forward — one definition, no drift)."""
    prompt_mask = prompt_mask.astype(jnp.bool_)
    lengths = prompt_mask.sum(axis=1).astype(jnp.int32)
    positions = jnp.where(prompt_mask, jnp.cumsum(prompt_mask, axis=1) - 1, 0).astype(jnp.int32)
    x = _embed_tokens(params, cfg, input_ids)
    return x, positions, lengths


def decode_inputs(params, cfg: TransformerConfig, cache: KVCache, tokens):
    """Shared pre-layer computation of the decode path: next-token embedding
    (in cfg.dtype), positions, and the kv_mask with the new slot marked."""
    positions = cache.lengths[:, None]  # [B,1]
    x = jnp.take(params["embed"]["embedding"], tokens[:, None], axis=0).astype(cfg.dtype)
    if cfg.embed_norm:
        x = _apply_norm(params["embed_norm"], cfg, x)
    if cfg.position == "learned":
        x = x + jnp.take(params["pos_embed"], positions, axis=0).astype(cfg.dtype)
    kv_mask = jax.vmap(lambda m, i: m.at[i].set(True))(cache.kv_mask, cache.lengths)
    return x, positions, kv_mask


def prefill(
    params,
    cfg: TransformerConfig,
    cache: KVCache,
    input_ids: jax.Array,
    prompt_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, KVCache]:
    """Process right-padded prompts; returns (last-token logits [B,V], cache).

    Reference analog: the first forward of ``InferenceEngine`` /
    ``DeepSpeedTransformerInference`` that fills the KV workspace.
    """
    B, S = input_ids.shape
    if prompt_mask is None:
        prompt_mask = jnp.ones((B, S), jnp.bool_)
    prompt_mask = prompt_mask.astype(jnp.bool_)
    x, positions, lengths = prefill_inputs(params, cfg, input_ids, prompt_mask)
    kv_mask = jnp.zeros((B, cache.max_len), jnp.bool_).at[:, :S].set(prompt_mask)
    write_start = jnp.zeros((B,), jnp.int32)
    x, cache = _layer_stack(params, cfg, x, cache, positions, write_start, kv_mask)
    cache = cache._replace(kv_mask=kv_mask, lengths=lengths)

    logits = _logits(params, cfg, x)  # [B, S, V]
    last = jnp.take_along_axis(logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return last, cache


def decode_step(
    params, cfg: TransformerConfig, cache: KVCache, tokens: jax.Array
) -> Tuple[jax.Array, KVCache]:
    """One token per row: tokens [B] -> (logits [B,V], cache).

    The generated token's position is ``cache.lengths`` (per row).
    """
    x, positions, kv_mask = decode_inputs(params, cfg, cache, tokens)
    x, cache = _layer_stack(params, cfg, x, cache, positions, cache.lengths, kv_mask)
    cache = cache._replace(kv_mask=kv_mask, lengths=cache.lengths + 1)
    return _logits(params, cfg, x)[:, 0], cache
