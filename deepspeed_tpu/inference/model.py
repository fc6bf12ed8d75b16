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

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.transformer import (
    TransformerConfig,
    _apply_norm,
    _embed_tokens,
    _norm_at,
    _times,
    act_fn,
    reading,
)
from deepspeed_tpu.ops.registry import register


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
    if cfg.eva_window:
        raise NotImplementedError(
            "EVA attention (eva_window > 0) in the v1 engine: its dense cache holds one row a "
            "position and has no summaries; serve it through InferenceEngineV2")
    if cfg.hc_mult or cfg.latent_attention:
        raise NotImplementedError(
            "hyper-connections (hc_mult > 0) and latent attention (kv_lora_rank > 0) in the v1 engine: "
            "its block step is the one-stream residual over plain keys and values; serve them "
            "through InferenceEngineV2, whose paged path reads ops/mhc.py and the latent pool")
    if cfg.sliding is not None:
        raise NotImplementedError(
            "a sliding kind (sliding_attention layers) in the v1 engine: its dense cache holds a row a position "
            "a layer at every layer and knows no window; serve it through InferenceEngineV2, whose sliding "
            "layers write a ring of pages beside the global ones")
    if cfg.layer_types is not None or (cfg.residual_multiplier, cfg.attention_multiplier) != (1.0, None):
        raise NotImplementedError(
            "a layer pattern (layer_types) or a residual/attention multiplier in the v1 engine: its cache is "
            "a row a position a layer and its block step one kind of layer with plain adds and scores; a "
            "state-space layer keeps a recurrent state a sequence. Serve it through InferenceEngineV2, "
            "which holds a state pool beside its page pool")
    hd = cfg.dims_per_head
    shape = (cfg.num_layers, batch_size, max_len, cfg.kv_heads, hd)
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        kv_mask=jnp.zeros((batch_size, max_len), jnp.bool_),
        lengths=jnp.zeros((batch_size,), jnp.int32),
    )


# ------------------------------------------------------------------ layers
def _dense(lp, key: str, cfg: TransformerConfig, x, einsum: Optional[str] = None, sums=None):
    """``x`` through the projection ``lp[key]`` (kernel, and bias where it has
    one), under the device-trace scope of that key (``reading``). ``sums``: the
    dtype the product's sums are handed over in (None: the operands')."""
    with reading(lp, key) as p:
        w = p["kernel"].astype(cfg.dtype)
        if sums is not None:
            out = jnp.matmul(x, w, preferred_element_type=sums)
        else:
            out = x @ w if einsum is None else jnp.einsum(einsum, x, w)
        return out + p["bias"].astype(cfg.dtype) if "bias" in p else out


def _qkv(lp, cfg: TransformerConfig, x):
    """Project hidden states to q/k/v using the training params.

    Matches ``nn.DenseGeneral`` in ``models/transformer.py:142-147``:
    kernel shapes wq [E,H,hd], wk/wv [E,kvH,hd]; bias present iff layernorm
    family (GPT-2 style).
    """
    return tuple(_dense(lp, key, cfg, x, "bse,ehd->bshd") for key in ("wq", "wk", "wv"))


def _attn_out(lp, cfg: TransformerConfig, ctx):
    return _dense(lp, "wo", cfg, ctx, "bshd,hde->bse")


def _mlp(lp, cfg: TransformerConfig, x):
    if cfg.activation == "silu_glu":
        h = jax.nn.silu(_dense(lp, "w_gate", cfg, x)) * _dense(lp, "w_up", cfg, x)
    else:
        h = act_fn(cfg.activation)(_dense(lp, "w_up", cfg, x))
    return _dense(lp, "w_down", cfg, h)


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
    every token and is added unweighted, or times ``sigmoid(x . shared_gate)``
    where the layer has that gate (``lp["shared_gate"]``), or as the mean of
    the shared experts (``cfg.moe_shared_average``).

    A chip's share of the layer (``cfg.expert_parallel``): the router scores
    and picks among all ``cfg.router_experts``, weights renormalised over all
    the picks, and the picks come back in that numbering; of the sum over a
    token's picks the terms of the ``cfg.num_experts`` experts held here are
    computed, the others' left out. Nothing stands in for the other chips. The
    regime below is then chosen as the uncut layer would choose it (``T >= 2 x``
    the ROUTER's experts: a held expert's mean group is the uncut layer's).

    Two dispatch regimes, chosen by the (static) token count:

    - decode (few tokens): every row through the experts SOME row picked,
      combined by the gate weights (zero where a row did not pick). A step's
      time is the experts' bytes, and the rows pick fewer experts than there
      are (128 rows of the benchmark's share touch 33 of 64, 64 rows of top-4
      touch 56, 8 rows about 25): on the TPU, where the layer scan hands the
      stacked weights (:class:`ExpertStack`), the kernel ``moe_decode`` reads
      each touched expert once from the stack where it lies
      (``ops/pallas/moe_decode.py``); elsewhere one einsum over all the
      stacked expert params (reference ``moe/sharded_moe.py`` combine).
      Gathering ROWS by expert would save products the step does not wait
      for.
    - prefill (T >= 2E tokens): RAGGED dispatch (round 5; reference FastGen's
      ``inference/v2/kernels/ragged_ops`` moe_gather/moe_scatter +
      ``cutlass_ops`` grouped GEMM) — sort the (token, expert) pairs by
      expert, gather the tokens' rows into that order, run grouped matmuls
      (:func:`_grouped_matmul`: on the TPU the megablox Pallas kernel at
      tiles picked from the call's shapes, elsewhere ``lax.ragged_dot``),
      gather each token's k rows back and sum them over k
      (:func:`_moe_ragged`), so prompt FFN FLOPs scale with top_k, not E
      (8x2 Mixtral-style: 4x fewer).

    Device-trace scopes: ``moe_router``, ``moe_experts`` (in a prefill
    ``moe_dispatch`` and ``moe_combine`` inside it, around the grouped
    matmuls), ``moe_shared`` (the caller opens ``moe`` around the layer).
    """
    from deepspeed_tpu.parallel.moe import route

    B, S, M = x.shape
    tokens = x.reshape(B * S, M)
    k = cfg.moe_top_k
    with jax.named_scope("moe_router"):
        logits = tokens.astype(jnp.float32) @ lp["gate"]["wg"]["kernel"].astype(jnp.float32)
        top_p, top_i = route(logits, k, kind=cfg.moe_router, bias=lp["gate"].get("e_bias"),
                             renormalize=cfg.moe_renormalize, scale=cfg.moe_routed_scale)
    with jax.named_scope("moe_experts"):
        out = _experts(lp["experts"], cfg, tokens, top_p, top_i)
    if "shared" in lp:
        with jax.named_scope("moe_shared"):
            shared = _mlp(lp["shared"], cfg, tokens)
            if "shared_gate" in lp:
                with jax.named_scope("moe_shared_gate"):
                    gate = tokens.astype(jnp.float32) @ lp["shared_gate"]["kernel"].astype(jnp.float32)
                    shared = shared * jax.nn.sigmoid(gate).astype(shared.dtype)
            if cfg.moe_shared_average:  # the mean of the shared experts: the one GLU over their widths, divided
                shared = shared * jnp.asarray(1.0 / cfg.moe_shared_experts, shared.dtype)
            out = out + shared
    return out.reshape(B, S, M), top_i


class ExpertStack(NamedTuple):
    """A routed layer's experts as row ``index`` of the scanned layers'
    STACKED leaves (``w_up`` and ``w_gate`` ``[layers, E, M, H]``, ``w_down``
    ``[layers, E, H, M]``), where a layer scan closes over them
    (``inference/paged.py::_forward_hidden``): the decode product's kernel reads
    the picked experts from the stack where it lies, and a custom call handed a
    layer's slice would have the slice copied for it. Every other path takes
    :meth:`layer`, which XLA fuses into what reads it as it does a scan's own
    slice."""

    stack: Any
    index: jax.Array

    def layer(self):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, self.index, 0, keepdims=False), self.stack)


def _experts(ep, cfg: TransformerConfig, tokens, top_p, top_i):
    """The picked experts' weighted sum for ``tokens`` [T, M]: of a chip's
    share (``cfg.expert_parallel``) the terms of the experts held here.
    ``ep``: the layer's experts, or an :class:`ExpertStack`."""
    T, E = tokens.shape[0], cfg.num_experts
    stack, ep = (ep, ep.layer()) if isinstance(ep, ExpertStack) else (None, ep)
    if cfg.expert_parallel is not None:
        # by the held experts' own numbers 0..E-1; a pick that lives on another
        # chip becomes E, which no group, one-hot or scatter takes, at weight 0
        local = top_i - cfg.first_expert
        held = (local >= 0) & (local < E)
        top_i, top_p = jnp.where(held, local, E), jnp.where(held, top_p, 0.0)
        if T >= 2 * cfg.router_experts:  # the uncut layer's rule: the mean group is the same, a chip's share of it
            # every pair has a row of the sorted gather whether its expert is here or not, so a long
            # prefill's tokens go a group at a time (a (128, 256) call: 1.3 GB a gather at once)
            n = 1
            # (no more pairs a group than 1 GiB of gathered rows: at a hidden width of 6,144, 87,381)
            pairs = min(_SHARE_GROUP_PAIRS, _SHARE_GROUP_BYTES // (tokens.shape[1] * tokens.dtype.itemsize))
            while T * cfg.moe_top_k > n * pairs and T % (2 * n) == 0 and T // (2 * n) >= 2 * E:
                n *= 2
            if n == 1:
                return _moe_ragged(cfg, ep, tokens, top_p, top_i, held)
            grouped = tuple(a.reshape((n, T // n) + a.shape[1:]) for a in (tokens, top_p, top_i, held))
            return jax.lax.map(lambda g: _moe_ragged(cfg, ep, *g), grouped).reshape(tokens.shape)
        gate = jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], top_i].set(top_p, mode="drop")
        return _decode_experts(stack, ep, cfg, tokens, gate)
    if _moe_ep_size() > 1:
        # expert-parallel serving (ISSUE 15): the ep-sharded experts are
        # reached through the explicit collective dispatch — the SAME
        # facade all_to_all the training path rides. Falls back to the
        # replicated paths below (GSPMD reshards the ep-sharded kernels)
        # only on non-divisible shapes.
        out = _moe_ep_collective(cfg, ep, tokens, top_p, top_i)
        if out is not None:
            return out
    if T >= 2 * E:
        return _moe_ragged(cfg, ep, tokens, top_p, top_i)

    gate = jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], top_i].set(top_p)
    return _decode_experts(stack, ep, cfg, tokens, gate)


def _decode_experts(stack: Optional[ExpertStack], ep, cfg: TransformerConfig, tokens, gate):
    """The decode regime's product, ``gate`` [T, E] float32 zero where a row
    did not pick: on the TPU, where the scan handed the stack and the kernel
    takes the shapes, over the experts some row picked, each read once from the
    stack where it lies (``ops/pallas/moe_decode.py``); else over every expert."""
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import moe_decode  # (registers the kernel)

    glu = cfg.activation == "silu_glu"
    if stack is not None and registry._default_backend() == "tpu":
        w = stack.stack
        (M, H), tokens = w["w_up"].shape[2:], tokens.astype(cfg.dtype)
        # (a quantized leaf has no dtype of its own: it is dequantized a layer's slice at a time, below)
        if moe_decode.takes(tokens.shape[0], M, H, tokens.dtype, getattr(w["w_up"], "dtype", None)):
            return registry.dispatch("moe_decode", "pallas")(
                tokens, gate, w["w_gate"] if glu else None, w["w_up"], w["w_down"], stack.index, cfg.activation)
    return _all_experts(tokens, gate, ep["w_gate"].astype(cfg.dtype) if glu else None,
                        ep["w_up"].astype(cfg.dtype), ep["w_down"].astype(cfg.dtype), None, cfg.activation)


@register("moe_decode", "xla")
def _all_experts(tokens, gate, w_gate, w_up, w_down, layer, activation: str):
    """Every expert for every token, combined by ``gate`` [T, E]: ``w_up``
    (and ``w_gate``, or None) ``[E, M, H]`` and ``w_down`` ``[E, H, M]``, or
    row ``layer`` of them stacked."""
    if layer is not None:
        w_gate, w_up, w_down = (None if w is None else w[layer] for w in (w_gate, w_up, w_down))
    h1 = jnp.einsum("tm,emh->teh", tokens, w_up)
    if w_gate is not None:
        h1 = jax.nn.silu(jnp.einsum("tm,emh->teh", tokens, w_gate)) * h1
    else:
        h1 = act_fn(activation)(h1)
    out_e = jnp.einsum("teh,ehm->tem", h1, w_down)
    return jnp.einsum("te,tem->tm", gate.astype(w_down.dtype), out_e)


# The no-drop collective dispatch materializes [T*k, E, T*k] routing
# one-hots (capacity = T*k for exactness) — quadratic in the token count —
# from the shared router's picks and weights, whatever kind it is.
# Fine at decode/short-prefill shapes; a long prefill would OOM on the
# one-hots alone, so beyond this bound the ep>1 engine falls back to the
# replicated ragged/dense paths (GSPMD reshards the ep-sharded kernels —
# same math, no collective wire).
_MOE_EP_COLLECTIVE_MAX_TOKENS = 1024


# (token, pick) pairs of one grouped dispatch of a chip's share of a routed layer
_SHARE_GROUP_PAIRS = 2 ** 17
# and the bytes of its gathered rows (2 ** 17 pairs of 4,096 columns in bf16: what the cells before PR 55 reach at most)
_SHARE_GROUP_BYTES = 2 ** 30


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
        dtype=cfg.dtype)


# A kernel may scope 16 MiB of the chip's VMEM unless it asks for more, and the
# megablox calls ask for nothing. `_gmm_vmem_bytes` is an ESTIMATE of what
# Mosaic scopes for a forward step and bounds it from neither side (Mosaic
# refused a step that counts 13.5 MiB there as 16.7 of its own, and took one
# that counts 17), so its budget is a heuristic. `_tgmm_vmem_bytes` gave
# Mosaic's own figure at each of the three steps it refused (17.50, 17.00 and
# 18.25 MiB) and 14.75 at the largest it took. Either way the guard is
# `tests/unit/ops/test_chip_compile.py`, which compiles both passes.
_GMM_VMEM_BUDGET = 12 * 2 ** 20
_TGMM_VMEM_BUDGET = 15 * 2 ** 20


def _gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """About what one ``(tm, tk, tn)`` step of megablox ``gmm`` holds in
    VMEM: the lhs, rhs and out blocks double-buffered by the pipeline, the
    fp32 accumulator and the fp32 product before it is added."""
    return 2 * (tm * tk + tk * tn + tm * tn) * itemsize + 2 * tm * tn * 4


def _tgmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """What one step of megablox ``tgmm`` (``lhs[rows of g].T @ grad[rows of
    g]`` a group) scopes: the ``[tm, tk]`` and ``[tm, tn]`` operand blocks and
    the ``[tk, tn]`` out block double-buffered, and the fp32 accumulator."""
    return 2 * (tm * tk + tm * tn + tk * tn) * itemsize + tk * tn * 4


def _lane_divisors(n: int, cap: int) -> list:
    """Divisors of ``n`` that are multiples of 128 and at most ``cap``, largest
    first; ``[n]`` where it has none (a block may always span a whole dim)."""
    return [d for d in range(min(cap, n) // 128 * 128, 0, -128) if n % d == 0] or [n]


def _row_tile(m: int, E: int, share: int, itemsize: int, fits) -> int:
    """The row tile of a grouped kernel: a tile that spans g groups is visited
    g times under a mask, so the MXU's work is about ``m * (1 + tm / (m / E))``.
    The largest power of two within ``1 / share`` of the mean group ``m / E``,
    in [128, 512], that ``fits``; a tiny ``m`` takes one tile of its own rows,
    rounded up to the dtype's sublane packing."""
    sublanes = 32 // itemsize  # rows of one packed (sublanes, 128) tile
    tm = 128
    while tm * 2 <= min(m // E // share, 512) and fits(tm * 2):
        tm *= 2
    return min(tm, -(-m // sublanes) * sublanes)


def _gmm_tiles(m: int, K: int, N: int, E: int, itemsize: int) -> Tuple[int, int, int]:
    """Tiles ``(tm, tk, tn)`` of the grouped matmul ``[m, K] x [E, K, N]``,
    a pure function of the call's static shapes.

    The kernel's grid is ``(N / tn, m / tm + boundary tiles, K / tk)``, n
    outermost and k innermost, and a step costs about a third of a microsecond
    whatever it multiplies (110,208 steps of 128 x 128 x 128 were 5% of the
    MXU's peak), so the tiles are as large as the work allows. Measured on
    the v5e at ``[65536, 2048] x [64, 2048, 1536]`` (PERF.md, PR 34):

    - ``tk`` is the whole K where a step then fits ``_GMM_VMEM_BUDGET``: no k
      loop, no remainder mask, one store a tile, and a group's ``[K, tn]``
      weights stay in VMEM over all of its row tiles. Halving it cost 30-60%:
      the weights are then fetched again at every row tile.
    - ``tn`` is the largest divisor of N that is a multiple of 128, up to
      1024, that fits beside it (the lhs is read ``N / tn`` times; 256 to
      512 gained 7-24%, wider another 3-5%).
    - ``tm`` follows the mean rows a group, ``m / E``: a tile that spans g
      groups is visited g times under a mask, so the MXU's work is about
      ``m * (1 + tm / (m / E))``. The largest power of two within a quarter
      of the mean group, in [128, 512] (at 1,024 rows a group 256 beat 128 by
      2% and 512 by 8%); a tiny ``m`` takes one tile of its own rows, rounded
      up to the dtype's sublane packing.
    - Where K does not fit whole beside a ``tn`` of 256 or more (under that the
      lhs' stream falls below the chip's 240 FLOPs a byte), K is split: the
      weights' stream then has ``tm`` FLOPs a byte, so ``tm`` goes up to the
      mean group itself, ``tn`` to 512, and ``tk`` is the largest 128-multiple
      divisor of K that fits.
    """
    def fits(tm, tk, tn):
        return _gmm_vmem_bytes(tm, tk, tn, itemsize) <= _GMM_VMEM_BUDGET

    for tn in _lane_divisors(N, 1024):
        if tn >= min(N, 256) and fits(128, K, tn):
            return _row_tile(m, E, 4, itemsize, lambda tm: fits(tm, K, tn)), K, tn
    tn = _lane_divisors(N, 512)[0]
    tm = _row_tile(m, E, 1, itemsize, lambda tm: fits(tm, 128, tn))
    tks = _lane_divisors(K, K)
    return tm, next((tk for tk in tks if fits(tm, tk, tn)), tks[-1]), tn


def _tgmm_tiles(m: int, K: int, N: int, E: int, itemsize: int) -> Tuple[int, int, int]:
    """Tiles ``(tm, tk, tn)`` of the weights' gradient ``[E, K, N]``, from the
    same static shapes. ``tgmm``'s grid is ``(N / tn, K / tk, row tiles)``,
    rows innermost: a ``[tk, tn]`` fp32 accumulator a group stays in VMEM over
    the group's rows while both operand blocks change at every step, so the
    lhs is read ``N / tn`` times and the cotangent ``K / tk`` times: a step
    has ``tk * tn / (tk + tn)`` FLOPs a byte of bf16 whatever ``tm`` is, and
    the time follows it (v5e, ``[65536, 2048]`` and ``[65536, 1536]`` a group
    of 1,024, PERF.md PR 34: 5.73 ms at 512 x 512, 4.29 at 1024 x 768, 3.97
    at 2048 x 768). ``tm`` follows the mean group as in :func:`_gmm_tiles`
    (256 beat 512 by 2-8%); ``tk`` and ``tn`` are the 128-multiple divisors
    with the most FLOPs a byte that fit ``_TGMM_VMEM_BUDGET`` beside it."""
    tm = _row_tile(m, E, 4, itemsize, lambda tm: True)
    fit = [(tk * tn / (tk + tn), tk, tn) for tk in _lane_divisors(K, K) for tn in _lane_divisors(N, N)
           if _tgmm_vmem_bytes(tm, tk, tn, itemsize) <= _TGMM_VMEM_BUDGET]
    _, tk, tn = max(fit)
    return tm, tk, tn


def _pad_rows(x, group_sizes, tm: int):
    """The grouped kernels require ``m % tm == 0``: pad ``x`` with zero rows
    credited to the LAST group (zero rows give zero outputs and add nothing
    to a weight's gradient)."""
    pad = -x.shape[0] % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        group_sizes = group_sizes.at[-1].add(pad)
    return x, group_sizes.astype(jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_padded(lhs, rhs, group_sizes, interpret: bool = False):
    """megablox ``gmm`` at the tiles :func:`_gmm_tiles` picks from the call's
    shapes, with the row count padded to the m-tile (:func:`_pad_rows`; the
    padding is sliced off after). bf16 (or whatever ``lhs`` is) in and out,
    fp32 accumulation.

    The gradient is this function's own, not the library's ``custom_vjp``:
    that one hands the forward's tiles to both backward kernels, where ``tk``
    lands on N and ``tn`` on K in the transposed product and ``tgmm`` keeps a
    ``[tk, tn]`` accumulator (Mosaic refused it at mixtral's widths). Here
    each kernel's tiles come from its own shapes: ``d lhs = grad @ rhs[g].T``
    is the forward's kernel at ``[m, N] x [E, N, K]``, so :func:`_gmm_tiles`
    of those; ``d rhs`` is ``tgmm`` at :func:`_tgmm_tiles`."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, K = lhs.shape
    E, _, N = rhs.shape
    tiling = _gmm_tiles(m, K, N, E, lhs.dtype.itemsize)
    lhs, sizes = _pad_rows(lhs, group_sizes, tiling[0])
    return gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype, tiling=tiling,
               interpret=interpret)[:m]


def _gmm_padded_fwd(lhs, rhs, group_sizes, interpret):
    return _gmm_padded(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _gmm_padded_bwd(interpret, residual, grad):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, rhs, group_sizes = residual
    m, K = lhs.shape
    E, _, N = rhs.shape
    tiling = _gmm_tiles(m, N, K, E, grad.dtype.itemsize)
    g, sizes = _pad_rows(grad, group_sizes, tiling[0])
    d_lhs = gmm(g, rhs, sizes, preferred_element_type=lhs.dtype, tiling=tiling,
                transpose_rhs=True, interpret=interpret)[:m]
    tiling = _tgmm_tiles(m, K, N, E, lhs.dtype.itemsize)
    g, sizes = _pad_rows(grad, group_sizes, tiling[0])
    d_rhs = tgmm(_pad_rows(lhs, group_sizes, tiling[0])[0].swapaxes(0, 1), g, sizes,
                 preferred_element_type=rhs.dtype, tiling=tiling, interpret=interpret)
    return d_lhs, d_rhs, None


_gmm_padded.defvjp(_gmm_padded_fwd, _gmm_padded_bwd)


def _grouped_matmul(lhs, rhs, group_sizes):
    """``lhs[rows of group g] @ rhs[g]`` for expert-contiguous rows.

    TPU (dims permitting): the megablox Pallas grouped-matmul kernel
    (tile-skips at group boundaries — the reference's ``cutlass_ops`` grouped
    GEMM analog) at the tiles :func:`_gmm_tiles` picks from the shapes.
    Elsewhere: ``lax.ragged_dot`` (XLA-CPU lowers it densely over groups;
    correct, and only the fallback)."""
    K, N = lhs.shape[1], rhs.shape[-1]
    if jax.default_backend() == "tpu" and K % 128 == 0 and N % 128 == 0:
        return _gmm_padded(lhs, rhs, group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def _moe_ragged(cfg: TransformerConfig, ep, tokens, top_p, top_i, held=None):
    """Grouped-GEMM expert dispatch: sort the [T*k] (token, expert) pairs by
    expert, gather the tokens' rows into that order, run the
    expert-contiguous matmuls (:func:`_grouped_matmul`), gather each token's
    k rows back and sum them over k (:func:`_gather_combine`). Exact same
    math as the dense-combine path (sum reordering only). Device-trace
    scopes: ``moe_dispatch`` and ``moe_combine`` (the caller opens
    ``moe_experts`` around both and the matmuls between them). ``held`` [T, k]
    bool (a chip's share): the pairs whose expert is here; the others carry
    expert ``E``, sort behind every group and belong to none, so no product is
    made for them, and the combine leaves their rows out."""
    E, k = cfg.num_experts, cfg.moe_top_k
    with jax.named_scope("moe_dispatch"):
        e_flat = top_i.reshape(-1)                   # [T*k]
        order = jnp.argsort(e_flat, stable=True)     # sorted row -> pair t*k + j
        group_sizes = jnp.bincount(e_flat, length=E)
        xg = tokens[order // k]                      # [T*k, M] gather: each pair's token

    up = _grouped_matmul(xg, ep["w_up"].astype(cfg.dtype), group_sizes)
    if cfg.activation == "silu_glu":
        h = jax.nn.silu(_grouped_matmul(
            xg, ep["w_gate"].astype(cfg.dtype), group_sizes)) * up
    else:
        h = act_fn(cfg.activation)(up)
    out_g = _grouped_matmul(h, ep["w_down"].astype(cfg.dtype), group_sizes)
    with jax.named_scope("moe_combine"):
        return _gather_combine(out_g, order, top_p, held)


def _gather_combine(out_g, order, top_p, held=None):
    """``out[t] = sum_j top_p[t, j] * out_g[inv[t*k + j]]`` for the experts'
    rows ``out_g`` [T*k, M] in sorted order, ``order`` the sort's permutation
    (sorted row -> pair) and ``inv`` its inverse, by a second sort: k gathers
    of ``[T, M]``, the products and the sum over k in float32, ONE rounding to
    ``out_g``'s dtype at the end. No activation is scattered: a scatter-add of
    the weighted rows collides k times a row, and XLA serialises it (v5e,
    ``[65536, 3584]`` bf16, PERF.md PR 40: 16.6 ms a call alone, this 4.5;
    one gather of ``[T, k, M]`` and a sum 7.5, by a re-layout in float32)."""
    T, k = top_p.shape
    inv = jnp.argsort(order).reshape(T, k)           # pair -> sorted row
    gates = top_p.astype(jnp.float32)
    if held is None:
        out = sum(gates[:, j:j + 1] * out_g[inv[:, j]].astype(jnp.float32) for j in range(k))
    else:  # a row no group holds was never written: it is not read as a number
        out = sum(jnp.where(held[:, j:j + 1], gates[:, j:j + 1] * out_g[inv[:, j]].astype(jnp.float32), 0.0)
                  for j in range(k))
    return out.astype(out_g.dtype)


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
    def ffn(y):
        if cfg.num_experts > 0:
            with reading(lp, "moe") as p:
                return _moe(p, cfg, y)
        with reading(lp, "mlp") as p:
            return _mlp(p, cfg, y)

    h = _norm_at(lp, "attn_norm", cfg, x)
    with reading(lp, "attn") as ap:
        q, k, v = _qkv(ap, cfg, h)
        alibi = None
        if cfg.position == "rope":
            from deepspeed_tpu.models.transformer import apply_qk_rope

            with jax.named_scope("rope"):
                q, k = apply_qk_rope(cfg, q, k, positions)
        elif cfg.position == "alibi":
            from deepspeed_tpu.models.transformer import alibi_slopes

            alibi = alibi_slopes(cfg.num_heads)

        # merge new K/V into cache at per-row write offsets
        ck = _write_cache(ck, k.astype(ck.dtype), write_start)
        cv = _write_cache(cv, v.astype(cv.dtype), write_start)
        ctx = _cached_attention(q, ck, cv, kv_mask, positions, alibi=alibi)
        attn_out = _attn_out(ap, cfg, ctx)

    if cfg.parallel_block:
        # falcon-style: attn and FFN both read the shared input norm `h`;
        # gpt-neox-style (parallel_mlp_norm): FFN reads its own norm of x
        if cfg.parallel_mlp_norm:
            h = _norm_at(lp, "mlp_norm", cfg, x)
        return x + attn_out + ffn(h), ck, cv
    x = x + attn_out
    return x + ffn(_norm_at(lp, "mlp_norm", cfg, x)), ck, cv


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
    x = _norm_at(params, "final_norm", cfg, x)
    if cfg.tie_embeddings:
        return _times(1.0 / cfg.logits_scaling, x @ params["embed"]["embedding"].T.astype(cfg.dtype))
    if cfg.logits_scaling != 1.0:
        raise NotImplementedError("logits_scaling with an untied head: no model has needed it")
    if cfg.num_pred_heads > 1:
        # head 0 (the next token's) of a [hidden, heads * vocab] kernel, fp32 logits
        head = params["lm_head"]["kernel"][:, :cfg.vocab_size].astype(cfg.dtype)
        return jnp.dot(x, head, preferred_element_type=jnp.float32)
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
    x = _times(cfg.embedding_multiplier, jnp.take(params["embed"]["embedding"], tokens[:, None], axis=0).astype(cfg.dtype))
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
