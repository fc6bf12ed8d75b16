"""Decoder-only transformer family (the framework's flagship model).

The reference ships transformer implementations for inference injection
(``deepspeed/module_inject/containers/*``, ``model_implementations/``) and a
legacy fused training layer (``ops/transformer/transformer.py:296``). Here the
model is a first-class Flax module designed for TPU:

  - one config covers Llama-style (RMSNorm + RoPE + SwiGLU + GQA) and
    GPT-2-style (LayerNorm + learned positions + GELU) decoders
  - ``nn.scan`` over layers: one compiled block, stacked params (fast compile,
    XLA-friendly), optional ``nn.remat`` for activation checkpointing
    (the analog of ``runtime/activation_checkpointing``)
  - what a layer saves for its backward follows ONE rule, whatever ``remat``
    says: *a layer saves what a product or a kernel made and the inputs they
    need; what an elementwise function computed inside itself is made again
    in the backward.* The norms (``_norm``) and the MLP's activation are
    wrapped in ``_made_again`` so that only their inputs are kept: a norm's
    fp32 copy, centred and normalised value and an activation's inner terms
    cost a few VPU operations an element to make and twice their bytes of
    memory traffic to keep. The one exception was measured: the erf of
    ``gelu_exact`` is tens of VPU operations, so it keeps its slope
    (``_gelu_exact``). ``remat: true`` is the other thing, the whole block
    made again: every product and the attention kernel run twice
  - attention dispatches through the ops registry so the Pallas flash kernel
    replaces the XLA einsum path on TPU (``deepspeed_tpu/ops``)
  - ``partition_rules`` provide tensor-parallel placements (the AutoTP analog,
    reference ``module_inject/auto_tp.py:193``) that the engine composes with
    ZeRO sharding
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.runtime.model import ModelSpec


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """The sizes of a state-space (Mamba-2) mixer, ``TransformerConfig.ssm``
    (``ops/ssm.py`` has the mathematics): ``n_heads`` heads of ``head_dim``
    channels, each with a state ``[head_dim, d_state]``; ``n_groups`` groups of
    heads share ``B`` and ``C``; a causal depthwise convolution over ``d_conv``
    inputs; the chunked scan's chunk."""

    n_heads: int
    head_dim: int
    d_state: int
    n_groups: int = 1
    d_conv: int = 4
    chunk_size: int = 256

    def __post_init__(self):
        if self.n_heads % self.n_groups or self.d_conv < 2:
            raise ValueError(f"a state-space mixer needs n_heads a multiple of n_groups and d_conv >= 2, got {self}")

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: ``x``, ``B`` and ``C`` together."""
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def proj_dim(self) -> int:
        """Columns of the in-projection: ``[z | xBC | dt]``."""
        return self.d_inner + self.conv_dim + self.n_heads


@dataclasses.dataclass(frozen=True)
class GDNConfig:
    """The sizes of a Gated DeltaNet (linear-attention) mixer, ``TransformerConfig.
    gdn`` (``ops/gdn.py`` has the mathematics): ``n_k_heads`` query/key heads of
    ``head_k_dim``, ``n_v_heads`` value heads of ``head_v_dim`` (value head ``h``
    reads key head ``h // (n_v_heads / n_k_heads)``), each value head with a
    state ``[head_k_dim, head_v_dim]``; a causal depthwise convolution over
    ``d_conv`` inputs of ``[q | k | v]``; the chunked delta rule's chunk."""

    n_k_heads: int
    n_v_heads: int
    head_k_dim: int
    head_v_dim: int
    d_conv: int = 4
    chunk_size: int = 64

    def __post_init__(self):
        if self.n_v_heads % self.n_k_heads or self.d_conv < 2:
            raise ValueError(f"a Gated DeltaNet mixer needs n_v_heads a multiple of n_k_heads and d_conv >= 2, got {self}")

    @property
    def key_dim(self) -> int:
        return self.n_k_heads * self.head_k_dim

    @property
    def value_dim(self) -> int:
        return self.n_v_heads * self.head_v_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: ``q``, ``k`` and ``v`` together."""
        return 2 * self.key_dim + self.value_dim

    @property
    def proj_dim(self) -> int:
        """Columns of the in-projection: ``[q | k | v | z]``."""
        return self.conv_dim + self.value_dim


@dataclasses.dataclass(frozen=True)
class ExpertParallel:
    """One chip's share of a routed layer that ``size`` chips hold together,
    ``TransformerConfig.expert_parallel``: this one is ``rank`` and holds
    experts ``rank * num_experts .. (rank + 1) * num_experts - 1`` of the
    ``size * num_experts`` the router scores."""

    size: int
    rank: int = 0

    def __post_init__(self):
        if self.size < 1 or not 0 <= self.rank < self.size:
            raise ValueError(f"an expert-parallel share needs size >= 1 and 0 <= rank < size, got {self}")


@dataclasses.dataclass(frozen=True)
class SlidingConfig:
    """What the ``sliding_attention`` kind of a layer pattern is, beside plain
    attention's shapes (``TransformerConfig.sliding``): a query at position
    ``t`` sees the keys ``j`` with ``0 <= t - j < window`` (its own counted).
    Where ``position == "rope"`` the sliding layers rotate their queries and
    keys; ``global_rope`` says whether the pattern's ``attention`` layers,
    which see every key, rotate too (False: no position term at all there, the
    ``cohere2`` family's way).

    Where the two kinds differ in more than the band, the sliding kind's own
    shapes stand here and the ``attention`` kind keeps the model's one-number
    fields (``sliding_kind`` answers for both): ``num_kv_heads``, ``head_dim``
    (queries and keys), ``v_head_dim`` and ``rope_theta`` (None: the model's),
    and ``sink``: a learned logit a query head that joins the softmax's
    denominator and nothing else, ``p_tj = exp(a_tj) / (exp(s_h) + sum_k
    exp(a_tk))`` (the parameter ``attn/sink`` ``[num_heads]``)."""

    window: int
    global_rope: bool = True
    num_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    rope_theta: Optional[float] = None
    sink: bool = False

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"a sliding window holds at least the query's own key, got window={self.window}")


LAYER_KINDS = ("attention", "mamba", "linear_attention", "sliding_attention")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    intermediate_size: int = 1408
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: Optional[int] = None  # None => MHA
    head_dim: Optional[int] = None  # None => hidden // heads
    max_seq_len: int = 2048
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    activation: str = "silu_glu"  # silu_glu | gelu (tanh approx) | gelu_exact | relu
    # QKV-projection bias override (qwen2-style: rmsnorm model WITH qkv bias).
    # None keeps the norm-derived default (layernorm models carry biases).
    qkv_bias: Optional[bool] = None
    # Output/MLP projection bias override (falcon-style: layernorm model with
    # bias-free dense layers). None keeps the norm-derived default.
    dense_bias: Optional[bool] = None
    # Falcon-7B-style parallel residual: attn and MLP both read ONE shared
    # input layernorm and add into the residual in parallel.
    parallel_block: bool = False
    # GPT-NeoX-style parallel residual: like parallel_block but the MLP reads
    # its OWN norm of the block input (x + attn(ln1(x)) + mlp(ln2(x))).
    parallel_mlp_norm: bool = False
    position: str = "rope"  # rope | learned | alibi (bloom-style score biases) | none
    rope_theta: float = 500000.0
    # Bloom-style LayerNorm applied to the token embeddings before layer 0.
    embed_norm: bool = False
    # Partial rotary (phi-style): rope only the first rotary_dim of head_dim.
    rotary_dim: Optional[int] = None
    # GPT-J/CodeGen rotary convention: adjacent pairs rotate together
    # (rotate_every_two) instead of the half-split llama/neox rotation.
    rope_interleaved: bool = False
    # MLP bias override (gpt-j: bias-free attention but biased MLP). None
    # falls back to dense_bias / the norm-derived default.
    mlp_bias: Optional[bool] = None
    # lm_head bias (phi-style untied head); disables the fused-CE path.
    lm_head_bias: bool = False
    norm_eps: float = 1e-5
    dropout: float = 0.0
    tie_embeddings: bool = False
    remat: bool = False
    scan_layers: bool = True
    attn_impl: str = "auto"  # auto | xla | flash | sparse | fpdt
    # Block-sparse attention config (reference ``sparse_attention`` config
    # section + ``ops/sparse_attention/sparsity_config.py``): a dict like
    # {"mode": "bigbird", "block": 16, "num_random_blocks": 1, ...} consumed
    # when attn_impl == "sparse". Training runs the tile-skipping Pallas
    # kernels fwd AND bwd. Must be a hashable tuple-of-pairs internally, so
    # pass a dict and it is frozen at construction.
    sparse_attention: Optional[Any] = None
    # FPDT long-context training (reference sequence/fpdt_layer.py:510,971):
    # attn_impl == "fpdt" runs the custom-VJP chunked attention — O(Cq·Ck)
    # score tiles, never O(S²) — composing with Ulysses sp. fpdt_offload
    # additionally parks the q/k/v/out residuals in (pinned) host memory
    # between forward and backward. NOTE: the memory-space transfers are
    # rejected by the current XLA SPMD partitioner ("Side-effect HLO must
    # have sharding" on the placement annotations) — offload therefore works
    # on single-device jit only; the engine raises on multi-device meshes.
    # Multi-chip long-context = fpdt (no offload) and/or ring attention.
    fpdt_q_chunk: int = 1024
    fpdt_kv_chunk: int = 1024
    fpdt_offload: bool = False
    # Engine-wired sparse embedding gradients (reference sparse_gradients +
    # runtime/sparse_tensor.py): the embedding backward all-gathers compact
    # (ids, rows) pairs instead of psum-ing the dense [V, H] grad. Set by the
    # engine when the DS config has ``sparse_gradients: true`` and the
    # heuristic wins; incompatible with tie_embeddings (the tied LM head's
    # dense [V, H] grad would dominate anyway).
    sparse_embedding_grads: bool = False
    # Pallas attention scheduling knobs forwarded to the flash kernel when it
    # is the resolved impl (dropped on the XLA path — identical math either
    # way): {"block_q": ..., "block_k": ..., "k_splits": ...}; the kernel
    # chooses its own from the shapes when none is given
    # (tools/flash_kernel_bench.py reads them on hardware). Frozen to a
    # tuple-of-pairs at construction (configs are jit static args).
    attn_kwargs: Optional[Any] = None
    sp_impl: str = "ulysses"  # ulysses (all-to-all) | ring (ppermute) over sp
    dtype: Any = jnp.float32  # activation dtype inside the module
    # Fused chunked-vocab LM-head + cross-entropy on the training path (the
    # [tokens, vocab] logits never materialize). Auto-disabled for small
    # vocabularies where chunking buys nothing.
    fused_ce: bool = True
    fused_ce_min_vocab: int = 4096
    # MoE (0 experts => dense MLP). Mirrors reference moe/layer.py knobs.
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_min_capacity: int = 4
    moe_aux_loss_weight: float = 0.01
    moe_drop_tokens: bool = True
    # PR-MoE (reference moe/layer.py use_residual; DeepSpeed-MoE pyramid):
    # a per-layer expert-count tuple makes the stack a pyramid (0 => dense
    # layer); requires scan_layers=False (heterogeneous layers cannot scan).
    moe_use_residual: bool = False
    moe_layer_experts: Optional[Tuple[int, ...]] = None
    # Emit device-computed MoE dispatch gauges (moe/capacity_factor,
    # moe/token_drop_rate, moe/expert_load_balance — parallel/moe.py
    # MOE_STAT_KEYS) from the training forward: the loss_fn returns
    # (loss, logits, stats_dict) instead of (loss, logits). The engine flips
    # this on via rebuild when telemetry is enabled (no-op for dense models).
    moe_metrics: bool = False
    # Expert-parallel token dispatch (ISSUE 15; parallel/moe.py): how the
    # [E, C, M] dispatch/combine reshards onto ep — "auto" runs the explicit
    # shard_map + facade all_to_all path (cross-tp token gather/drop) on
    # ep x tp meshes and GSPMD constraints elsewhere; "collective"/"gspmd"
    # force one.
    moe_dispatch: str = "auto"
    # Capacity-factor autotuning ceiling (runtime moe_autotune block): when
    # set, capacity arrays are sized by THIS factor and the enforced cutoff
    # follows a traced scalar (batch key "moe_capacity_factor", threaded by
    # the engine's controller) — capacity moves between steps with the jit
    # cache staying at one program.
    moe_capacity_factor_max: Optional[float] = None
    # How a routed layer scores and weighs its experts (parallel/moe.py
    # ``route``): "softmax" takes the top-k of a softmax and renormalises
    # them; "sigmoid" takes sigmoid scores, CHOOSES by score + a per-expert
    # correction bias (a parameter, ``e_bias``) and weighs by the unbiased
    # scores, renormalised if ``moe_renormalize``, times ``moe_routed_scale``.
    # A sigmoid-routed layer is drop-free in training and serving alike.
    moe_router: str = "softmax"
    moe_renormalize: bool = True
    moe_routed_scale: float = 1.0
    # width of one routed (and one shared) expert; None => intermediate_size
    moe_intermediate_size: Optional[int] = None
    # experts every token visits beside its routed ones, as ONE silu-GLU of
    # moe_shared_experts x the expert width, added unweighted
    moe_shared_experts: int = 0
    # leading layers with a dense MLP (width intermediate_size) before the
    # routed stack: built outside the layer scan as ``dense_<i>``, the
    # ``num_layers - first_dense_layers`` routed ones scanned as ``layers``
    first_dense_layers: int = 0
    # Latent attention (kv_lora_rank > 0): queries through a rank-q_lora_rank
    # bottleneck with a norm, keys and values
    # up-projected per head from ONE normed rank-kv_lora_rank latent a token,
    # beside ONE rotary key of qk_rope_head_dim shared by all heads. A head's
    # query/key is [qk_nope_head_dim | qk_rope_head_dim], its value
    # v_head_dim; scores scale by the whole query width. Serving caches the
    # latent and the rotary key alone (inference/paged.py). Without a latent,
    # in a pattern with a sliding kind, ``v_head_dim`` is the ``attention``
    # kind's value width where it is not the key's (0: the key's)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # A learned sparse-attention indexer beside latent attention (index_topk >
    # 0; ``ops/dsa.py``): index_heads heads of index_head_dim score every cached
    # token against ONE index key a token (cached beside the latent), and a
    # query attends the index_topk positions of largest score alone; a context
    # of no more than index_topk tokens takes every candidate. 0 says none: a
    # static branch, such a model compiles as it did.
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # EVA attention (eva_window > 0; EvaByte): token t attends, under ONE
    # softmax, to the exact keys of its own window of eva_window positions
    # (causally) and to one SUMMARY a chunk of eva_chunk positions of every
    # window before it: per kv head, with learned ``phi`` and ``mu`` [kvH, hd],
    # a chunk's summary key is sum_i a_i k_i + mu and its value sum_i a_i v_i,
    # a = softmax over the chunk of k_i . phi (``ops/eva.py``). Keys carry
    # RoPE at their own absolute positions before they are pooled. Serving
    # caches a window's exact rows and earlier windows' summaries as two
    # kinds of rows of one page pool (inference/paged.py).
    eva_window: int = 0
    eva_chunk: int = 0
    # RMSNorm multiplies by 1 + scale (the parameter is the offset from one)
    norm_unit_offset: bool = False
    # the residual stream stays fp32 between layers whatever ``dtype`` is
    fp32_residual: bool = False
    # output heads: the head's kernel is [hidden, num_pred_heads * vocab],
    # head m (columns m*vocab ...) predicts token t + 1 + m. Loss and serving
    # read head 0, the next-token distribution
    num_pred_heads: int = 1
    # dtype the parameters are CREATED in (a checkpoint's own, where it says)
    param_dtype: Any = jnp.float32
    # Hyper-connections (hc_mult > 0; mHC, ``ops/mhc.py``): the residual is
    # hc_mult streams a token; each sublayer reads a learned per-token mix of
    # them and writes back through a doubly stochastic hc_mult x hc_mult matrix
    # made by hc_sinkhorn_iters Sinkhorn rounds (hc_eps added to each sum) of
    # exp of logits clamped to hc_res_clamp. 0 is the one-stream residual
    # ``x + f(norm(x))``, a static branch: such a model compiles as it did.
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    # a checkpoint's ``rope_scaling`` dict; type "yarn" alone is taken (the
    # DeepSeek-V2/V3 formulae, ``yarn_frequencies``), by latent attention alone
    rope_scaling: Optional[Any] = None
    # A layer PATTERN: the kind of every layer's mixer, ``LAYER_KINDS``
    # ("attention": ``Attention``; "mamba": ``Mamba2Mixer`` at the sizes
    # ``ssm``), each followed by the dense MLP. The stack is scanned over whole
    # periods of the pattern (``period``), a period's layers unrolled in the
    # scan's body as ``layers/layer_<j>``. None is the homogeneous stack, the
    # scan over ``Block`` as it always was.
    layer_types: Optional[Tuple[str, ...]] = None
    ssm: Optional[SSMConfig] = None
    # "linear_attention": ``GatedDeltaNet`` at the sizes ``gdn``. A pattern may
    # have a routed MLP (``num_experts`` > 0) in every layer, drop-free
    gdn: Optional[GDNConfig] = None
    # "sliding_attention": ``Attention`` under a band of ``sliding.window`` keys;
    # the record also says which attention kinds of the pattern rotate. A pattern
    # of the two attention kinds alone may be a ``parallel_block`` with a routed
    # MLP in every layer
    sliding: Optional[SlidingConfig] = None
    # Gated attention: the query projection is twice as wide, a head's
    # ``[q | gate]``, and the attention's output is times ``sigmoid(gate)``
    # before ``wo``; per-head RMSNorm of q and k (``q_norm``, ``k_norm``) before
    # the rotary
    attn_output_gate: bool = False
    qk_norm: bool = False
    # the shared expert's output times ``sigmoid(x . shared_gate)``, one scalar a token
    moe_shared_gate: bool = False
    # this chip's share of a routed layer spread over chips: ``num_experts`` are
    # HELD here, the router scores ``router_experts`` and picks among them all;
    # the layer computes the terms of its own experts (and the shared expert).
    # None: every expert is here, and the routed layer is what it always was
    expert_parallel: Optional[ExpertParallel] = None
    # scalars on the embedding, the attention scores (None: head_dim^-0.5),
    # every residual add and the logits (divided by it); 1 is no multiply
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # a LayerNorm's bias (False: scale alone), a sigmoid router's correction
    # bias ``e_bias`` (False: the picks are the largest scores themselves), and
    # the shared GLU's output times 1 / moe_shared_experts (the MEAN of the
    # shared experts, where they are added unweighted otherwise)
    norm_bias: bool = True
    moe_router_bias: bool = True
    moe_shared_average: bool = False
    # plain attention's values times this, before they are cached and summed (the
    # same number as the heads' output times it: the sum is linear in the values)
    value_multiplier: float = 1.0

    def __post_init__(self):
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            kinds = set(self.layer_types)
            if len(self.layer_types) != self.num_layers or not kinds <= set(LAYER_KINDS):
                raise ValueError(
                    f"layer_types has {len(self.layer_types)} entries of {sorted(kinds)} for num_layers="
                    f"{self.num_layers}: one of {LAYER_KINDS} a layer")
            if "mamba" in kinds and self.ssm is None:
                raise ValueError("layer_types with a 'mamba' layer needs its sizes, ssm=SSMConfig(...)")
            if "linear_attention" in kinds and self.gdn is None:
                raise ValueError("layer_types with a 'linear_attention' layer needs its sizes, gdn=GDNConfig(...)")
            if isinstance(self.sliding, dict):
                object.__setattr__(self, "sliding", SlidingConfig(**self.sliding))
            if ("sliding_attention" in kinds) != (self.sliding is not None):
                raise ValueError("a 'sliding_attention' layer and its window go together: layer_types of "
                                 f"{sorted(kinds)} with sliding={self.sliding}")
            if self.sliding is not None and (self.position not in ("rope", "none") or self.state_layers
                                             or self.attn_impl in ("sparse", "fpdt")):
                raise ValueError("a 'sliding_attention' layer is plain attention under a band, beside plain "
                                 "attention alone, with rotary positions or none: not with learned or alibi "
                                 "positions, state-space or linear-attention layers, attn_impl sparse | fpdt")
            # what a pattern of the two attention kinds alone with a routed MLP may be beside sequential and routed
            # in every layer: ONE parallel block, or leading dense layers before the routed ones (outside the scan)
            attends = kinds <= {"attention", "sliding_attention"} and self.num_experts > 0
            parallel = self.parallel_block and not attends
            leading = self.first_dense_layers and not (attends and not self.parallel_block)
            if (self.hc_mult or parallel or leading
                    or self.moe_layer_experts or self.kv_lora_rank or self.eva_window or self.fp32_residual):
                raise ValueError(
                    "a layer pattern (layer_types) is built around plain attention and one MLP (dense, or "
                    "routed in every layer) in a sequential one-stream block: no hyper-connections, no "
                    "parallel_block and no leading dense layers but in a pattern of the two attention kinds "
                    "alone with a routed MLP (and not both there), no pyramid layers, latent or EVA attention, "
                    "fp32 residual")
        elif self.sliding is not None:
            raise ValueError("sliding=SlidingConfig(...) is the data of a layer pattern's 'sliding_attention' "
                             "kind: give layer_types")
        if self.v_head_dim and not self.kv_lora_rank and self.sliding is None:
            raise ValueError(f"v_head_dim={self.v_head_dim} without a latent (kv_lora_rank == 0): a value narrower "
                             "than its key is latent attention's, or the kinds' of a pattern with a sliding kind")
        if isinstance(self.expert_parallel, dict):
            object.__setattr__(self, "expert_parallel", ExpertParallel(**self.expert_parallel))
        if self.expert_parallel is not None and self.expert_parallel.size == 1:
            # one chip holds every expert: the routed layer as it always was, to the instruction
            object.__setattr__(self, "expert_parallel", None)
        if self.expert_parallel is not None and not self.drop_free_moe:
            raise ValueError("expert_parallel (a chip's share of a routed layer) needs a drop-free routed "
                             "layer: num_experts > 0 with a sigmoid router or a layer pattern")
        if (self.attn_output_gate or self.qk_norm) and (self.latent_attention or self.eva_window
                                                        or self.attn_impl in ("sparse", "fpdt")):
            raise ValueError("attn_output_gate / qk_norm are plain attention's: not with latent or EVA "
                             "attention, nor attn_impl sparse | fpdt")
        if self.residual_multiplier != 1.0 and (self.hc_mult or self.parallel_block):
            raise ValueError("residual_multiplier scales the adds of a sequential one-stream block: not with "
                             "hyper-connections or parallel_block")
        if self.attention_multiplier is not None and self.attn_impl in ("sparse", "fpdt"):
            raise ValueError(f"attention_multiplier with attn_impl={self.attn_impl!r}: that path scales its "
                             "scores by head_dim^-0.5 itself")
        if isinstance(self.rope_scaling, dict):
            # frozen dataclass must stay hashable (configs are jit static args)
            object.__setattr__(self, "rope_scaling", tuple(sorted(self.rope_scaling.items())))
        if self.rope_scaling is not None:
            kind = dict(self.rope_scaling).get("type", dict(self.rope_scaling).get("rope_type"))
            if kind != "yarn" or not self.latent_attention:
                raise ValueError(
                    f"rope_scaling of type {kind!r}: only type 'yarn' is taken, and only by latent "
                    "attention (kv_lora_rank > 0); every other rotary path computes plain frequencies")
        if self.hc_mult and (self.hc_mult < 2 or self.parallel_block or self.eva_window):
            raise ValueError(
                f"hyper-connections (hc_mult={self.hc_mult}) need at least two streams around a "
                "sequential block (no parallel_block) with a one-dtype residual (no EVA attention)")
        if self.moe_router not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_router must be softmax|sigmoid, got {self.moe_router!r}")
        if self.index_topk and not (self.latent_attention and self.index_heads > 0
                                    and self.index_head_dim >= self.qk_rope_head_dim > 0):
            raise ValueError(
                f"a sparse-attention indexer (index_topk={self.index_topk}) selects for latent attention "
                f"(kv_lora_rank > 0) with index_heads > 0 heads of index_head_dim >= qk_rope_head_dim columns, "
                f"got index_heads={self.index_heads}, index_head_dim={self.index_head_dim}, "
                f"kv_lora_rank={self.kv_lora_rank}")
        if self.index_topk and (self.rope_scaling is not None or self.hc_mult or self.parallel_block):
            raise ValueError("a sparse-attention indexer (index_topk > 0) with rope_scaling, hyper-connections or "
                             "parallel_block is not built: its keys rotate by plain frequencies in a sequential "
                             "one-stream block")
        if self.kv_lora_rank and not self.q_lora_rank:
            raise ValueError("latent attention (kv_lora_rank > 0) needs q_lora_rank > 0: a plain "
                             "query projection beside latent keys and values is not built")
        if bool(self.eva_window) != bool(self.eva_chunk) or (
                self.eva_window and (self.eva_window % self.eva_chunk or self.latent_attention
                                     or self.position != "rope")):
            raise ValueError(
                f"EVA attention needs eva_window a multiple of eva_chunk, rotary positions and plain "
                f"keys and values (got eva_window={self.eva_window}, eva_chunk={self.eva_chunk}, "
                f"position={self.position!r}, kv_lora_rank={self.kv_lora_rank})")
        if self.first_dense_layers and not (0 < self.first_dense_layers < self.num_layers
                                            and self.num_experts > 0):
            raise ValueError(
                f"first_dense_layers={self.first_dense_layers} needs a routed stack after it "
                f"(num_layers={self.num_layers}, num_experts={self.num_experts})")
        if self.moe_layer_experts is not None and len(self.moe_layer_experts) != self.num_layers:
            raise ValueError(
                f"moe_layer_experts has {len(self.moe_layer_experts)} entries "
                f"for num_layers={self.num_layers} — one expert count per layer"
            )
        if isinstance(self.sparse_attention, dict):
            # frozen dataclass must stay hashable (configs are jit static args)
            object.__setattr__(self, "sparse_attention",
                               tuple(sorted(self.sparse_attention.items())))
        if isinstance(self.attn_kwargs, dict):
            object.__setattr__(self, "attn_kwargs",
                               tuple(sorted(self.attn_kwargs.items())))
        if self.attn_impl == "sparse" and not self.sparse_attention:
            raise ValueError(
                "attn_impl='sparse' needs a sparse_attention config dict, e.g. "
                "{'mode': 'bigbird', 'block': 16, 'num_random_blocks': 1}")
        if self.fpdt_offload and self.attn_impl != "fpdt":
            raise ValueError("fpdt_offload=True needs attn_impl='fpdt'")
        if self.sparse_embedding_grads and self.tie_embeddings:
            raise ValueError(
                "sparse_embedding_grads with tie_embeddings is counter-"
                "productive: the tied LM head contributes a dense [V, H] "
                "gradient either way")

    @property
    def sparse_attention_dict(self) -> Optional[dict]:
        return dict(self.sparse_attention) if self.sparse_attention else None

    def experts_for_layer(self, i: int) -> int:
        if self.moe_layer_experts is not None:
            return self.moe_layer_experts[i]
        return 0 if i < self.first_dense_layers else self.num_experts

    @property
    def has_moe(self) -> bool:
        return self.num_experts > 0 or bool(
            self.moe_layer_experts and any(e > 0 for e in self.moe_layer_experts)
        )

    @property
    def num_moe_layers(self) -> int:
        return sum(1 for i in range(self.num_layers) if self.experts_for_layer(i) > 0)

    @property
    def moe_dynamic_capacity(self) -> bool:
        """Whether the gate enforces a traced (autotunable) capacity cutoff
        — requires a ceiling AND drops (capacity is meaningless without)."""
        return (self.moe_capacity_factor_max is not None and self.moe_drop_tokens
                and self.has_moe)

    @property
    def drop_free_moe(self) -> bool:
        """Whether a routed layer is ``DropFreeMoE`` (no capacity, no drops, a
        shared expert where the config has one): a sigmoid router's, and a
        layer pattern's whatever its router."""
        return self.num_experts > 0 and (self.moe_router == "sigmoid" or self.layer_types is not None)

    @property
    def router_experts(self) -> int:
        """Experts the router scores and numbers its picks by: those held here
        times the chips that share the layer."""
        return self.num_experts * (self.expert_parallel.size if self.expert_parallel else 1)

    @property
    def first_expert(self) -> int:
        """The router's number of the first expert held here."""
        return self.num_experts * self.expert_parallel.rank if self.expert_parallel else 0

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def period(self) -> Optional[Tuple[str, ...]]:
        """The shortest run of kinds that ``layer_types`` repeats whole after
        its leading dense layers (the whole of it, if none does): what one step
        of the layer scan runs."""
        if self.layer_types is None:
            return None
        types = self.layer_types[self.first_dense_layers:]  # (leading dense layers run outside the scan)
        L = len(types)
        p = next(p for p in range(1, L + 1) if L % p == 0 and types == types[:p] * (L // p))
        return types[:p]

    @property
    def attention_layers(self) -> int:
        """Layers that hold keys and values: the page pool's."""
        return self.num_layers if self.layer_types is None else self.layer_types.count("attention")

    @property
    def ssm_layers(self) -> int:
        """Mamba-2 layers: each a row of the state pool."""
        return 0 if self.layer_types is None else self.layer_types.count("mamba")

    @property
    def gdn_layers(self) -> int:
        """Gated DeltaNet layers: each a row of the state pool."""
        return 0 if self.layer_types is None else self.layer_types.count("linear_attention")

    @property
    def sliding_layers(self) -> int:
        """Layers that hold a window of keys and values: the ring pool's."""
        return 0 if self.layer_types is None else self.layer_types.count("sliding_attention")

    @property
    def state_layers(self) -> int:
        """Layers that hold a recurrent state: the state pool's rows."""
        return self.ssm_layers + self.gdn_layers

    @property
    def latent_rotary(self) -> "LatentRotary":
        """What latent attention's scores are made with, in training and in
        serving: the rotary frequencies over ``qk_rope_head_dim`` (None: plain
        ``rope_theta``) and the softmax scale, which each hands to its
        attention call. YaRN changes both (``yarn_frequencies``)."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.rope_scaling is None:
            return LatentRotary(None, scale)
        inv_freq, softmax = yarn_frequencies(self.qk_rope_head_dim, self.rope_theta, self.rope_scaling)
        return LatentRotary(inv_freq, scale * softmax)

    @property
    def hc_params(self) -> int:
        """One sublayer's hyper-connection: ``phi``, ``b`` and the three ``alpha``."""
        n = self.hc_mult
        return (n * self.hidden_size + 1) * (n * n + 2 * n) + 3 if n else 0

    @property
    def routed_layers(self) -> int:
        """Layers with a router, in the order their picks are handed out."""
        if self.moe_layer_experts is not None:
            return self.num_moe_layers
        return self.num_layers - self.first_dense_layers if self.num_experts > 0 else 0

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def dims_per_head(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def flops_per_token(self, seq_len: int) -> float:
        """Approximate training FLOPs/token (fwd+bwd, 6ND + attention).

        For MoE configs N is the ACTIVE parameter count (top-k experts)."""
        n = self.num_active_params()
        attn = 12 * self.attention_layers * self.hidden_size * seq_len  # score+value matmuls
        return 6 * n + attn

    def _mlp_params(self, width: Optional[int] = None) -> int:
        """One MLP's (one expert's) parameter count."""
        proj = 3 if self.activation == "silu_glu" else 2
        return proj * self.hidden_size * (width or self.intermediate_size)

    def _attention_params(self, kind: str = "attention") -> int:
        h, H = self.hidden_size, self.num_heads
        if self.sliding is not None:  # the kind's own shapes: q and k at its key width, v and o at its value's
            own = sliding_kind(self, kind)
            return (h * own["head_dim"] * (H + own["kv_heads"]) + h * own["v_head_dim"] * (own["kv_heads"] + H)
                    + (H if own["sink"] else 0))
        if self.latent_attention:
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            q = h * self.q_lora_rank + self.q_lora_rank * (H * qk + 1)
            kv = (h * (self.kv_lora_rank + self.qk_rope_head_dim) + self.kv_lora_rank
                  + self.kv_lora_rank * H * (self.qk_nope_head_dim + self.v_head_dim))
            return q + kv + H * self.v_head_dim * h + self._indexer_params()
        hd = self.dims_per_head
        eva = 2 * self.kv_heads * hd if self.eva_window else 0  # phi and mu
        gate = h * hd * H if self.attn_output_gate else 0  # the query projection's second half
        return h * hd * (H + 2 * self.kv_heads) + hd * H * h + eva + gate + (2 * hd if self.qk_norm else 0)

    def _indexer_params(self) -> int:
        """The indexer of one layer: its query and key projections, the key's LayerNorm, the heads' weights."""
        if not self.index_topk:
            return 0
        Hi, Di = self.index_heads, self.index_head_dim
        return self.q_lora_rank * Hi * Di + self.hidden_size * (Di + Hi) + 2 * Di

    def _ssm_params(self) -> int:
        """One state-space mixer: both projections, the convolution and its
        bias, ``A_log``, ``dt_bias``, ``D``, the gated norm."""
        s = self.ssm
        return (self.hidden_size * (s.proj_dim + s.d_inner) + (s.d_conv + 1) * s.conv_dim
                + 3 * s.n_heads + s.d_inner)

    def _gdn_params(self) -> int:
        """One Gated DeltaNet mixer: the three projections, the convolution,
        ``A_log``, ``dt_bias``, the gated norm."""
        g = self.gdn
        return (self.hidden_size * (g.proj_dim + 2 * g.n_v_heads + g.value_dim) + g.d_conv * g.conv_dim
                + 2 * g.n_v_heads + g.head_v_dim)

    def num_params(self) -> int:
        h, v, l = self.hidden_size, self.vocab_size, self.num_layers
        qkv = self._attention_params()
        mlp = self._mlp_params()
        expert = self._mlp_params(self.expert_width)
        total = v * h * (1 if self.tie_embeddings else 1 + self.num_pred_heads)  # embedding (+ head)
        total += h  # final norm
        for i in range(l):
            n_exp = self.experts_for_layer(i)
            if n_exp > 0:
                layer_mlp = n_exp * expert + h * self.router_experts  # experts (held here) + router
                layer_mlp += self.moe_shared_experts * expert + (h if self.moe_shared_gate else 0)
                if self.moe_router == "sigmoid" and self.moe_router_bias:
                    layer_mlp += self.router_experts  # the correction bias
                if self.moe_use_residual:
                    layer_mlp += mlp + 2 * h + 2  # residual MLP + coefficient gate
            else:
                layer_mlp = mlp
            kind = self.layer_types[i] if self.layer_types else "attention"
            mixer = {"mamba": self._ssm_params, "linear_attention": self._gdn_params,
                     "sliding_attention": lambda: self._attention_params(kind)}.get(kind, lambda: qkv)()
            total += mixer + layer_mlp + (h if self.parallel_block else 2 * h) + 2 * self.hc_params
        return total

    def num_active_params(self) -> int:
        """Params a single token touches (top-k experts instead of all)."""
        if not self.has_moe:
            return self.num_params()
        mlp = self._mlp_params(self.expert_width)
        dead = 0
        for i in range(self.num_layers):
            n_exp = self.experts_for_layer(i)
            if n_exp > 0:
                # (of a chip's share a token visits top_k / size of the experts held here, on average)
                visits = min(self.moe_top_k, n_exp) if self.expert_parallel is None else (
                    self.moe_top_k / self.expert_parallel.size)
                dead += int((n_exp - visits) * mlp)
        return self.num_params() - dead


# ---------------------------------------------------------------- presets
PRESETS = {
    "tiny": TransformerConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                              num_layers=2, num_heads=4, max_seq_len=128),
    "gpt2-125m": TransformerConfig(vocab_size=50257, hidden_size=768, intermediate_size=3072,
                                   num_layers=12, num_heads=12, max_seq_len=1024,
                                   norm="layernorm", activation="gelu", position="learned",
                                   tie_embeddings=True),
    "llama3-8b": TransformerConfig(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                                   num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192,
                                   rope_theta=500000.0),
    "llama3-1b": TransformerConfig(vocab_size=128256, hidden_size=2048, intermediate_size=8192,
                                   num_layers=16, num_heads=32, num_kv_heads=8, max_seq_len=8192),
}


def act_fn(name: str):
    """Non-GLU activation by config name (shared by every MLP/expert site)."""
    if name == "relu":
        return jax.nn.relu
    if name == "gelu_exact":  # HF 'gelu' is the erf form
        return lambda x: jax.nn.gelu(x, approximate=False)
    if name == "gelu":
        return jax.nn.gelu
    raise ValueError(f"unknown activation {name!r} (silu_glu | gelu | gelu_exact | relu)")


@jax.custom_vjp
def _gelu_exact(x):
    """``jax.nn.gelu(x, approximate=False)`` whose backward keeps ONE array,
    the slope, and makes nothing again: the erf is a long polynomial on the
    VPU, and making it again cost the 410M train cell 0.18 ms a layer-step
    where the two arrays it spared were read under a product (PERF.md, PR 38)."""
    return jax.nn.gelu(x, approximate=False)


def _gelu_exact_fwd(x):
    twice_cdf = jax.lax.erfc(-x * np.sqrt(0.5).astype(x.dtype))  # ``jax.nn.gelu``'s own expression
    xf = x.astype(jnp.float32)
    slope = 0.5 * twice_cdf.astype(jnp.float32) + xf * jnp.exp(-0.5 * xf * xf) * np.float32(1 / np.sqrt(2 * np.pi))
    return 0.5 * x * twice_cdf, slope.astype(x.dtype)


_gelu_exact.defvjp(_gelu_exact_fwd, lambda slope, g: (g * slope,))


def _made_again(what):
    """The module docstring's rule, as code: ``what`` (an elementwise function,
    or the flax class of one) keeps its inputs alone for the backward."""
    return (nn.remat if isinstance(what, type) else jax.checkpoint)(what, prevent_cse=False)


def _gathered(module: nn.Module, name: str):
    """``dot_general=`` of the product that reads ``module``'s ``name/kernel``:
    None (flax's own) unless the engine published one for the step it traces
    (under ZeRO-3 the weight's own gather, ``runtime/zero.py``)."""
    from deepspeed_tpu.topology.mesh import dot_general_for

    return dot_general_for(module.path + (name, "kernel"))


def _mlp_activation(name: str):
    """``act_fn(name)`` as a dense MLP applies it between its products: made
    again from its input in the backward, but for the erf form, which keeps
    its slope instead."""
    return _gelu_exact if name == "gelu_exact" else _made_again(act_fn(name))


class RMSNorm(nn.Module):
    eps: float = 1e-5
    param_dtype: Any = jnp.float32
    unit_offset: bool = False  # the weight is 1 + scale; scale is drawn off zero
    out_dtype: Any = None  # None: the input's

    @nn.compact
    def __call__(self, x):
        from deepspeed_tpu.ops import rms_norm

        init = nn.initializers.normal(0.05) if self.unit_offset else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],), self.param_dtype)
        if self.unit_offset:
            scale = 1.0 + scale.astype(jnp.float32)
        y = rms_norm(x, scale, eps=self.eps)
        return y if self.out_dtype is None else y.astype(self.out_dtype)


def _norm(config: TransformerConfig, name: str):
    # the backward keeps ``x``: the fp32 copy, the centred and the normalised value are made again
    if config.norm == "rmsnorm":
        return _made_again(RMSNorm)(
            eps=config.norm_eps, param_dtype=config.param_dtype, unit_offset=config.norm_unit_offset,
            out_dtype=config.dtype if config.fp32_residual else None, name=name)
    return _made_again(nn.LayerNorm)(epsilon=config.norm_eps, param_dtype=config.param_dtype,
                                     use_bias=config.norm_bias, name=name)


# ``apply_qk_rope`` has TWO paths to the same numbers, and the first is kept for one reason alone: a table over
# every declared position is what each serving program pinned by ``tests/unit/inference/fixtures`` (the census) was
# recorded with, and PR 48 was not to re-record them. Past this many declared positions (no configuration before
# PR 48 declares more than 32,768) the angles are computed from the call's own positions, which is no more work
# for any configuration. ROADMAP S18: the second path for all, the census re-recorded, this constant gone.
_ROPE_TABLE_POSITIONS = 2 ** 17


def rope_tables(seq_len: int, dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    angles = jnp.outer(t, freqs)  # [S, dim/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_qk_rope(cfg: "TransformerConfig", q, k, positions, theta: Optional[float] = None):
    """Apply (possibly partial) rotary embeddings per the config.

    Phi-style partial rotary ropes only the first ``rotary_dim`` of head_dim;
    the tail dims pass through. ``rope_interleaved`` selects the GPT-J
    pairwise rotation. Shared by the training attention and both inference
    decode paths so the three sites cannot drift. ``theta``: the base of a
    pattern's kind that states its own (``sliding_kind``; None: ``rope_theta``)."""
    hd = q.shape[-1]
    rd = cfg.rotary_dim or hd
    theta = cfg.rope_theta if theta is None else theta
    if cfg.max_seq_len > _ROPE_TABLE_POSITIONS:
        # the angles of the call's own positions, the table's numbers: a table over every position such a
        # config declares is made anew by every call (262,144 x 32: 0.9 ms a layer-step on the v5e, PR 48)
        freqs = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
        angles = positions.reshape(-1, 1).astype(jnp.float32) * freqs[None, :]
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        positions = jnp.arange(positions.size, dtype=positions.dtype).reshape(positions.shape)
    else:
        cos, sin = rope_tables(cfg.max_seq_len, rd, theta)
    ap = lambda x: apply_rope(x, cos, sin, positions, interleaved=cfg.rope_interleaved)  # noqa: E731
    if rd < hd:
        q = jnp.concatenate([ap(q[..., :rd]), q[..., rd:]], -1)
        k = jnp.concatenate([ap(k[..., :rd]), k[..., rd:]], -1)
        return q, k
    return ap(q), ap(k)


def alibi_slopes(num_heads: int) -> jnp.ndarray:
    """Per-head ALiBi slopes (reference: the inference softmax kernels'
    alibi path, ``csrc/transformer/inference/csrc/softmax.cu``; formula
    matches HF ``build_alibi_tensor`` so bloom checkpoints reproduce)."""
    import math

    closest = 2 ** math.floor(math.log2(num_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(closest) - 3)))
    slopes = [base ** p for p in range(1, closest + 1)]
    if closest != num_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * closest) - 3)))
        slopes += [extra_base ** p for p in range(1, 2 * (num_heads - closest), 2)]
    return jnp.asarray(slopes, jnp.float32)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, positions: jax.Array,
               interleaved: bool = False) -> jax.Array:
    """x: [B, S, H, D]; cos/sin: [maxS, D/2]; positions: [B, S]."""
    from deepspeed_tpu.ops import rope as rope_op

    return rope_op(x, cos, sin, positions, interleaved=interleaved)


class _SparseGradEmbed(nn.Embed):
    """``nn.Embed`` whose backward ships sparse rows through the DP sync.

    Engine-wired ``sparse_gradients: true`` (reference runtime/sparse_tensor.py:69):
    identical params/forward to ``nn.Embed``; only the gradient's cross-replica
    sync changes (see ``runtime/sparse_grad.sparse_lookup``)."""

    def __call__(self, inputs):
        from deepspeed_tpu.runtime.sparse_grad import sparse_lookup

        table = self.embedding
        if self.dtype is not None:
            table = table.astype(self.dtype)
        return sparse_lookup(table, inputs)


class Attention(nn.Module):
    config: TransformerConfig
    # of a pattern with a sliding kind (``TransformerConfig.sliding``): the band
    # this layer attends under (None: every key up to the query) and whether it
    # rotates (None: as ``config.position`` says)
    window: Optional[int] = None
    rotates: Optional[bool] = None
    # and the kind's own shapes (``sliding_kind``; None: the model's one-number
    # fields): kv heads, the width of queries and keys, of values, the rotary
    # base, and whether a learned logit a head (``sink``) joins the softmax's sum
    kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    rope_theta: Optional[float] = None
    sink: bool = False

    @nn.compact
    def __call__(self, x, mask, positions, train: bool):
        cfg = self.config
        hd = self.head_dim or cfg.dims_per_head
        kv_heads = self.kv_heads or cfg.kv_heads
        qkv_bias = cfg.qkv_bias if cfg.qkv_bias is not None else cfg.norm == "layernorm"
        # gated attention: a head's projection is ``[q | gate]``
        q = nn.DenseGeneral((cfg.num_heads, hd * (2 if cfg.attn_output_gate else 1)), use_bias=qkv_bias,
                            dot_general=_gathered(self, "wq"),
                            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="wq")(x)
        k = nn.DenseGeneral((kv_heads, hd), use_bias=qkv_bias, dot_general=_gathered(self, "wk"),
                            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="wk")(x)
        v = nn.DenseGeneral((kv_heads, self.v_head_dim or hd), use_bias=qkv_bias, dot_general=_gathered(self, "wv"),
                            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="wv")(x)
        v = _times(cfg.value_multiplier, v)
        if cfg.attn_output_gate:
            q, gate = q[..., :hd], q[..., hd:]
        if cfg.qk_norm:
            q, k = _norm(cfg, "q_norm")(q), _norm(cfg, "k_norm")(k)

        if cfg.position == "rope" and self.rotates is not False:
            q, k = apply_qk_rope(cfg, q, k, positions, self.rope_theta)
        slopes = alibi_slopes(cfg.num_heads) if cfg.position == "alibi" else None
        banded = {} if self.window is None else {"window": self.window}
        if self.sink:
            # drawn at unit variance: a sink left at a constant would be left unchecked
            banded["sink"] = self.param("sink", nn.initializers.normal(1.0), (cfg.num_heads,), cfg.param_dtype)

        from deepspeed_tpu.ops import causal_attention
        from deepspeed_tpu.parallel.ulysses import sp_active, ulysses_shard, ulysses_unshard

        if cfg.attn_impl == "sparse":
            # Block-sparse attention (reference sparse_attention config +
            # sparsity_config.py): static layout from the config, the
            # tile-skipping Pallas kernels run fwd AND bwd.
            from deepspeed_tpu.ops.sparse_attention import (
                block_sparse_attention,
                get_sparsity_config,
            )

            if sp_active():
                raise NotImplementedError("attn_impl='sparse' under sequence parallelism")
            sa = dict(cfg.sparse_attention_dict)
            mode = sa.pop("mode", "bigbird")
            block = sa.pop("block", 16)
            S = q.shape[1]
            scfg = get_sparsity_config(mode, num_heads=cfg.num_heads,
                                       block=block, **sa)
            layout = scfg.make_layout(S)
            if cfg.kv_heads != cfg.num_heads:
                G = cfg.num_heads // cfg.kv_heads
                k = jnp.repeat(k, G, axis=2)
                v = jnp.repeat(v, G, axis=2)
            # ALiBi and key padding compose through the masked softmax
            # (round 5; those combos ride the XLA path — see
            # ops/sparse_attention.block_sparse_attention)
            out = block_sparse_attention(q, k, v, layout, block=block,
                                         alibi_slopes=slopes, pad_mask=mask)
        elif cfg.attn_impl == "fpdt":
            # FPDT long-context training (reference fpdt_layer.py:971
            # FPDT_Attention): custom-VJP chunked attention, O(Cq·Ck) score
            # tiles. Composes with Ulysses sp exactly like the dense path —
            # the all-to-all head shard happens via the same sharding
            # constraints. fpdt_offload parks the q/k/v/out residuals in
            # (pinned) host memory between forward and backward (the
            # reference's host-offloaded chunks), SPMD-safe.
            from deepspeed_tpu.sequence.fpdt import fpdt_attention

            if mask is not None:
                raise NotImplementedError(
                    "attn_impl='fpdt' with a padding mask is not wired; "
                    "right-pad and rely on causal masking or drop the mask")
            q, k, v = ulysses_shard(q), ulysses_shard(k), ulysses_shard(v)
            out = fpdt_attention(q, k, v, q_chunk=cfg.fpdt_q_chunk,
                                 kv_chunk=cfg.fpdt_kv_chunk, causal=True,
                                 alibi_slopes=slopes,
                                 offload=cfg.fpdt_offload)
            out = ulysses_unshard(out)
        elif cfg.sp_impl == "ring" and sp_active() and mask is None and self.window is None:
            # ring attention: K/V rotate over the sp ring (ppermute), queries
            # stay seq-sharded — O(S/P) memory, neighbor-link comm. ALiBi
            # rides the hops (each block's global k offset feeds the bias).
            from deepspeed_tpu.parallel.ring_attention import ring_attention
            from deepspeed_tpu.topology.mesh import get_mesh

            out = ring_attention(q, k, v, mesh=get_mesh(), axis="sp",
                                 alibi_slopes=slopes)
        else:
            # Ulysses SP: seq-shard -> head-shard all-to-all around exact
            # attention. Alibi composes for free: ulysses_shard is a sharding
            # CONSTRAINT (the program stays global SPMD), so the partitioner
            # splits the per-head slope bias along with the head axis.
            q, k, v = ulysses_shard(q), ulysses_shard(k), ulysses_shard(v)
            scaled = {} if cfg.attention_multiplier is None else {"softmax_scale": cfg.attention_multiplier}
            out = causal_attention(q, k, v, mask=mask, impl=cfg.attn_impl,
                                   alibi_slopes=slopes, **scaled, **banded,
                                   **dict(cfg.attn_kwargs or ()))  # [B,S,H,hd]
            out = ulysses_unshard(out)
        if cfg.attn_output_gate:
            with jax.named_scope("attn_gate"):
                out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
        dense_bias = cfg.dense_bias if cfg.dense_bias is not None else cfg.norm == "layernorm"
        out = nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1), use_bias=dense_bias, dot_general=_gathered(self, "wo"),
                              dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="wo")(out)
        if cfg.dropout > 0:
            out = nn.Dropout(cfg.dropout, deterministic=not train)(out)
        return out


def sliding_kind(cfg: TransformerConfig, kind: str) -> dict:
    """What an attention layer of ``kind`` is in a pattern with a sliding kind, one statement for the flax
    module, the cache plan and the paged path: ``window`` and ``rotates``, and its shapes: ``kv_heads``,
    ``head_dim`` (queries and keys), ``v_head_dim``, ``rope_theta``, ``sink``. The ``attention`` kind's are the
    model's one-number fields, the sliding kind's ``cfg.sliding``'s own where it states them."""
    shape = {"kv_heads": cfg.kv_heads, "head_dim": cfg.dims_per_head,
             "v_head_dim": cfg.v_head_dim or cfg.dims_per_head, "rope_theta": cfg.rope_theta, "sink": False}
    if kind != "sliding_attention":
        return {"window": None, "rotates": cfg.sliding.global_rope, **shape}
    own = cfg.sliding
    return {"window": own.window, "rotates": True, "kv_heads": own.num_kv_heads or shape["kv_heads"],
            "head_dim": own.head_dim or shape["head_dim"],
            "v_head_dim": own.v_head_dim or own.head_dim or shape["v_head_dim"],
            "rope_theta": shape["rope_theta"] if own.rope_theta is None else own.rope_theta, "sink": own.sink}


class LatentRotary(NamedTuple):
    """``TransformerConfig.latent_rotary``: what ``LatentAttention`` and the
    paged ``_latent_attention`` both make their scores with."""

    inv_freq: Optional[np.ndarray]  # [qk_rope_head_dim / 2] float32; None: plain rope_theta
    softmax_scale: float            # the scores' scale, YaRN's factor in it


@functools.lru_cache(maxsize=8)
def yarn_frequencies(dim: int, theta: float, scaling: tuple) -> Tuple[np.ndarray, float]:
    """YaRN as the DeepSeek-V2/V3 modelling code has it, whose key names
    ``scaling`` (a config's ``rope_scaling``, as sorted pairs) uses: with ``f_i
    = theta^(-2i/dim)``, ``corr(r) = dim ln(L0 / (2 pi r)) / (2 ln theta)``,
    ``low = max(floor(corr(beta_fast)), 0)``, ``high = min(ceil(corr(beta_slow)),
    dim - 1)`` and ``ramp_i = clip((i - low) / (high - low), 0, 1)``, pair ``i``
    turns by ``(f_i / factor) ramp_i + f_i (1 - ramp_i)`` a position: fast pairs
    as before, slow ones ``factor`` times slower. Returns those frequencies and
    what multiplies the softmax scale, ``ym(mscale_all_dim)^2``, with ``ym(s) =
    0.1 s ln(factor) + 1``. Cos and sin are multiplied by ``ym(mscale) /
    ym(mscale_all_dim)``, which is 1 wherever the two keys agree: a config
    where they differ is refused, until one needs it."""
    import math

    sc = dict(scaling)
    factor, original = float(sc["factor"]), float(sc["original_max_position_embeddings"])
    if float(sc.get("mscale", 1)) != float(sc.get("mscale_all_dim", 0)):
        raise ValueError(f"yarn with mscale {sc.get('mscale', 1)} != mscale_all_dim {sc.get('mscale_all_dim', 0)}: "
                         "cos and sin would be multiplied by their ratio, which no rotary path here does")

    def corr(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(float(sc.get("beta_fast", 32)))), 0)
    high = min(math.ceil(corr(float(sc.get("beta_slow", 1)))), dim - 1)
    freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / ((high - low) or 0.001), 0.0, 1.0)
    all_dim = 0.1 * float(sc.get("mscale_all_dim", 0)) * math.log(factor) + 1.0 if factor > 1 else 1.0
    return (freq / factor * ramp + freq * (1 - ramp)).astype(np.float32), all_dim * all_dim


def rope_at(x: jax.Array, positions: jax.Array, theta: float, interleaved: bool,
            inv_freq: Optional[np.ndarray] = None) -> jax.Array:
    """Rotary embedding over the whole last dim of ``x`` [..., S, H, D] at
    ``positions`` [..., S], the angles computed from the positions themselves
    (no table of ``max_seq_len`` rows: a context of 200k would make one of
    6.5M entries for every call). ``inv_freq`` [D/2] replaces the plain
    ``theta^(-2i/D)`` (scaled rotary: ``TransformerConfig.latent_rotary``).
    fp32 inside, ``x``'s dtype out."""
    d = x.shape[-1]
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[..., None, None] * inv_freq  # [..., S, 1, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)
    else:
        x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class EvaAttention(nn.Module):
    """EVA attention (``TransformerConfig.eva_window``; ``ops/eva.py`` has the
    mathematics): the projections are :class:`Attention`'s, under its names,
    and each kv head has two more vectors, ``phi`` (a chunk's pooling weights)
    and ``mu`` (added to a summary key). Both are drawn NONZERO: at zero the
    pooling is a mean and ``mu`` is absent, and nothing could tell whether
    either was computed. Sequences start at position 0 here (no cache)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, positions, train: bool):
        from deepspeed_tpu.ops.eva import eva_attention

        cfg = self.config
        if mask is not None:
            raise NotImplementedError("EVA attention with a padding mask: right-pad, the mask is causal")
        hd, kvH = cfg.dims_per_head, cfg.kv_heads

        def dense(features, name, **kw):
            return nn.DenseGeneral(features, use_bias=False, dtype=cfg.dtype, dot_general=_gathered(self, name),
                                   param_dtype=cfg.param_dtype, name=name, **kw)

        q, k, v = dense((cfg.num_heads, hd), "wq")(x), dense((kvH, hd), "wk")(x), dense((kvH, hd), "wv")(x)
        phi = self.param("phi", nn.initializers.normal(hd ** -0.5), (kvH, hd), cfg.param_dtype)
        mu = self.param("mu", nn.initializers.normal(0.5), (kvH, hd), cfg.param_dtype)
        q = rope_at(q, positions, cfg.rope_theta, cfg.rope_interleaved)
        k = rope_at(k, positions, cfg.rope_theta, cfg.rope_interleaved)
        # one window or less is plain causal attention (the flash kernel, with
        # its gradient); past it the windows' exact parts go through the
        # kernel's forward where there is one, which has no gradient: training
        # past one window runs the XLA form
        impl = "xla" if train else "auto"
        with jax.named_scope("eva"):
            if q.shape[1] <= cfg.eva_window:
                from deepspeed_tpu.ops import causal_attention

                out = causal_attention(q, k, v, impl=cfg.attn_impl)
            else:
                out = eva_attention(q, k, v, phi, mu, cfg.eva_window, cfg.eva_chunk, impl=impl)[0]
        return dense(cfg.hidden_size, "wo", axis=(-2, -1))(out)


class _IndexKeyNorm(nn.Module):
    """The index key's LayerNorm (``ops/dsa.py::key_norm``): ``scale`` and a ``bias`` drawn off zero, because a
    bias left at zero is a leaf no check can see."""

    width: int
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        from deepspeed_tpu.ops import dsa
        from deepspeed_tpu.parallel.moe import _nonzero_normal

        scale = self.param("scale", nn.initializers.ones, (self.width,), self.param_dtype)
        bias = self.param("bias", _nonzero_normal(0.05), (self.width,), self.param_dtype)
        return dsa.key_norm(x, scale, bias)


class LatentAttention(nn.Module):
    """Latent attention over a full sequence, the plain (non-absorbed) way:
    keys and values are up-projected per head from the normed latent and the
    heads attend as usual (``TransformerConfig.kv_lora_rank``). Serving keeps
    the latent and the shared rotary key alone and absorbs the up-projections
    into the query and the output (``inference/paged.py``); both read these
    parameters: ``wq_a``/``q_norm``/``wq_b``, ``wkv_a``/``kv_norm``,
    ``wkv_b`` [rank, H, nope + v] kept whole, ``wo``.

    With an indexer (``index_topk > 0``; ``ops/dsa.py``) a query attends the
    positions it selects and no others, here as a pair bias on the plain
    attention: ``idx_wq`` [q rank, heads, D] from the query's latent,
    ``idx_wk`` [hidden, D] and ``idx_k_norm`` (scale, bias) for the one key a
    token, ``idx_w`` [hidden, heads] for the heads' weights."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, positions, train: bool):
        cfg = self.config
        H, nope, rope_d, vd = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        def dense(features, name, **kw):
            return nn.DenseGeneral(features, use_bias=False, dtype=cfg.dtype, dot_general=_gathered(self, name),
                                   param_dtype=cfg.param_dtype, name=name, **kw)

        c_q = _norm(cfg, "q_norm")(dense(cfg.q_lora_rank, "wq_a")(x))
        q = dense((H, nope + rope_d), "wq_b")(c_q)
        kv = dense(cfg.kv_lora_rank + rope_d, "wkv_a")(x)
        c_kv = _norm(cfg, "kv_norm")(kv[..., : cfg.kv_lora_rank])
        rot = cfg.latent_rotary
        rope = functools.partial(rope_at, positions=positions, theta=cfg.rope_theta,
                                 interleaved=cfg.rope_interleaved, inv_freq=rot.inv_freq)
        k_rope = rope(kv[..., None, cfg.kv_lora_rank:])  # ONE head, shared by all
        q_rope = rope(q[..., nope:])
        kv_up = dense((H, nope + vd), "wkv_b")(c_kv)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate([kv_up[..., :nope], jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
        v = kv_up[..., nope:]

        from deepspeed_tpu.ops import causal_attention

        # the kernels take one head size for q, k and v: pad the values up to
        # the keys' width where they differ (zeros add nothing to p @ v)
        width = nope + rope_d
        if vd < width:
            v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, width - vd)))
        kept = {}
        if cfg.index_topk:
            kept["bias"] = self.selection_bias(x, c_q, positions, dense)
        out = causal_attention(q, k, v, mask=mask, impl=cfg.attn_impl, softmax_scale=rot.softmax_scale,
                               **kept, **dict(cfg.attn_kwargs or ()))[..., :vd]
        return dense(cfg.hidden_size, "wo", axis=(-2, -1))(out)

    def selection_bias(self, x, c_q, positions, dense):
        """``[B, H, S, S]`` float32: 0 where query ``t`` attends ``s``, -1e30 elsewhere (the indexer's choice)."""
        from deepspeed_tpu.ops import dsa

        cfg = self.config
        Hi, Di, rope_d = cfg.index_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
        q = dense((Hi, Di), "idx_wq")(c_q)
        k = dense(Di, "idx_wk")(x)
        k = _IndexKeyNorm(Di, cfg.param_dtype, name="idx_k_norm")(k)
        w = dsa.head_weights(dense(Hi, "idx_w")(x), Hi, Di)
        turn = functools.partial(dsa.rotate, positions=positions, rope_dim=rope_d, theta=cfg.rope_theta,
                                 interleaved=cfg.rope_interleaved)
        q, k = turn(q), turn(k[..., None, :])[..., 0, :]
        within = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])  # a key's slot in the sequence given
        chosen = dsa.select_mask(dsa.index_scores(q, k, w, within), cfg.index_topk, q_positions=within)
        bias = jnp.where(chosen, 0.0, -1e30).astype(jnp.float32)
        return jnp.broadcast_to(bias[:, None], (x.shape[0], cfg.num_heads) + bias.shape[1:])


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A_log`` as the Mamba-2 reference draws it: ``A`` uniform in [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step drawn log-uniform in [0.001, 0.1] (the
    Mamba-2 reference's): with ``A`` in [1, 16] a head forgets over tens to a
    thousand tokens."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _d_init(key, shape, dtype=jnp.float32):
    return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5).astype(dtype)


class _ConvKernel(nn.Module):
    """The depthwise convolution's ``kernel`` [taps, channels] and ``bias``,
    where a flax convolution would put them; ``ops/ssm.py`` applies them."""

    taps: int
    channels: int
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self) -> dict:
        from deepspeed_tpu.parallel.moe import _nonzero_normal

        return {"kernel": self.param("kernel", nn.initializers.normal(self.taps ** -0.5),
                                     (self.taps, self.channels), self.param_dtype),
                "bias": self.param("bias", _nonzero_normal(0.2), (self.channels,), self.param_dtype)}


class _Scale(nn.Module):
    """A norm's ``scale`` alone (the gated norm of a state-space mixer)."""

    width: int
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self) -> dict:
        return {"scale": self.param("scale", nn.initializers.ones, (self.width,), self.param_dtype)}


class Mamba2Mixer(nn.Module):
    """A Mamba-2 state-space mixer over a full sequence from an empty state
    (``TransformerConfig.ssm``; ``ops/ssm.py`` has the mathematics, which the
    paged serving path calls with these parameters and a state it keeps):
    ``ssm_in_proj`` [hidden, z | xBC | dt], ``ssm_conv`` (kernel, bias),
    ``A_log``, ``dt_bias``, ``D`` a head, ``ssm_norm`` (the gated norm's scale),
    ``ssm_out_proj``. ``A_log``, ``dt_bias`` and ``D`` are drawn NONZERO and
    spread: left at a constant, nothing could tell whether they were read.
    ``mask`` [B, S] marks the live tokens of right-padded rows."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, positions, train: bool):
        from deepspeed_tpu.ops import ssm

        cfg, s = self.config, self.config.ssm
        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                            dot_general=_gathered(self, name), name=name)

        zxbcdt = dense(s.proj_dim, "ssm_in_proj")(x)
        heads = functools.partial(self.param, shape=(s.n_heads,), dtype=cfg.param_dtype)
        leaves = {
            "ssm_conv": _ConvKernel(s.d_conv, s.conv_dim, cfg.param_dtype, name="ssm_conv")(),
            "A_log": heads("A_log", _a_log_init), "dt_bias": heads("dt_bias", _dt_bias_init),
            "D": heads("D", _d_init),
            "ssm_norm": _Scale(s.d_inner, cfg.param_dtype, name="ssm_norm")(),
        }
        new_lens = None if mask is None else (mask > 0).sum(axis=1).astype(jnp.int32)
        y, _, _ = ssm.mix(zxbcdt, leaves, s, cfg.norm_eps, new_lens=new_lens)
        return dense(cfg.hidden_size, "ssm_out_proj")(y)


class GatedDeltaNet(nn.Module):
    """A Gated DeltaNet (linear-attention) mixer over a full sequence from an
    empty state (``TransformerConfig.gdn``; ``ops/gdn.py`` has the mathematics,
    which the paged serving path calls with these parameters and a state it
    keeps): ``gdn_in_proj`` [hidden, q | k | v | z], ``gdn_ba_proj`` [hidden, b |
    a], ``gdn_conv`` [taps, channels] (no bias), ``A_log`` and ``dt_bias`` a value head
    (drawn spread, as ``Mamba2Mixer``'s), ``gdn_norm`` (the gated norm's scale,
    one value head's width), ``gdn_out_proj``. ``mask`` [B, S] marks the live
    tokens of right-padded rows."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, positions, train: bool):
        from deepspeed_tpu.ops import gdn

        cfg, g = self.config, self.config.gdn
        def dense(features, name, dtype=cfg.dtype):
            return nn.Dense(features, use_bias=False, dtype=dtype, param_dtype=cfg.param_dtype,
                            dot_general=_gathered(self, name), name=name)

        # [q | k | v | z] as the product's float32 sums: nothing between the projections rounds them (ops/gdn.py)
        qkvz, ba = dense(g.proj_dim, "gdn_in_proj", jnp.float32)(x), dense(2 * g.n_v_heads, "gdn_ba_proj")(x)
        heads = functools.partial(self.param, shape=(g.n_v_heads,), dtype=cfg.param_dtype)
        leaves = {
            "gdn_conv": self.param("gdn_conv", nn.initializers.normal(g.d_conv ** -0.5),
                                   (g.d_conv, g.conv_dim), cfg.param_dtype),
            "A_log": heads("A_log", _a_log_init), "dt_bias": heads("dt_bias", _dt_bias_init),
            "gdn_norm": _Scale(g.head_v_dim, cfg.param_dtype, name="gdn_norm")(),
        }
        new_lens = None if mask is None else (mask > 0).sum(axis=1).astype(jnp.int32)
        y, _, _ = gdn.mix(qkvz, ba, leaves, g, cfg.norm_eps, new_lens=new_lens)
        return dense(cfg.hidden_size, "gdn_out_proj")(y)


class MLP(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, train: bool):
        cfg = self.config
        bias = cfg.mlp_bias if cfg.mlp_bias is not None else (
            cfg.dense_bias if cfg.dense_bias is not None else cfg.norm == "layernorm")
        def dense(features, name):
            return nn.Dense(features, use_bias=bias, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                            dot_general=_gathered(self, name), name=name)

        if cfg.activation == "silu_glu":
            gate, up = dense(cfg.intermediate_size, "w_gate")(x), dense(cfg.intermediate_size, "w_up")(x)
            # ``gate``, ``up`` and ``h`` are kept (the products' own), the sigmoid is made again
            h = _made_again(lambda gate, up: nn.silu(gate) * up)(gate, up)
        else:
            h = _mlp_activation(cfg.activation)(dense(cfg.intermediate_size, "w_up")(x))
        out = dense(cfg.hidden_size, "w_down")(h)
        if cfg.dropout > 0:
            out = nn.Dropout(cfg.dropout, deterministic=not train)(out)
        return out


def _hc_alpha_init(key, shape, dtype=jnp.float32):
    """(a_pre, a_post, a_res) about (1, 1, 4): ``m`` has unit variance, so at
    ``a_res`` 4 a token's ``exp(A)`` spans e^-8 .. e^8, far from doubly
    stochastic: the Sinkhorn rounds do work that two rounds would not, which a
    check on seeded weights can then tell (PERF.md, section 6, PR 39)."""
    return (jnp.asarray([1.0, 1.0, 4.0]) * (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32))
            ).astype(dtype)


class HyperConnection(nn.Module):
    """One sublayer's hyper-connection (``TransformerConfig.hc_mult``;
    ``ops/mhc.py`` has the mathematics): ``phi`` [n * hidden, n^2 + 2n], ``b``
    [n^2 + 2n] and ``alpha`` = (a_pre, a_post, a_res), from which every token's
    ``Mix`` of the ``[n, B, S, hidden]`` streams is made. Every leaf is drawn
    NONZERO and spread: at zero the mix would be the same for every token and
    the matrix uniform after one Sinkhorn round, and nothing could tell
    whether the product with ``phi`` or the iterations were computed."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, streams):
        from deepspeed_tpu.ops import mhc
        from deepspeed_tpu.parallel.moe import _nonzero_normal

        cfg = self.config
        n = cfg.hc_mult
        rows, cols = n * cfg.hidden_size, n * n + 2 * n
        phi = self.param("phi", nn.initializers.normal(rows ** -0.5), (rows, cols), cfg.param_dtype)
        b = self.param("b", _nonzero_normal(1.0), (cols,), cfg.param_dtype)
        alpha = self.param("alpha", _hc_alpha_init, (3,), cfg.param_dtype)
        return mhc.mix(streams, phi, b, alpha, norm_eps=cfg.norm_eps, iters=cfg.hc_sinkhorn_iters,
                       eps=cfg.hc_eps, clamp=cfg.hc_res_clamp)


def _times(multiplier: float, x):
    """``multiplier * x``; at 1, ``x`` itself: a model without the multiplier
    traces the program it always did."""
    return x if multiplier == 1.0 else x * jnp.asarray(multiplier, x.dtype)


class Block(nn.Module):
    # ``train`` is a module attribute (not a call kwarg) because nn.scan does
    # not forward kwargs through the scanned call.
    config: TransformerConfig
    train: bool = False
    layer_idx: int = 0  # selects the pyramid expert count (PR-MoE)
    dense: bool = False  # a leading dense layer of a routed model (first_dense_layers)
    kind: str = "attention"  # the mixer, one of LAYER_KINDS (``TransformerConfig.layer_types``)

    @nn.compact
    def __call__(self, carry, _=None):
        from deepspeed_tpu.ops import mhc

        cfg = self.config
        attn_cls = (LatentAttention if cfg.latent_attention
                    else EvaAttention if cfg.eva_window else Attention)
        if cfg.sliding is not None:  # this layer's attention kind: its band, and whether it rotates
            attn_cls = functools.partial(Attention, **sliding_kind(cfg, self.kind))
        cap_scale = None
        if cfg.moe_dynamic_capacity:
            # dynamic capacity rides the carry as a traced fp32 scalar (the
            # engine's autotuning controller feeds it through the batch) —
            # dense layers pass it through untouched
            x, mask, positions, aux, cap_scale = carry
        else:
            x, mask, positions, aux = carry
        if cfg.parallel_block:
            # x = x + attn(ln1(x)) + mlp(h); h = ln1(x) shared (falcon) or a
            # separate ln2(x) (gpt-neox parallel_mlp_norm)
            x_in = x
            h = _norm(cfg, "attn_norm")(x_in)
            x = x + attn_cls(cfg, name="attn")(h, mask, positions, self.train)
            if cfg.parallel_mlp_norm:
                h = _norm(cfg, "mlp_norm")(x_in)
        elif cfg.hc_mult:
            # ``x`` is the streams: a sublayer reads their mix and its output
            # is written back through H_res and H_post (``ops/mhc.py``); the
            # modules ``attn_hc`` and ``mlp_hc`` hold each one's phi, b, alpha
            mixed = HyperConnection(cfg, name="attn_hc")(x)
            with jax.named_scope("attn_hc"):
                u = mhc.read(x, mixed)
            out = attn_cls(cfg, name="attn")(_norm(cfg, "attn_norm")(u), mask, positions, self.train)
            with jax.named_scope("attn_hc"):
                x = mhc.write(x, out, mixed)
            mixed = HyperConnection(cfg, name="mlp_hc")(x)
            with jax.named_scope("mlp_hc"):
                u = mhc.read(x, mixed)
            h = _norm(cfg, "mlp_norm")(u)
        elif self.kind == "mamba":
            x = x + _times(cfg.residual_multiplier, Mamba2Mixer(cfg, name="ssm")(
                _norm(cfg, "ssm_pre_norm")(x), mask, positions, self.train))
            h = _norm(cfg, "mlp_norm")(x)
        elif self.kind == "linear_attention":
            x = x + _times(cfg.residual_multiplier, GatedDeltaNet(cfg, name="gdn")(
                _norm(cfg, "gdn_pre_norm")(x), mask, positions, self.train))
            h = _norm(cfg, "mlp_norm")(x)
        else:
            x = x + _times(cfg.residual_multiplier, attn_cls(cfg, name="attn")(
                _norm(cfg, "attn_norm")(x), mask, positions, self.train
            ))
            h = _norm(cfg, "mlp_norm")(x)

        def add(out):  # the feed-forward's write-back
            if cfg.hc_mult:
                with jax.named_scope("mlp_hc"):
                    return mhc.write(x, out, mixed)
            return x + _times(cfg.residual_multiplier, out)

        # the scanned stack is built with layer_idx 0, so a leading dense
        # layer says so itself and is not looked up by its index
        n_exp = 0 if self.dense else (cfg.moe_layer_experts[self.layer_idx]
                                      if cfg.moe_layer_experts is not None else cfg.num_experts)
        # moe_metrics rides the aux carry as (scalar, stats-dict) — the
        # structure is decided once by CausalLM (dense layers pass it through
        # untouched, so the scan carry stays consistent across the stack)
        collect = cfg.moe_metrics and self.train and cfg.has_moe
        if n_exp > 0 and cfg.drop_free_moe:
            # drop-free by construction: no capacity, no auxiliary loss
            from deepspeed_tpu.parallel.moe import DropFreeMoE

            if cfg.norm == "layernorm":  # flax's LayerNorm hands its float32 sums on; a Dense casts them, this does not
                h = h.astype(cfg.dtype)
            with jax.named_scope("moe"):
                x = add(DropFreeMoE(cfg, name="moe")(h))  # the add reads ``moe``, as it did
        elif n_exp > 0:
            from deepspeed_tpu.parallel.moe import MoEConfig, MoELayer

            moe_cfg = MoEConfig(
                num_experts=n_exp,
                top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                min_capacity=cfg.moe_min_capacity,
                drop_tokens=cfg.moe_drop_tokens,
                aux_loss_weight=cfg.moe_aux_loss_weight,
                collect_metrics=collect,
                dispatch=cfg.moe_dispatch,
                max_capacity_factor=(cfg.moe_capacity_factor_max
                                     if cfg.moe_dynamic_capacity else None),
            )
            moe_out = MoELayer(
                moe_cfg, cfg.hidden_size, cfg.intermediate_size,
                activation=cfg.activation, dtype=cfg.dtype, train=self.train,
                use_residual=cfg.moe_use_residual,
                name="moe",
            )(h, cap_scale)
            if collect:
                l_aux, out, stats = moe_out
                aux_sum, stats_acc = aux
                aux = (aux_sum + l_aux,
                       {k: stats_acc[k] + stats[k] for k in stats_acc})
            else:
                l_aux, out = moe_out
                aux = aux + l_aux
            x = add(out)
        else:
            x = add(MLP(cfg, name="mlp")(h, self.train))
        if cfg.moe_dynamic_capacity:
            return (x, mask, positions, aux, cap_scale), None
        return (x, mask, positions, aux), None


class Period(nn.Module):
    """One period of a layer pattern (``TransformerConfig.period``): its
    blocks, each of its own kind, as ``layer_<j>``. What ``CausalLM`` scans
    where the stack is not homogeneous; a parameter's leading axis is then the
    period, and layer ``i`` of the model is period ``i // len(period)``'s
    ``layer_<i % len(period)>``."""

    config: TransformerConfig
    train: bool = False

    @nn.compact
    def __call__(self, carry, _=None):
        cfg = self.config
        block_cls = nn.remat(Block, prevent_cse=False) if cfg.remat else Block
        for j, kind in enumerate(cfg.period):
            carry, _ = block_cls(cfg, self.train, kind=kind, name=f"layer_{j}")(carry, None)
        return carry, None


class _HeadKernel(nn.Module):
    """Declares the untied LM-head kernel param without running the matmul —
    the fused-CE path reads the weight directly. Param path/shape/init match
    ``nn.Dense(name="lm_head")`` exactly so both paths share one parameter."""

    hidden: int
    vocab: int
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel", nn.initializers.lecun_normal(), (self.hidden, self.vocab), self.param_dtype
        )


class CausalLM(nn.Module):
    """Decoder-only LM. batch: {'input_ids': [B,S], optional 'labels',
    'attention_mask', 'position_ids'} -> (loss, logits). On the training path
    with ``fused_ce`` active, logits is None (the fused chunked-vocab CE never
    materializes it)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, batch, train: bool = False):
        cfg = self.config
        ids = batch["input_ids"]
        B, S = ids.shape
        positions = batch.get("position_ids")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        pad_mask = batch.get("attention_mask")  # [B, S] 1=keep

        embed_cls = _SparseGradEmbed if cfg.sparse_embedding_grads else nn.Embed
        # Under a learned indexer the embedding is drawn at unit variance. flax's own draw (hidden^-1/2 a
        # column) leaves the first layer's attention output nine tenths of the stream its MLP reads, so the
        # handful of boundary tokens that two roundings of one index score choose differently there move
        # every later layer's selection (benchmarks/configs/glm-5.json, assumed.weights).
        drawn = {"embedding_init": nn.initializers.normal(1.0)} if cfg.index_topk else {}
        x = embed_cls(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                      param_dtype=cfg.param_dtype, name="embed", **drawn)(ids)
        x = _times(cfg.embedding_multiplier, x)
        if cfg.fp32_residual:
            x = x.astype(jnp.float32)
        if cfg.embed_norm:
            x = _norm(cfg, "embed_norm")(x)
        if cfg.position == "learned":
            pos_emb = self.param(
                "pos_embed", nn.initializers.normal(0.02), (cfg.max_seq_len, cfg.hidden_size)
            )
            x = x + pos_emb[None, :S, :].astype(cfg.dtype)

        aux = jnp.zeros((), jnp.float32)
        collect_moe = cfg.moe_metrics and train and cfg.has_moe
        if collect_moe:
            from deepspeed_tpu.parallel.moe import (MOE_DYNAMIC_STAT_KEYS,
                                                    MOE_STAT_KEYS)

            # (aux-loss sum, per-layer stat sums) — averaged over MoE layers
            # below; Block keeps this structure through the whole stack.
            # Dynamic-capacity gates additionally report the enforced factor.
            keys = (MOE_DYNAMIC_STAT_KEYS if (cfg.moe_dynamic_capacity and train)
                    else MOE_STAT_KEYS)
            aux = (aux, {k: jnp.zeros((), jnp.float32) for k in keys})
        block_cls = Block
        if cfg.remat:
            block_cls = nn.remat(Block, prevent_cse=False)
        if cfg.scan_layers and cfg.moe_layer_experts is not None:
            raise ValueError(
                "pyramid MoE (moe_layer_experts) needs scan_layers=False: "
                "heterogeneous expert counts cannot stack into one scan"
            )
        if cfg.hc_mult:
            from deepspeed_tpu.ops import mhc

            x = mhc.spread(x, cfg.hc_mult)  # [n, B, S, hidden]: every stream starts as the embedding
        carry = (x, pad_mask, positions, aux)
        if cfg.moe_dynamic_capacity:
            # the autotuning controller's knob: a traced fp32 scalar the
            # engine injects per step (falls back to the configured static
            # factor — same program either way, only the value moves)
            cap = batch.get("moe_capacity_factor")
            cap = (jnp.float32(cfg.moe_capacity_factor) if cap is None
                   else jnp.asarray(cap, jnp.float32).reshape(()))
            carry = carry + (cap,)
        if cfg.scan_layers:
            for i in range(cfg.first_dense_layers):  # outside the scan, each its own tree (and of its own kind)
                kind = {"kind": cfg.layer_types[i]} if cfg.layer_types else {}
                carry, _ = block_cls(cfg, train, dense=True, name=f"dense_{i}", **kind)(carry, None)
            # a layer pattern is scanned a whole period a step (``Period`` applies ``remat`` a block)
            pattern = cfg.layer_types is not None
            stack = nn.scan(
                Period if pattern else block_cls,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                length=(cfg.num_layers - cfg.first_dense_layers) // (len(cfg.period) if pattern else 1),
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, train, name="layers")
            # flax names the BODY ``layers``; what the scan itself does (the
            # stacking of saved residuals, the slices of stacked parameters)
            # reads ``layer_scan/while/body`` with no ``layers`` in a trace
            with jax.named_scope("layer_scan"):
                carry, _ = stack(carry, None)
        else:
            for i in range(cfg.num_layers):
                carry, _ = block_cls(cfg, train, layer_idx=i, dense=i < cfg.first_dense_layers,
                                     kind=cfg.layer_types[i] if cfg.layer_types else "attention",
                                     name=f"layer_{i}")(carry, None)
        x, aux = carry[0], carry[3]
        if cfg.hc_mult:
            x = mhc.collapse(x)  # the streams summed: no learned collapse (the config has no key for one)

        moe_stats = None
        if collect_moe:
            aux, stat_sums = aux
            n_moe = max(cfg.num_moe_layers, 1)
            moe_stats = {k: v / n_moe for k, v in stat_sums.items()}

        # logits / logits_scaling, as the final hidden state times its inverse: the fused head never forms logits
        x = _times(1.0 / cfg.logits_scaling, _norm(cfg, "final_norm")(x))
        labels = batch.get("labels")
        if labels is None:
            labels = jnp.concatenate([ids[:, 1:], jnp.full((B, 1), -100, dtype=ids.dtype)], axis=1)

        use_fused = (train and cfg.fused_ce and cfg.vocab_size >= cfg.fused_ce_min_vocab
                     and not cfg.lm_head_bias)
        # ``lm_head_ce`` names head matmul + cross-entropy, fused or not, in
        # a device trace (jvp(..) and transpose(jvp(..)) under autodiff)
        with jax.named_scope("lm_head_ce"):
            if use_fused:
                # fused chunked-vocab LM head + CE: no [B,S,V] logits in HBM
                # (see ops/cross_entropy.py). Training returns logits=None.
                from deepspeed_tpu.ops.cross_entropy import lm_head_cross_entropy

                if cfg.tie_embeddings:
                    head = self.variables["params"]["embed"]["embedding"]  # [V, h]
                else:
                    head = _HeadKernel(cfg.hidden_size, cfg.vocab_size * cfg.num_pred_heads,
                                       cfg.param_dtype, name="lm_head")()[:, :cfg.vocab_size].T
                loss = lm_head_cross_entropy(x, head.astype(cfg.dtype), labels, pad_mask)
                logits = None
            else:
                if cfg.tie_embeddings:
                    embed = self.variables["params"]["embed"]["embedding"]
                    logits = x @ embed.T.astype(cfg.dtype)
                elif cfg.num_pred_heads > 1:
                    # head 0 of [hidden, heads * vocab], fp32 logits
                    head = _HeadKernel(cfg.hidden_size, cfg.vocab_size * cfg.num_pred_heads,
                                       cfg.param_dtype, name="lm_head")()
                    logits = jnp.dot(x, head[:, :cfg.vocab_size].astype(cfg.dtype),
                                     preferred_element_type=jnp.float32)
                else:
                    logits = nn.Dense(cfg.vocab_size, use_bias=cfg.lm_head_bias, dtype=cfg.dtype,
                                      param_dtype=cfg.param_dtype, name="lm_head")(x)
                loss = cross_entropy_loss(logits, labels, pad_mask)
        if cfg.has_moe:
            # aux is pre-weighted by MoELayer; average over layers
            loss = loss + aux / cfg.num_layers
        if moe_stats is not None:
            # engine contract (_loss_and_aux): a trailing dict of scalars is
            # the device-computed stats side channel (moe/* gauges)
            return loss, logits, moe_stats
        return loss, logits


# --------------------------------------------------- pipelined execution
@jax.named_scope("embed")  # the scope flax's ``embed`` module gives CausalLM
def _embed_tokens(params, cfg: TransformerConfig, ids):
    """Functional twin of the embedding front-end of ``CausalLM.__call__``."""
    x = _times(cfg.embedding_multiplier, jnp.take(params["embed"]["embedding"], ids, axis=0).astype(cfg.dtype))
    if cfg.embed_norm:
        x = _apply_norm(params["embed_norm"], cfg, x)
    if cfg.position == "learned":
        x = x + params["pos_embed"][None, : ids.shape[1], :].astype(cfg.dtype)
    return x


@contextlib.contextmanager
def reading(tree, key: str):
    """``tree[key]``, inside the device-trace scope ``key``. The functional
    twins of the flax modules name what they compute by the parameter key
    they read, which is the name flax gives the module that owns it
    (``layers/attn/wq/dot_general``): one vocabulary in training and serving,
    taken from the tree ``CausalLM.init`` makes. HLO metadata only."""
    with jax.named_scope(key):
        yield tree[key]


def _apply_norm(norm_params, cfg: TransformerConfig, x):
    """Functional twin of ``_norm`` (RMSNorm / flax LayerNorm)."""
    if cfg.norm == "rmsnorm":
        from deepspeed_tpu.ops import rms_norm

        scale = norm_params["scale"]
        if cfg.norm_unit_offset:
            scale = 1.0 + scale.astype(jnp.float32)
        y = rms_norm(x, scale, eps=cfg.norm_eps)
        return y.astype(cfg.dtype) if cfg.fp32_residual else y
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
    y = y * norm_params["scale"].astype(jnp.float32)
    if "bias" in norm_params:
        y = y + norm_params["bias"].astype(jnp.float32)
    return y.astype(cfg.dtype)


def _norm_at(tree, key: str, cfg: TransformerConfig, x):
    """``_apply_norm`` with the parameters ``tree[key]``, under that key's scope."""
    with reading(tree, key) as p:
        return _apply_norm(p, cfg, x)


def _lm_head_and_loss(params, cfg: TransformerConfig, x, batch, aux):
    x = _times(1.0 / cfg.logits_scaling, _norm_at(params, "final_norm", cfg, x))
    ids = batch["input_ids"]
    labels = batch.get("labels")
    if labels is None:
        B = ids.shape[0]
        labels = jnp.concatenate([ids[:, 1:], jnp.full((B, 1), -100, dtype=ids.dtype)], axis=1)
    with jax.named_scope("lm_head_ce"):
        if cfg.tie_embeddings:
            logits = x @ params["embed"]["embedding"].T.astype(cfg.dtype)
        else:
            logits = x @ params["lm_head"]["kernel"].astype(cfg.dtype)
            if "bias" in params["lm_head"]:
                logits = logits + params["lm_head"]["bias"].astype(cfg.dtype)
        loss = cross_entropy_loss(logits, labels, batch.get("attention_mask"))
    if cfg.has_moe:
        loss = loss + aux / cfg.num_layers
    return loss, logits


def pipelined_causal_lm_loss(params, batch, rng, *, config: TransformerConfig,
                             num_microbatches: int, mesh, train: bool = True,
                             virtual_stages: int = 1):
    """CausalLM forward+loss with the layer stack executed as an SPMD pipeline
    over the ``pp`` mesh axis (see ``parallel/pipeline_spmd.spmd_pipeline``).

    Embedding and the LM head run outside the pipeline (replicated over pp,
    sharded over dp/tp as usual); the batch splits into ``num_microbatches``
    along dim 0. For dense models this is numerically identical to the
    unpipelined model (same param tree; dropout patterns differ). For MoE
    models, gate capacity and the load-balancing aux loss are computed
    per-microbatch rather than over the full batch — the same per-microbatch
    routing semantics the reference has under gradient accumulation.
    """
    from deepspeed_tpu.parallel.pipeline_spmd import spmd_pipeline_interleaved

    cfg = config
    if not cfg.scan_layers:
        raise ValueError("pipelined execution requires scan_layers=True (stacked layer params)")
    if cfg.layer_types is not None:
        raise ValueError("pipelined execution of a layer pattern (layer_types): a stage scans one Block "
                         "over its share of a homogeneous stack, and a period's layers are not one Block")
    if cfg.moe_metrics and train and cfg.has_moe:
        raise ValueError(
            "moe_metrics is not wired through the pipelined loss path (the "
            "stats dict cannot ride the pp activation ring) — the engine "
            "skips the rebuild on pp>1 meshes; construct with "
            "moe_metrics=False for pipelined MoE")
    if cfg.moe_dynamic_capacity:
        raise ValueError(
            "moe_capacity_factor_max (capacity autotuning) is not wired "
            "through the pipelined loss path (the capacity scalar cannot "
            "ride the pp activation ring) — the engine skips it on pp>1 "
            "meshes; construct without moe_capacity_factor_max")
    M = num_microbatches
    ids = batch["input_ids"]
    B, S = ids.shape
    if B % M:
        raise ValueError(f"batch {B} not divisible by pipeline microbatches {M}")
    positions = batch.get("position_ids")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    pad_mask = batch.get("attention_mask")

    x = _embed_tokens(params, cfg, ids)
    split = lambda v: v.reshape((M, B // M) + v.shape[1:])
    # Activations + aux ride the ring; mask/positions are stage-invariant and
    # go through side_stream (indexed locally, no inter-stage comm).
    stream = (split(x), jnp.zeros((M,), jnp.float32))
    side = (None if pad_mask is None else split(pad_mask), split(positions))

    block = Block(cfg, train)

    def stage_fn(stage_layers, carry, side, srng):
        x, aux = carry
        mask, pos = side
        n_local = jax.tree_util.tree_leaves(stage_layers)[0].shape[0]
        rngs = jax.random.split(srng, n_local)

        def body(c, xs):
            lp, r = xs
            c2, _ = block.apply({"params": lp}, c, rngs={"dropout": r})
            return c2, None

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, _, _, aux), _ = jax.lax.scan(body, (x, mask, pos, aux), (stage_layers, rngs))
        return (x, aux)

    # virtual <= 1 delegates to the plain fill-and-drain pipeline
    x_out, aux = spmd_pipeline_interleaved(
        stage_fn, params["layers"], stream, mesh=mesh, rng=rng,
        side_stream=side, virtual=virtual_stages,
    )
    x_full = x_out.reshape((B,) + x_out.shape[2:])
    # Equal-size microbatches: mean of per-microbatch means == full-batch mean.
    return _lm_head_and_loss(params, cfg, x_full, batch, aux.mean())


def cross_entropy_loss(logits, labels, pad_mask=None, ignore_index: int = -100):
    """Mean token cross entropy in fp32 with ignore mask."""
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    if pad_mask is not None:
        valid = valid & (pad_mask > 0)
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)


# ------------------------------------------------------- tensor parallelism
def causal_lm_partition_rules(path: str, shape: tuple) -> Optional[P]:
    """AutoTP-style placement rules for CausalLM parameters.

    Column-parallel: q/k/v, gate/up projections, lm_head (output dim over tp).
    Row-parallel: o and down projections (input dim over tp).
    Embedding: vocab dim over tp. Right-aligned so the scan's leading layer
    dimension stays unsharded. (Reference analog: ``module_inject/auto_tp.py``
    tp_parser + LinearLayer/LinearAllreduce.)

    ``path`` is a ``jax.tree_util.keystr`` string, i.e. bracket form like
    ``"['layers']['attn']['wq']['kernel']"`` — match whole quoted names.
    """

    def has(token: str) -> bool:
        return f"'{token}'" in path

    def right(*entries):
        pad = len(shape) - len(entries)
        if pad < 0:
            return None
        return P(*([None] * pad + list(entries)))

    if has("experts") or has("gate"):
        from deepspeed_tpu.parallel.moe import moe_partition_rules

        return moe_partition_rules(path, shape)
    if has("pos_embed"):
        return None
    if has("embed") and has("embedding"):
        return right("tp", None)
    kernel = has("kernel")
    if kernel and (has("wq") or has("wk") or has("wv")):
        # DenseGeneral kernel [emb, heads, head_dim]: shard heads over tp
        return right(None, "tp", None) if len(shape) >= 3 else right(None, "tp")
    if kernel and has("wo"):
        # DenseGeneral kernel [heads, head_dim, emb]: shard heads over tp
        return right("tp", None, None) if len(shape) >= 3 else right("tp", None)
    if kernel and (has("w_gate") or has("w_up")):
        return right(None, "tp")
    if kernel and has("w_down"):
        return right("tp", None)
    if kernel and has("lm_head"):
        return right(None, "tp")
    if has("bias"):
        # biases of column-parallel layers follow the output (head) dim
        if has("wq") or has("wk") or has("wv"):
            return right("tp", None) if len(shape) >= 2 else None
        if has("w_gate") or has("w_up"):
            return right("tp")
    return None


def pipeline_partition_rules(path: str, shape: tuple) -> Optional[P]:
    """Partition rules with the stacked layer dim sharded over ``pp``.

    Composes with the tp rules (which are right-aligned, leaving dim 0 free on
    scanned-layer leaves). With a pp=1 mesh the ``pp`` entry is a no-op, so
    these rules are safe unconditionally for pipelined specs.
    """
    base = causal_lm_partition_rules(path, shape)
    if "'layers'" in path:
        entries = list(base) if base is not None else []
        entries += [None] * (len(shape) - len(entries))
        if entries and entries[0] is None:
            entries[0] = "pp"
        return P(*entries)
    return base


def causal_lm_spec(
    config: TransformerConfig,
    example_seq_len: int = 8,
    pipeline_microbatches: int = 0,
    pipeline_virtual_stages: int = 1,
) -> ModelSpec:
    """Build the engine-facing ModelSpec for a CausalLM.

    ``pipeline_microbatches > 1`` enables pipelined execution of the layer
    stack over the mesh's ``pp`` axis (reference ``PipelineModule`` +
    ``PipelineEngine`` path); with pp == 1 the plain forward is used.
    """
    module = CausalLM(config)
    example = {"input_ids": jnp.zeros((2, example_seq_len), jnp.int32)}

    def init_fn(rng):
        p_rng, d_rng = jax.random.split(rng)
        return module.init({"params": p_rng, "dropout": d_rng}, example, train=False)["params"]

    def loss_fn(params, batch, rng):
        if pipeline_microbatches > 1:
            from deepspeed_tpu.topology.mesh import get_mesh, has_mesh

            if has_mesh() and get_mesh().shape["pp"] > 1:
                return pipelined_causal_lm_loss(
                    params, batch, rng, config=config,
                    num_microbatches=pipeline_microbatches,
                    mesh=get_mesh(), train=True,
                    virtual_stages=pipeline_virtual_stages,
                )
        return module.apply({"params": params}, batch, train=True, rngs={"dropout": rng})

    def apply_fn(params, batch):
        return module.apply({"params": params}, batch, train=False)

    return ModelSpec(
        init_fn=init_fn,
        loss_fn=loss_fn,
        apply_fn=apply_fn,
        name=f"CausalLM({config.hidden_size}x{config.num_layers})",
        partition_rules=pipeline_partition_rules if pipeline_microbatches > 1 else causal_lm_partition_rules,
        model_config=config,
        # lets the engine re-derive the spec with config tweaks it owns
        # (e.g. sparse_embedding_grads from DS `sparse_gradients: true`)
        rebuild=lambda new_cfg: causal_lm_spec(
            new_cfg, example_seq_len=example_seq_len,
            pipeline_microbatches=pipeline_microbatches,
            pipeline_virtual_stages=pipeline_virtual_stages),
    )
