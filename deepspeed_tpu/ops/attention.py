"""Attention ops.

The XLA implementation is the universal fallback (fused by the compiler); the
Pallas flash kernel (``ops/pallas/flash_attention.py``) registers under the
same op name and wins dispatch on TPU. Reference analog: the inference/training
softmax+context CUDA kernels (``csrc/transformer/inference/csrc/softmax.cu``
etc.) and Triton flash variants (``ops/transformer/inference/triton/``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.registry import available_impls, dispatch, register

_NEG_INF = -1e9  # mask fill well below any real score but finite for fp16 safety


@register("causal_attention", "xla")
def _xla_causal_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, D]
    mask: Optional[jax.Array] = None,  # [B, S] 1=keep (padding mask)
    alibi_slopes: Optional[jax.Array] = None,  # [H] bloom-style score biases
    bias: Optional[jax.Array] = None,  # [H, S, S] or [B, H, S, S] additive
    causal: bool = True,
    softmax_scale: Optional[float] = None,  # None: D^-0.5
    window: Optional[int] = None,  # a band: query t sees keys j with 0 <= t - j < window
    lengths: Optional[jax.Array] = None,  # [B] int32: each row's live tokens, which come first; a pad's row is zeros
    sink: Optional[jax.Array] = None,  # [H]: a logit a query head in the softmax's denominator alone
) -> jax.Array:
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    assert H % Hkv == 0, f"query heads {H} not a multiple of kv heads {Hkv}"
    G = H // Hkv

    qg = q.reshape(B, S, Hkv, G, D).astype(jnp.float32) * (D**-0.5 if softmax_scale is None else softmax_scale)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))

    if alibi_slopes is not None:
        # slopes * key-position; equal to slopes*(j-i) up to a per-row
        # constant, which softmax cancels (same convention as HF bloom, so
        # ingested checkpoints reproduce bit-comparable logits). XLA fuses
        # this broadcast into the masked add — no [H,S,S] buffer.
        kpos = jnp.arange(S, dtype=jnp.float32)
        scores = scores + (alibi_slopes.reshape(Hkv, G)[None, :, :, None, None]
                           * kpos[None, None, None, None, :])
    if bias is not None:
        # evoformer-style pair bias (reference csrc/deepspeed4science/
        # evoformer_attn): broadcast [.., H, S, S] onto the grouped layout
        b5 = bias if bias.ndim == 4 else bias[None]
        scores = scores + b5.reshape(b5.shape[0], Hkv, G, S, S).astype(jnp.float32)

    keep = None
    if causal:
        keep = jnp.tril(jnp.ones((S, S), bool))[None, None, None]
    if window is not None:
        band = band_keep(jnp.arange(S)[:, None], jnp.arange(S)[None, :], window)[None, None, None]
        keep = band if keep is None else keep & band
    if mask is not None:
        m = mask[:, None, None, None, :] > 0
        keep = m if keep is None else keep & m
    if keep is not None:
        scores = jnp.where(keep, scores, _NEG_INF)
    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1)
    else:  # p_tj = exp(a_tj) / (exp(s_h) + sum_k exp(a_tk)): the sink is a column that weighs no value
        column = jnp.broadcast_to(sink.astype(jnp.float32).reshape(1, Hkv, G, 1, 1), scores.shape[:-1] + (1,))
        probs = jax.nn.softmax(jnp.concatenate([scores, column], axis=-1), axis=-1)[..., :-1]
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v).reshape(B, S, H, v.shape[-1])
    if lengths is not None:  # what the flash forward hands back for a pad (``_flash_fwd``)
        out = jnp.where((jnp.arange(S) < lengths[:, None])[:, :, None, None], out, jnp.zeros((), out.dtype))
    return out


def band_keep(q_positions, k_positions, window: int):
    """THE statement of a sliding window: a query at ``t`` sees the key at ``j`` only while ``t - j <
    window`` (its own key counted; causality is the caller's). The dense fallback, the banded flash
    forward's edge cells and the paged path's first live slot all say this."""
    return q_positions - k_positions < window


def first_live(q_positions, window: int):
    """The oldest position a query at ``q_positions`` still sees under ``band_keep``."""
    return jnp.maximum(q_positions - (window - 1), 0)


def resolves_to_flash(impl: str = "auto") -> bool:
    """Whether a model configured with this ``attn_impl`` would actually run
    the non-materializing Pallas flash kernel — i.e. the SAME resolution
    ``dispatch`` performs at call time, so memory estimates cannot diverge
    from what dispatches (e.g. 'flash' silently falls back to the
    materializing XLA attention when the kernel failed to import). 'sparse'
    and 'fpdt' branch before this op and materialize score-class workspace,
    so they are never flash for estimation purposes."""
    if impl in ("sparse", "fpdt"):
        return False
    pallas = available_impls("causal_attention").get("pallas")
    return pallas is not None and dispatch("causal_attention", impl) is pallas


def causal_attention(q, k, v, mask=None, impl: str = "auto",
                     alibi_slopes=None, bias=None, softmax_scale=None, window=None, lengths=None, sink=None,
                     **kernel_kwargs):
    """Grouped-query causal attention with optional ALiBi slopes and additive
    pair bias. ALiBi is fused into the Pallas flash kernels (slope * column
    iota — no bias tiles) so bloom-style training keeps the flash path; the
    slopes are treated as NON-LEARNED positional constants there (their
    gradient is stopped — pass impl='xla' to differentiate learned slopes).
    Dense pair bias rides the XLA path (fully differentiable — the evoformer
    training case needs d_bias).

    ``softmax_scale`` replaces the scores' ``D^-0.5`` (YaRN's latent attention
    states its own: ``TransformerConfig.latent_rotary``); it goes to whichever
    implementation runs, and is handed over only where it is given.

    ``window`` (static) lays a band under the causal mask (``band_keep``): on the
    Pallas path the forward runs no grid cell wholly under the band
    (``flash_banded_forward``: a forward alone, its backward refused by name);
    with a padding mask, ALiBi or a pair bias beside it the dense path runs.

    ``lengths`` (int32 ``[B]``) says that a row's live tokens come FIRST and how
    many they are: a fresh prompt padded to its bucket. It is a word of the
    forward alone, as the band is (differentiating through it is refused by
    name): the pads' rows come back as zeros from every implementation, and
    the Pallas forward runs no grid cell whose queries are all pads
    (``flash_attention.py::_flash_fwd``), under the band as under the causal
    mask alone. Beside a padding mask, ALiBi or a pair bias the dense path runs.

    ``sink`` ([H], a learned logit a query head that joins the softmax's
    denominator and nothing else) and a value narrower than its key (``v``'s
    last dimension apart from ``k``'s) are words of the forward alone too: on
    the Pallas path the kernels of the band and the lengths take them.

    kernel_kwargs (block_q / block_k / k_splits) are Pallas scheduling knobs
    with identical math — they are forwarded only when dispatch resolves to
    the pallas kernel and dropped on the XLA path (which has no blocking)."""
    scaled = {} if softmax_scale is None else {"softmax_scale": softmax_scale}
    if bias is not None:
        return _xla_causal_attention(q, k, v, mask=mask, alibi_slopes=alibi_slopes, bias=bias, window=window,
                                     lengths=lengths, sink=sink, **scaled)
    fn = dispatch("causal_attention", impl)
    if window is not None or lengths is not None or sink is not None or v.shape[-1] != k.shape[-1]:
        if fn is available_impls("causal_attention").get("pallas") and mask is None and alibi_slopes is None:
            kw = {key: val for key, val in kernel_kwargs.items() if key in ("block_q", "block_k")}
            return _per_shard_flash(fn, q, k, v, None, None, dict(kw, window=window, **scaled), lengths, sink)
        return _xla_causal_attention(q, k, v, mask=mask, alibi_slopes=alibi_slopes, window=window,
                                     lengths=lengths, sink=sink, **scaled)
    if fn is available_impls("causal_attention").get("pallas"):
        return _per_shard_flash(fn, q, k, v, mask, alibi_slopes, dict(kernel_kwargs, **scaled))
    if alibi_slopes is not None:
        return fn(q, k, v, mask=mask, alibi_slopes=alibi_slopes, **scaled)
    return fn(q, k, v, mask=mask, **scaled)


def evoformer_attention(q, k, v, pair_bias=None, mask=None):
    """DS4Science evoformer attention (reference
    ``csrc/deepspeed4science/evoformer_attn/`` — CUTLASS attention with
    broadcast bias for AlphaFold-family models): BIDIRECTIONAL attention over
    residue/MSA axes with an additive pair-representation bias and an optional
    keep-mask. Fully differentiable including d(pair_bias).

    q/k/v: [B, S, H, D]; pair_bias: [H, S, S] or [B, H, S, S]; mask: [B, S].
    """
    return _xla_causal_attention(q, k, v, mask=mask, bias=pair_bias, causal=False)


def _per_shard_flash(fn, q, k, v, mask, alibi_slopes, kernel_kwargs, lengths=None, sink=None):
    """The flash kernel under GSPMD (``ops/partition.py``): attention is
    independent per (batch row, kv-head group), so batch splits over the
    data axes (the rows' live ``lengths`` with it) and heads over sp (the
    Ulysses head shard) and tp (a head's ``sink`` with them)."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.partition import kernel_mesh, live_axes, per_shard
    from deepspeed_tpu.topology.mesh import BATCH_AXES

    def call(q, k, v, mask, slopes, lengths, sink):
        kw = dict(kernel_kwargs)
        if slopes is not None:
            kw["alibi_slopes"] = slopes
        if lengths is not None:
            kw["lengths"] = lengths
        if sink is not None:
            kw["sink"] = sink
        return fn(q, k, v, mask=mask, **kw)

    ctx = kernel_mesh()
    if ctx is None:
        return call(q, k, v, mask, alibi_slopes, lengths, sink)
    mesh, free = ctx
    b = live_axes(mesh, free, BATCH_AXES, q.shape[0])
    # kv heads must split like q heads: GQA groups stay whole on a device
    h = live_axes(mesh, free, ("sp", "tp"), q.shape[2], k.shape[2])
    qkv = P(b, None, h, None)
    in_specs = (qkv, qkv, qkv, None if mask is None else P(b, None),
                None if alibi_slopes is None else P(h), None if lengths is None else P(b),
                None if sink is None else P(h))
    return per_shard(call, mesh, free, in_specs, qkv)(q, k, v, mask, alibi_slopes, lengths, sink)
