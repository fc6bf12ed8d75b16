"""Paged KV cache + ragged forward step (device side).

TPU-native analog of the reference FastGen kernel suite
(``inference/v2/kernels/ragged_ops/``: ``blocked_flash`` paged attention,
``linear_blocked_kv_rotary`` fused KV-insert+RoPE): the KV pool is ONE
page-major array ``[L*NB, bs, kvH*hd]`` — every layer's pages in a row, a
page being a lane-dense ``[bs, kvH*hd]`` slab, the layout the paged kernel
DMAs from, so nothing re-lays it out between the write and the read. Layer
``l``'s page ``p`` is row ``l*NB + p``. A sequence's cache is addressed
through its block table, and one jitted step processes a mixed
prefill/decode ragged batch:

  - the layer scan CARRIES the pool and updates it in place: no per-layer
    slice of it is taken and no second pool is stacked up
  - KV insert = one scatter per layer (``.at[row, slot].set``) of the new
    tokens' ``[kvH*hd]`` rows at ``(layer*NB + block_table[pos // bs],
    pos % bs)`` — the fused-KV-copy+RoPE kernel. Pad-row writes index one
    past the last page and drop (``mode="drop"``): there is no trash slot
  - paged attention reads the same array through ``block_table + layer*NB``:
    the Pallas flash-decode kernel (``ops/pallas/paged_attention.py``) DMAs
    the row's live pages, the XLA fallback gathers them to ``[P*bs, kvH, hd]``
    then runs masked GQA attention (slot index within the gathered view ==
    global position, so causality is ``slot <= q_pos``).

Static shapes everywhere: (rows, chunk, pages) are bucketed by the host layer
(``ragged.py``), so XLA compiles a handful of step programs.
"""

from __future__ import annotations

import functools

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.cache import PagedKVPool, Pools, StatePool, attention_kind, ring_columns
from deepspeed_tpu.inference.model import (ExpertStack, _apply_norm, _attn_out, _dense, _logits, _mlp,
                                           _moe_with_picks, _qkv)
from deepspeed_tpu.inference.sampling import greedy_tokens, sample_logits
from deepspeed_tpu.models.transformer import TransformerConfig, _norm_at, _times, reading
from deepspeed_tpu.ops import gdn, mhc, ssm


def _kv_block_quant(x: jax.Array, quant: str):
    """``[T, kvH, hd] float -> (values [T, kvH*hd], scales [T, kvH])``
    through THE shared block math (``ops.quant``): one symmetric absmax block
    per (token, head) ``hd`` vector, so pool scatters stay one-scatter-per-
    array and dequant is a per-slot multiply."""
    from deepspeed_tpu.ops.quant import fp8_block_math, int8_block_math

    T, kvH, hd = x.shape
    x2 = x.astype(jnp.float32).reshape(T * kvH, hd)
    q, s = int8_block_math(x2) if quant == "int8" else fp8_block_math(x2)
    return q.reshape(T, kvH * hd), s.reshape(T, kvH)


def _page_writer(block_tables, positions, new_lens, bs: int, num_rows: int):
    """``put(a, new, first_page)``: the new tokens' rows ``new`` [N*C, X] into
    a layer's pages of ``a`` [num_rows, bs, X] (or [num_rows, bs*X]), a whole
    page at a time.

    The chip scatters whole pages well and part-pages one token at a time. A
    sequence's new tokens are consecutive positions from ``positions[:, 0]``,
    so they touch at most ``J`` of its pages; each touched page is gathered,
    the new tokens laid over their slots, and the page put back. Pages are
    private to a sequence wherever it writes, so no two pages of one call
    are the same; pages no token touches (and pad sequences') index
    ``num_rows`` and drop. Everything but the layer's offset is computed
    here, once for all layers and arrays.
    """
    N, C = positions.shape
    J = (C + bs - 2) // bs + 1
    start = positions[:, 0]
    blk = jnp.clip(start[:, None] // bs + jnp.arange(J), 0, block_tables.shape[1] - 1)
    page = jnp.take_along_axis(block_tables, blk, axis=1)  # [N, J]
    # the chunk's token that lands in slot s of touched page j, if any
    tok = jnp.arange(J * bs) - start[:, None] % bs  # [N, J*bs]
    new_here = (tok >= 0) & (tok < new_lens[:, None])
    page = jnp.where(new_here.reshape(N, J, bs).any(axis=2), page, num_rows)
    tok = jnp.clip(tok, 0, C - 1)[:, :, None]

    def put(a, new, first_page):
        rows = (first_page + page).reshape(-1)
        X = new.shape[-1]
        old = a.at[rows].get(mode="clip").reshape(N, J * bs, X)
        laid = jnp.take_along_axis(new.reshape(N, C, X), tok, axis=1)
        both = jnp.where(new_here[:, :, None], laid, old)
        return a.at[rows].set(both.reshape((N * J,) + a.shape[1:]), mode="drop")

    return put


from deepspeed_tpu.ops.registry import dispatch, register


@register("paged_attention", "xla")
def _xla_paged_attention(q, pool_k, pool_v, block_tables, q_positions, block_size,
                         new_lens=None, alibi_slopes=None, k_scale=None, v_scale=None, first_live=None, sink=None):
    """Masked GQA attention of new queries against paged caches (dense-gather
    fallback; the Pallas flash-decode kernel in
    ``ops/pallas/paged_attention.py`` wins dispatch on TPU).

    q: [N, C, H, hd]; pool_{k,v}: [pages, bs, kvH*hd] (the whole pool, every
    layer's pages); block_tables: [N, P] page indices INTO that array (the
    caller adds the layer's offset); q_positions: [N, C]. Returns [N, C, H, hd].

    ``k_scale``/``v_scale`` ([pages, bs*kvH] fp32) mark a quantized pool:
    dequantization happens on the GATHERED blocks ([N, P*bs, ...], bounded by
    the batch's block tables) — the full-precision pool is never materialized.

    ``first_live`` [N, C] (a ring of pages rolled so its oldest live page comes
    first: the Pallas kernel's docstring) masks the slots before it too. A
    ring's first page is dead slots every day, and a masked score times a
    value that is not a number is not a number, so under ``first_live`` the
    values no query of the row sees are zeroed before the product.

    A value may be narrower than its key (``pool_v`` ``[pages, bs, kvH*hdv]``: the
    output is ``[N, C, H, hdv]``), and ``sink`` ([H]) is a logit a query head
    that joins the softmax's denominator and nothing else.
    """
    N, C, H, hd = q.shape
    P = block_tables.shape[1]
    kvH = pool_k.shape[-1] // hd

    def rows(a):  # a row's pages, in block-table order: slot index == position
        return a[block_tables].reshape(N, P * block_size, kvH, -1)

    ck, cv = rows(pool_k), rows(pool_v)  # [N, P*bs, kvH, hd]
    if k_scale is not None:
        ck = (ck.astype(jnp.float32) * rows(k_scale)).astype(q.dtype)
        cv = (cv.astype(jnp.float32) * rows(v_scale)).astype(q.dtype)
    G = H // kvH
    qg = q.reshape(N, C, kvH, G, hd)
    scores = jnp.einsum("nckgd,ntkd->nkgct", qg, ck).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    t_idx = jnp.arange(P * block_size)
    if alibi_slopes is not None:
        # slot index within the gathered view == global position, so the
        # bloom convention slopes * key-position applies directly
        scores = scores + (alibi_slopes.reshape(kvH, G)[None, :, :, None, None]
                           * t_idx.astype(jnp.float32)[None, None, None, None, :])
    ok = t_idx[None, None, :] <= q_positions[:, :, None]  # causal over positions
    if first_live is not None:
        ok = ok & (t_idx[None, None, :] >= first_live[:, :, None])
        cv = jnp.where(ok.any(axis=1)[:, :, None, None], cv, jnp.zeros((), cv.dtype))
    scores = jnp.where(ok[:, None, None, :, :], scores, -1e30)
    if sink is not None:  # a column that weighs no value
        column = jnp.broadcast_to(sink.astype(jnp.float32).reshape(1, kvH, G, 1, 1), scores.shape[:-1] + (1,))
        probs = jax.nn.softmax(jnp.concatenate([scores, column], axis=-1), axis=-1)[..., :-1].astype(cv.dtype)
    else:
        probs = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    ctx = jnp.einsum("nkgct,ntkd->nckgd", probs, cv)
    return ctx.reshape(N, C, H, cv.shape[-1])


def paged_attention(q, pool_k, pool_v, block_tables, q_positions, block_size,
                    new_lens=None, impl: str = "auto", alibi_slopes=None,
                    k_scale=None, v_scale=None, first_live=None, sink=None):
    import deepspeed_tpu.ops.pallas.paged_attention  # noqa: F401  (registers the kernel)

    # alibi is fused in BOTH implementations (the Pallas flash-decode kernel
    # adds slope * key-position on its existing position iota), so dispatch
    # is uniform — bloom keeps the fast decode path. Likewise quantized-pool
    # dequant: the kernel fuses it into its VMEM block loads, the XLA
    # fallback applies it to the gathered blocks.
    return dispatch("paged_attention", impl)(
        q, pool_k, pool_v, block_tables, q_positions, block_size,
        new_lens=new_lens, alibi_slopes=alibi_slopes,
        k_scale=k_scale, v_scale=v_scale, first_live=first_live, sink=sink,
    )


@register("latent_paged_attention", "xla")
def _xla_latent_paged_attention(q, pool, block_tables, q_positions, block_size, scale, v_width,
                                new_lens=None, mask=None):
    """Dense-gather fallback of the latent kernel (``mla_paged_attn`` in
    ``ops/pallas/paged_attention.py``). q: [N, C, H, W] against the whole
    slab; pool: [pages, bs, W]; a token's value is its slab's first
    ``v_width`` columns. ``mask`` bool [N, C, P*bs]: the positions a query
    attends, where an indexer chose them; it marks none past the query's own,
    and ``ok & mask`` below is the statement of that: the kernel's step under
    a mask makes no causal compare of its own (``dsa_paged_attn``, since
    PR 56). Returns [N, C, H, v_width]."""
    N, C, H, W = q.shape
    P = block_tables.shape[1]
    slab = pool[block_tables].reshape(N, P * block_size, W)  # slot index == position
    scores = jnp.einsum("nchw,ntw->nhct", q, slab).astype(jnp.float32) * scale
    ok = jnp.arange(P * block_size)[None, None, :] <= q_positions[:, :, None]
    if mask is not None:
        ok = ok & mask[..., :P * block_size]  # (the index kernel's scores come in whole tiles of columns)
    scores = jnp.where(ok[:, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(slab.dtype)
    return jnp.einsum("nhct,ntv->nchv", probs, slab[..., :v_width])


def latent_paged_attention(q, pool, block_tables, q_positions, block_size, scale, v_width,
                           new_lens=None, impl: str = "auto", mask=None):
    import deepspeed_tpu.ops.pallas.paged_attention  # noqa: F401  (registers the kernel)

    if mask is not None and impl == "auto" and q.shape[1] < _MASKED_KERNEL_MIN_QUERIES:
        impl = "xla"  # a token and its drafts: the kernel's masked form takes whole tiles of queries
    return dispatch("latent_paged_attention", impl)(
        q, pool, block_tables, q_positions, block_size, scale, v_width, new_lens=new_lens, mask=mask)


_MASKED_KERNEL_MIN_QUERIES = 16


def masked_walk_reads(queries: int, dtype):
    """The type ``latent_paged_attention`` reads a chunk's ``mask`` in: the kernel's walk 0/1 in the queries' own
    (handed that, it converts nothing), XLA's form bool."""
    import deepspeed_tpu.ops.pallas.paged_attention  # noqa: F401  (registers the kernel)

    kernel = queries >= _MASKED_KERNEL_MIN_QUERIES and dispatch("latent_paged_attention") is not _xla_latent_paged_attention
    return dtype if kernel else jnp.bool_


def latent_selected_attention(q, pool, block_tables, selected, block_size, scale, v_width):
    """ONE query a row against the cached tokens an indexer chose for it,
    gathered by position: q [N, 1, H, W]; ``selected`` int32 [N, K] positions
    of the row, -1 for none; pool and block_tables as the latent kernel takes
    them (the layer's offset added). 2,048 rows of 1,280 B where the walk
    would read every page up to the query. Returns [N, 1, H, v_width]."""
    live = selected >= 0
    at = jnp.maximum(selected, 0)
    page = jnp.take_along_axis(block_tables, at // block_size, axis=1)
    rows = pool[page, at % block_size]  # [N, K, W]
    scores = jnp.einsum("nchw,nkw->nhck", q, rows).astype(jnp.float32) * scale
    scores = jnp.where(live[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
    return jnp.einsum("nhck,nkv->nchv", probs, rows[..., :v_width])


def _rms(x, scale, eps):
    from deepspeed_tpu.ops import rms_norm

    # XLA's, which fuses into its neighbours: a kernel of its own for a
    # [rows, 512] norm costs a decode step more than the norm
    return rms_norm(x, scale, eps=eps, impl="xla")


# rows of a call go through an indexed layer's attention a group of this many tokens at a time
_ATTEND_GROUP_TOKENS = 8192


def _indexed_latent_attention(ap, cfg: TransformerConfig, h, positions, new_lens, block_tables, bs,
                              pk, pv, put_values, first_page, hand_mask: bool = False):
    """``_latent_attention`` under a learned indexer (``cfg.index_topk > 0``;
    ``ops/dsa.py``): beside its latent a token caches its index key, in the
    index pool ``pv`` at the page and slot the latent has in ``pk``; a query
    scores every cached key of its row, keeps the ``index_topk`` positions of
    largest score, and attends those and no others: a chunk by the causal walk
    under a per-query mask, one token a row over its chosen rows gathered by
    position. A block table that holds no more tokens than a query keeps has
    every candidate taken: the dense walk, and no scores.

    Everything after the latents (the query's up-projection, the indexer's
    scores, the choice, the absorbed query, the attention, the value
    up-projection and the output's) goes a group of rows at a time where a call brings more than ``_ATTEND_GROUP_TOKENS``: a
    row of 8,192 queries holds 1.3 GB of absorbed queries and as much of
    scores and mask. Returns (attention output [N, C, E], ``pk``, ``pv``, the
    choice; None where every candidate is taken): int32 [N, index_topk]
    positions (-1: none) for one token a row; for a chunk, asked by
    ``hand_mask``, the mask packed 32 positions a word (``dsa.pack_mask``),
    else int32 [N, 2], the cached tokens a row's queries scored and kept."""
    from deepspeed_tpu.models.transformer import rope_at
    from deepspeed_tpu.ops import dsa

    rank, nope, rope_d = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    Hi, Di = cfg.index_heads, cfg.index_head_dim
    N, C = positions.shape
    W, Wi = pk.shape[-1], pv.shape[-1]
    S = block_tables.shape[1] * bs
    dt = cfg.dtype
    rot = cfg.latent_rotary
    turn = functools.partial(dsa.rotate, rope_dim=rope_d, theta=cfg.rope_theta, interleaved=cfg.rope_interleaved)

    def slab(latent, rope):  # [latent | rotary | zeros up to the pool's width]
        pad = [jnp.zeros(latent.shape[:-1] + (W - rank - rope_d,), dt)] * (W > rank + rope_d)
        return jnp.concatenate([latent, rope] + pad, axis=-1)

    with jax.named_scope("mla"):  # and inside it the parameter keys read (``reading``)
        c_q = _dense(ap, "wq_a", cfg, h)
        with reading(ap, "q_norm") as p:
            c_q = _rms(c_q, p["scale"], cfg.norm_eps)
        kv = _dense(ap, "wkv_a", cfg, h)  # [N, C, rank + rope]
        with reading(ap, "kv_norm") as p:
            c_kv = _rms(kv[..., :rank], p["scale"], cfg.norm_eps)
        with jax.named_scope("rope"):
            k_rope = rope_at(kv[..., None, rank:], positions, cfg.rope_theta, cfg.rope_interleaved)[..., 0, :]
        with reading(ap, "wkv_b") as p:
            w_kvb = p["kernel"].astype(dt)  # [rank, H, nope + v], kept whole
        with jax.named_scope("dsa_index"):  # the one index key a token: projection, LayerNorm, rotary
            k_idx = _dense(ap, "idx_wk", cfg, h)
            with reading(ap, "idx_k_norm") as p:
                k_idx = dsa.key_norm(k_idx, p["scale"], p["bias"])
            with jax.named_scope("rope"):
                k_idx = turn(k_idx[..., None, :], positions)[..., 0, :]
            if Wi > Di:
                k_idx = jnp.concatenate([k_idx, jnp.zeros(k_idx.shape[:-1] + (Wi - Di,), k_idx.dtype)], axis=-1)
    with jax.named_scope("kv_write"):
        pk = put_values(pk, slab(c_kv, k_rope).astype(pk.dtype).reshape(-1, W), first_page)
        pv = put_values(pv, k_idx.astype(pv.dtype).reshape(-1, Wi), first_page)

    def attend(rows):
        """A group of rows, from the query's latent on: (the attention's output [g, C, E], the choice or None)."""
        c_q, h, positions, new_lens, tables = rows
        chosen = handed = None
        with jax.named_scope("mla"):
            q = _dense(ap, "wq_b", cfg, c_q, "ncr,rhd->nchd")
            with jax.named_scope("rope"):
                q_rope = rope_at(q[..., nope:], positions, cfg.rope_theta, cfg.rope_interleaved)
            if S > cfg.index_topk:
                with jax.named_scope("dsa_index"):
                    q_idx = _dense(ap, "idx_wq", cfg, c_q, "ncr,rhd->nchd")
                    with jax.named_scope("rope"):
                        q_idx = turn(q_idx, positions)
                    w = dsa.head_weights(_dense(ap, "idx_w", cfg, h, sums=jnp.float32), Hi, Di)
                    keys = pv[tables].reshape(-1, S, Wi)[..., :Di]  # slot index == position
                    scores = dsa.index_scores(q_idx, keys.astype(q_idx.dtype), w, positions)
                with jax.named_scope("dsa_select"):
                    if C == 1:
                        chosen = handed = dsa.select_positions(scores[:, 0], cfg.index_topk)
                    else:
                        # (in the type the walk below reads it in; the scores are -inf past a query's position)
                        chosen = dsa.select_mask(scores, cfg.index_topk, masked_walk_reads(C, q.dtype), positions)
                        if hand_mask:
                            handed = dsa.pack_mask(chosen[..., :S])
                        else:  # a row's (cached tokens scored, cached tokens kept), counted off the selection itself
                            live = jnp.arange(C)[None, :] < new_lens[:, None]  # (a pad query stands at position 0)
                            handed = jnp.stack([jnp.where(live, positions + 1, 0).sum(axis=1),
                                                (chosen.astype(bool) & live[..., None]).sum(axis=(1, 2), dtype=jnp.int32)],
                                               axis=-1)
            with reading(ap, "wkv_b"):
                q_lat = jnp.einsum("nchd,rhd->nchr", q[..., :nope], w_kvb[..., :nope])
            q_slab = slab(q_lat, q_rope)
        if chosen is None:
            o_lat = latent_paged_attention(q_slab, pk, tables, positions, bs, rot.softmax_scale, rank,
                                           new_lens=new_lens)
        else:
            with jax.named_scope("mla"), jax.named_scope("dsa_attend"):
                if C == 1:
                    o_lat = latent_selected_attention(q_slab, pk, tables, chosen, bs, rot.softmax_scale, rank)
                else:
                    o_lat = latent_paged_attention(q_slab, pk, tables, positions, bs, rot.softmax_scale, rank,
                                                   new_lens=new_lens, mask=chosen)
        with jax.named_scope("mla"):
            with reading(ap, "wkv_b"):  # the value half of the kernel read above
                o = jnp.einsum("nchr,rhv->nchv", o_lat, w_kvb[..., nope:])
            return _dense(ap, "wo", cfg, o, "nchv,hve->nce"), handed

    rows = (c_q, h, positions, new_lens, block_tables + first_page)
    group = max(1, _ATTEND_GROUP_TOKENS // C)
    if N > group and N % group == 0:
        grouped = jax.tree_util.tree_map(lambda a: a.reshape((N // group, group) + a.shape[1:]), rows)
        out, handed = jax.tree_util.tree_map(lambda a: a.reshape((N,) + a.shape[2:]), jax.lax.map(attend, grouped))
    else:
        out, handed = attend(rows)
    return out, pk, pv, handed


def _latent_attention(ap, cfg: TransformerConfig, h, positions, new_lens, block_tables, bs,
                      pk, put_values, first_page):
    """Latent attention of new tokens against the latent pool, in the
    ABSORBED form: the pool holds a token's normed latent and its rotary key,
    the key up-projection is folded into the query (``q_lat = q_nope @
    W_UK^T``) and the value up-projection applied to the attended latent
    (``o = (P @ c_kv) @ W_UV``), so no per-head key or value of a cached token
    is ever formed. The same mathematics as ``models/transformer.py``'s
    ``LatentAttention``. Returns (attention output [N, C, E], the pool)."""
    from deepspeed_tpu.models.transformer import rope_at

    rank, nope, rope_d = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    W = pk.shape[-1]
    dt = cfg.dtype
    with jax.named_scope("mla"):  # and inside it the parameter keys read (``reading``)
        c_q = _dense(ap, "wq_a", cfg, h)
        with reading(ap, "q_norm") as p:
            c_q = _rms(c_q, p["scale"], cfg.norm_eps)
        q = _dense(ap, "wq_b", cfg, c_q, "ncr,rhd->nchd")
        kv = _dense(ap, "wkv_a", cfg, h)  # [N, C, rank + rope]
        with reading(ap, "kv_norm") as p:
            c_kv = _rms(kv[..., :rank], p["scale"], cfg.norm_eps)
        rot = cfg.latent_rotary  # frequencies and softmax scale: the flax module's own
        with jax.named_scope("rope"):
            k_rope = rope_at(kv[..., None, rank:], positions, cfg.rope_theta, cfg.rope_interleaved,
                             rot.inv_freq)[..., 0, :]
            q_rope = rope_at(q[..., nope:], positions, cfg.rope_theta, cfg.rope_interleaved,
                             rot.inv_freq)
        with reading(ap, "wkv_b") as p:
            w_kvb = p["kernel"].astype(dt)  # [rank, H, nope + v], kept whole
            q_lat = jnp.einsum("nchd,rhd->nchr", q[..., :nope], w_kvb[..., :nope])

        def slab(latent, rope):  # [latent | rotary | zeros up to the pool's width]
            pad = [jnp.zeros(latent.shape[:-1] + (W - rank - rope_d,), dt)] * (W > rank + rope_d)
            return jnp.concatenate([latent, rope] + pad, axis=-1)

        q_slab, row = slab(q_lat, q_rope), slab(c_kv, k_rope)
    with jax.named_scope("kv_write"):
        pk = put_values(pk, row.astype(pk.dtype).reshape(-1, W), first_page)
    o_lat = latent_paged_attention(q_slab, pk, block_tables + first_page, positions, bs,
                                   rot.softmax_scale, rank, new_lens=new_lens)
    with jax.named_scope("mla"):
        with reading(ap, "wkv_b"):  # the value half of the kernel read above
            o = jnp.einsum("nchr,rhv->nchv", o_lat, w_kvb[..., nope:])
        out = _dense(ap, "wo", cfg, o, "nchv,hve->nce")
    return out, pk


def _eva_attention(cfg: TransformerConfig, call: "_Call"):
    """An attention builder (``_ATTENTION``): EVA attention of a call's new
    tokens against a pool whose pages are of two kinds (``cache.WindowLayout``
    is the host's account of the same columns). Everything but the layer's
    offset is computed here, once for all layers.

    A row feeds either ONE token, anywhere (decode, ``put``), or a chunk that
    starts at position 0 (a prompt; the host refuses anything else):

    - one token at ``t`` (window ``w``, offset ``r``): its key and value go to
      its window page; it attends through the paged kernel over the row's
      table re-read as ``[w closed windows' summary pages | window pages]``
      up to slot ``w * window / chunk + r``, which is one softmax over the
      summaries and its window's exact rows; and if it is its window's last
      token (``r == window - 1``) the window's pages are pooled, page for
      row, into the summary pages that follow the closed windows'
      (``eva_close``, under a ``cond``: a step in which no row closes pays
      nothing, and one in which a row does pays for that row's window). No
      exact row moves: the next window writes over the pages.
    - a chunk: ``ops/eva.py`` computes its windows at once from the chunk's
      own keys (``eva_prefill``); what is WRITTEN is the summaries of the
      windows it closes and the exact rows of the window it leaves open, each
      a whole page at a time. A closed window's exact rows never reach the
      pool.
    """
    from deepspeed_tpu.models.transformer import rope_at
    from deepspeed_tpu.ops.eva import eva_attention, pool_chunks

    positions, new_lens, block_tables, bs, num_rows = call[:5]
    N, C = positions.shape
    P = block_tables.shape[1]
    W, c = cfg.eva_window, cfg.eva_chunk
    if c != bs:
        raise ValueError(f"EVA attention with eva_chunk={c} needs kv_block_size={c}, got {bs}")
    w_pages, per_closed = W // bs, W // c // bs
    s_cols = P - w_pages  # the summary pages' columns, the closed windows' first
    kvH, hd = cfg.kv_heads, cfg.dims_per_head
    X = kvH * hd
    col = jnp.arange(P)

    # ---- the one-token rows
    single = new_lens == 1
    t = positions[:, 0]
    w, r = t // W, t % W
    s_page = jnp.take_along_axis(block_tables, (s_cols + r // bs)[:, None], axis=1)[:, 0]
    s_page = jnp.where(single, s_page, num_rows)  # other rows' writes drop
    s_slot = r % bs
    closed_cols = (per_closed * w)[:, None]
    view = jnp.take_along_axis(
        block_tables, jnp.clip(jnp.where(col < closed_cols, col, s_cols + col - closed_cols), 0, P - 1), axis=1)
    view_pos = jnp.where(single, (W // c) * w + r, -1)[:, None]  # -1: no context, zeros out
    closing = single & (r == W - 1)
    close_to = jnp.take_along_axis(
        block_tables, jnp.clip(closed_cols + jnp.arange(per_closed), 0, P - 1), axis=1)
    close_to = jnp.where(closing[:, None], close_to, num_rows)
    closing_first, n_closing = jnp.argsort(~closing), closing.sum()
    window_cols = block_tables[:, s_cols:]

    # ---- the chunk rows
    if C > 1:
        width = W if C > W else -(-C // c) * c
        Cp = -(-C // width) * width
        n_win = Cp // width
        chunk_row = new_lens > 1
        n_closed = jnp.where(chunk_row, new_lens // W, 0)
        open_len = jnp.where(chunk_row, new_lens - n_closed * W, 0)
        n_closable = Cp // W  # windows a chunk of this shape can close
        sum_to = jnp.where(
            jnp.repeat(jnp.arange(n_closable)[None, :] < n_closed[:, None], per_closed, axis=1),
            block_tables[:, :n_closable * per_closed], num_rows)
        open_pages = width // bs
        open_to = jnp.where(jnp.arange(open_pages)[None, :] * bs < open_len[:, None],
                            window_cols[:, :open_pages], num_rows)
        open_at = jnp.clip(n_closed, 0, n_win - 1)

        def open_rows(new):  # [N, Cp, kvH, hd] -> the pages of each row's open window
            return jax.vmap(lambda a, i: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False))(
                new.reshape(N, n_win, open_pages, bs, X), open_at)

    def one_token(q, k, v, phi, mu, pk, pv, first_page):
        with jax.named_scope("kv_write"):
            pk = pk.at[first_page + s_page, s_slot].set(k.astype(pk.dtype).reshape(N, X), mode="drop")
            pv = pv.at[first_page + s_page, s_slot].set(v.astype(pv.dtype).reshape(N, X), mode="drop")
        ctx = paged_attention(q, pk, pv, view + first_page, view_pos, bs, new_lens=single.astype(jnp.int32))

        @jax.named_scope("eva_close")
        def close(pk, pv):
            """The closing rows' windows, a row at a time (they come first in
            ``closing_first``), page for summary row."""
            def one(i, pools):
                pk, pv = pools
                row = closing_first[i]
                pages = first_page + window_cols[row]
                ks, vs = pool_chunks(pk[pages].reshape(w_pages, bs, kvH, hd),
                                     pv[pages].reshape(w_pages, bs, kvH, hd), phi, mu)
                to = first_page + close_to[row]
                return (pk.at[to].set(ks.reshape(per_closed, bs, X), mode="drop"),
                        pv.at[to].set(vs.reshape(per_closed, bs, X), mode="drop"))

            return jax.lax.fori_loop(0, n_closing, one, (pk, pv))

        # a step in which no row closes pays nothing, one in which a row does
        # pays for that row's window (4 MB a layer at the published widths)
        pk, pv = jax.lax.cond(n_closing > 0, close, lambda pk, pv: (pk, pv), pk, pv)
        return ctx, pk, pv

    @jax.named_scope("eva_prefill")
    def chunk(q, k, v, phi, mu, pk, pv, first_page):
        if Cp != C:
            q, k, v = (jnp.pad(a, ((0, 0), (0, Cp - C), (0, 0), (0, 0))) for a in (q, k, v))
        ctx, ks, vs = eva_attention(q, k, v, phi, mu, W, c)
        with jax.named_scope("eva_close"):
            if n_closable:
                to = first_page + sum_to
                pk = pk.at[to].set(ks[:, :n_closable * W // c].astype(pk.dtype).reshape(
                    N, n_closable * per_closed, bs, X), mode="drop")
                pv = pv.at[to].set(vs[:, :n_closable * W // c].astype(pv.dtype).reshape(
                    N, n_closable * per_closed, bs, X), mode="drop")
        with jax.named_scope("kv_write"):
            to = first_page + open_to
            pk = pk.at[to].set(open_rows(k).astype(pk.dtype), mode="drop")
            pv = pv.at[to].set(open_rows(v).astype(pv.dtype), mode="drop")
        return ctx[:, :C], pk, pv

    @jax.named_scope("eva")
    def attend(ap, h, pages, first_page, kind):
        q, k, v = _qkv(ap, cfg, h)
        with jax.named_scope("rope"):
            q = rope_at(q, positions, cfg.rope_theta, cfg.rope_interleaved)
            k = rope_at(k, positions, cfg.rope_theta, cfg.rope_interleaved)
        phi, mu = ap["phi"], ap["mu"]
        first, pk, pv = one_token(q[:, :1], k[:, :1], v[:, :1], phi, mu, pages.k, pages.v, first_page)
        if C == 1:
            return _attn_out(ap, cfg, first), pages._replace(k=pk, v=pv), None
        ctx, pk, pv = chunk(q, k, v, phi, mu, pk, pv, first_page)
        ctx = ctx.at[:, :1].set(jnp.where(single[:, None, None, None], first.astype(ctx.dtype), ctx[:, :1]))
        return _attn_out(ap, cfg, ctx), pages._replace(k=pk, v=pv), None

    return attend


def _windowed_attention(cfg: TransformerConfig, call: "_Call"):
    """An attention builder (``_ATTENTION``) for a pattern with a sliding kind:
    ``pages`` are the class of page the layer's ``kind`` writes (``Pools.ring``
    for a ``sliding_attention`` layer, ``Pools.kv`` for an ``attention`` one),
    ``first_page`` the layer's first page in it. Everything but the layer's
    offset is computed here, once for all layers.

    A call feeds either ONE token a row, anywhere (``C == 1``: decode, ``put``),
    or chunks that each start at position 0 (fresh prompts; the host refuses
    anything else, ``ragged.build_ragged_batch``):

    - one token at ``t``: its key and value go to the page of block ``t //
      bs``, a global column for an ``attention`` layer, ring column ``(t // bs)
      % R`` for a sliding one; the ``attention`` layer runs the paged kernel
      over the global columns as every model does, the sliding layer over its
      ring ROLLED so that the oldest live block comes first (a gather of ``R``
      ints a row, on the device from the row's position: a chain never comes
      back to the host for it), with positions counted from that block's first
      slot and ``first_live`` the slots of it the window has left behind.
    - a chunk: attention is computed from the chunk's own ``q, k, v``
      (``causal_attention``: on the chip the flash forward, under the band for
      a sliding layer, GQA in its index maps, told the rows' ``new_lens`` so
      that it runs no cell past a prompt's last token and a pad's row is
      zeros), never through the pages; an
      ``attention`` layer then writes every page of the prompt in one bulk
      write (``_page_writer``), a sliding layer the pages of the prompt's LAST
      window alone, whole pages, each into its ring column.

    The two kinds' shapes are their own (``transformer.sliding_kind``: kv heads,
    the width of keys and of values, the rotary base, a sink a head in the
    sliding kind's softmax): a token's row is as wide as its class's array, keys
    and values apart, and the projections' shapes are the parameters'.

    Device-trace scopes: ``swa`` around a sliding layer's attention,
    ``attn_full`` around the other kind's, each with ``kv_write`` and the
    kernel's own name inside."""
    from deepspeed_tpu.models.transformer import apply_qk_rope, sliding_kind
    from deepspeed_tpu.ops.attention import causal_attention, first_live

    positions, new_lens, block_tables, bs, full_rows, ring_rows = call[:6]
    N, C = positions.shape
    W = cfg.sliding.window
    R = ring_columns(W, bs)
    Pg = block_tables.shape[1] - R
    global_cols, ring_cols = block_tables[:, :Pg], block_tables[:, Pg:]

    if C == 1:
        fed = new_lens == 1
        t = positions[:, 0]
        block, slot = t // bs, t % bs
        g_page = jnp.where(fed, jnp.take_along_axis(global_cols, jnp.clip(block, 0, Pg - 1)[:, None], axis=1)[:, 0],
                           full_rows)  # a pad row's writes drop
        r_page = jnp.where(fed, jnp.take_along_axis(ring_cols, (block % R)[:, None], axis=1)[:, 0], ring_rows)
        low = first_live(t, W)
        oldest = low // bs  # the oldest block with a live slot: the rolled ring's first column
        rolled = jnp.take_along_axis(ring_cols, (oldest[:, None] + jnp.arange(R)) % R, axis=1)
        rel_pos, rel_low = (t - oldest * bs)[:, None], (low - oldest * bs)[:, None]
        ones = fed.astype(jnp.int32)

        def attention(q, k, v, sliding, window, sink, pk, pv, first_page):
            with jax.named_scope("kv_write"):
                page = first_page + (r_page if sliding else g_page)
                pk = pk.at[page, slot].set(k.astype(pk.dtype).reshape(N, -1), mode="drop")
                pv = pv.at[page, slot].set(v.astype(pv.dtype).reshape(N, -1), mode="drop")
            if sliding:
                return paged_attention(q, pk, pv, rolled + first_page, rel_pos, bs, new_lens=ones,
                                       first_live=rel_low, sink=sink), pk, pv
            return paged_attention(q, pk, pv, global_cols + first_page, t[:, None], bs, new_lens=ones,
                                   sink=sink), pk, pv
    else:
        put_full = _page_writer(global_cols, positions, new_lens, bs, full_rows)
        # the blocks that hold a prompt's last window, oldest first, and the chunk's token of each of their slots
        blocks = (jnp.maximum(new_lens - W, 0) // bs)[:, None] + jnp.arange(R)  # [N, R]
        to = jnp.where(blocks * bs < new_lens[:, None], jnp.take_along_axis(ring_cols, blocks % R, axis=1), ring_rows)
        tok = jnp.clip(blocks[:, :, None] * bs + jnp.arange(bs), 0, C - 1).reshape(N, R * bs, 1)

        def put_ring(a, new, first_page):
            X = a.shape[-1]
            laid = jnp.take_along_axis(new.reshape(N, C, X), tok, axis=1)
            return a.at[(first_page + to).reshape(-1)].set(laid.reshape(N * R, bs, X), mode="drop")

        def attention(q, k, v, sliding, window, sink, pk, pv, first_page):
            ctx = causal_attention(q, k, v, impl=cfg.attn_impl, window=window, lengths=new_lens, sink=sink)
            with jax.named_scope("kv_write"):
                put = put_ring if sliding else put_full
                pk = put(pk, k.astype(pk.dtype).reshape(N * C, -1), first_page)
                pv = put(pv, v.astype(pv.dtype).reshape(N * C, -1), first_page)
            return ctx, pk, pv

    def attend(ap, h, pages, first_page, kind):
        sliding = kind == "sliding_attention"
        how = sliding_kind(cfg, kind)
        q, k, v = _qkv(ap, cfg, h)
        v = _times(cfg.value_multiplier, v)
        if cfg.position == "rope" and how["rotates"]:
            with jax.named_scope("rope"):
                q, k = apply_qk_rope(cfg, q, k, positions, how["rope_theta"])
        with jax.named_scope("swa" if sliding else "attn_full"):
            ctx, pk, pv = attention(q, k, v, sliding, how["window"], ap["sink"] if how["sink"] else None,
                                    pages.k, pages.v, first_page)
        return _attn_out(ap, cfg, ctx), pages._replace(k=pk, v=pv), None

    return attend


class _Call(NamedTuple):
    """One call's geometry, the same for every layer: what an attention builder (``_ATTENTION``) computes its
    indices from, once for all layers."""

    positions: jax.Array  # [N, C]
    new_lens: jax.Array  # [N]
    block_tables: jax.Array  # [N, P]
    bs: int
    kv_rows: int  # rows of the first class's arrays: a write that indexes this one drops
    ring_rows: int  # likewise of the ring's (0: the model has none)
    quant: Optional[str]  # the first class's storage (``PagedKVPool.quant``), static at trace time
    hand_mask: bool  # an indexed layer hands out a chunk's mask in place of its counts
    w_page: Optional[jax.Array]  # where each new token's row goes in a layer's pages of the first class, pad
    w_slot: Optional[jax.Array]  # tokens at page ``kv_rows``; None under EVA, whose rows go by window


def _row_writers(call: _Call):
    """``(put_values, put_pages)`` where a block of positions is a page: values go a token's row at a time when
    a sequence brings fewer tokens than a page holds (decode, drafts: a 4 KB row against a 64 KB page), a whole
    page at a time when it brings a chunk of a prompt; scales are a lane-dense row a PAGE, so they always go a
    page at a time (``put_pages``: None where nothing goes so)."""
    by_page = call.positions.shape[1] >= call.bs
    put_pages = None
    if by_page or call.quant is not None:
        put_pages = _page_writer(call.block_tables, call.positions, call.new_lens, call.bs, call.kv_rows)

    def put_values(a, new, first_page):
        if by_page:
            return put_pages(a, new, first_page)
        return a.at[first_page + call.w_page, call.w_slot].set(new, mode="drop")

    return put_values, put_pages


def _plain_attention(cfg: TransformerConfig, call: _Call):
    """An attention builder (``_ATTENTION``): keys and values by head, a block of positions a page."""
    positions, new_lens, block_tables, bs = call[:4]
    quant = call.quant
    put_values, put_pages = _row_writers(call)
    alibi = None
    if cfg.position == "alibi":
        from deepspeed_tpu.models.transformer import alibi_slopes

        alibi = alibi_slopes(cfg.num_heads)

    def attend(ap, h, pages, first_page, kind):
        pk, pv, psk, psv = pages
        q, k, v = _qkv(ap, cfg, h)
        if cfg.attn_output_gate:  # a head's projection is [q | gate]
            q, gate = q[..., :v.shape[-1]], q[..., v.shape[-1]:]
        if cfg.qk_norm:
            q, k = _norm_at(ap, "q_norm", cfg, q), _norm_at(ap, "k_norm", cfg, k)
        if cfg.position == "rope":
            from deepspeed_tpu.models.transformer import apply_qk_rope

            with jax.named_scope("rope"):
                q, k = apply_qk_rope(cfg, q, k, positions)
        kvH, hd = k.shape[-2], k.shape[-1]
        if cfg.attention_multiplier is not None:
            # the paged kernels scale the scores by hd^-0.5 themselves: the rest goes into q
            q = _times(cfg.attention_multiplier * hd ** 0.5, q)
        with jax.named_scope("kv_write"):
            if quant is not None:
                # quantized KV write: the same one-scatter-per-array shape,
                # plus one scatter of scale pages per array (pad rows drop
                # for values AND scales alike)
                kq, ks = _kv_block_quant(k.reshape(-1, kvH, hd), quant)
                vq, vs = _kv_block_quant(v.reshape(-1, kvH, hd), quant)
                pk = put_values(pk, kq.astype(pk.dtype), first_page)
                pv = put_values(pv, vq.astype(pv.dtype), first_page)
                psk = put_pages(psk, ks, first_page)
                psv = put_pages(psv, vs, first_page)
            else:
                pk = put_values(pk, k.astype(pk.dtype).reshape(-1, kvH * hd), first_page)
                pv = put_values(pv, v.astype(pv.dtype).reshape(-1, kvH * hd), first_page)
        ctx = paged_attention(q, pk, pv, block_tables + first_page, positions, bs,
                              new_lens=new_lens, alibi_slopes=alibi,
                              k_scale=psk, v_scale=psv)
        if cfg.attn_output_gate:
            with jax.named_scope("attn_gate"):
                ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ctx.dtype)
        return _attn_out(ap, cfg, ctx), PagedKVPool(pk, pv, psk, psv), None

    return attend


def _latent_attention_of(cfg: TransformerConfig, call: _Call):
    """An attention builder (``_ATTENTION``): ``_latent_attention`` against the latent pool, ``pages.k`` alone."""
    put_values, _ = _row_writers(call)

    def attend(ap, h, pages, first_page, kind):
        out, pk = _latent_attention(ap, cfg, h, *call[:4], pages.k, put_values, first_page)
        return out, pages._replace(k=pk), None

    return attend


def _indexed_attention_of(cfg: TransformerConfig, call: _Call):
    """An attention builder (``_ATTENTION``): ``_indexed_latent_attention``, the latents in ``pages.k``, the
    index keys in ``pages.v`` (``cache.PageClass.second_holds``); the third value is what the queries kept."""
    put_values, _ = _row_writers(call)

    def attend(ap, h, pages, first_page, kind):
        out, pk, pv, kept = _indexed_latent_attention(ap, cfg, h, *call[:4], pages.k, pages.v, put_values,
                                                      first_page, hand_mask=call.hand_mask)
        return out, pages._replace(k=pk, v=pv), kept

    return attend


# ``cache.attention_kind(cfg)`` -> ``(cfg, the call's geometry) -> attend(ap, h, pages, first_page, kind) ->
# (attention output [N, C, E], pages, what an indexed layer's queries kept or None)``: ``pages`` the
# ``PagedKVPool`` of the class the layer's ``kind`` writes, ``first_page`` the layer's first page in it
_ATTENTION = {"plain": _plain_attention, "latent": _latent_attention_of, "indexed": _indexed_attention_of,
              "eva": _eva_attention, "windowed": _windowed_attention}


def _forward_hidden(
    params,
    cfg: TransformerConfig,
    pools: Pools,
    tokens: jax.Array,  # [N, C] int32
    positions: jax.Array,  # [N, C] int32
    new_lens: jax.Array,  # [N] int32
    block_tables: jax.Array,  # [N, P] int32
    block_size: int,
    all_positions: bool = False,
    with_picks: bool = False,
    with_selected: bool = False,
) -> Tuple[jax.Array, ...]:
    """One mixed prefill/decode layer-stack pass -> (last-token hidden [N, E],
    pools). Shared by the single-step ``ragged_forward`` and the K-step
    ``ragged_decode_chain`` — one definition of the serving transformer math.

    ``with_picks=True`` on a routed model (``num_experts > 0``) returns a
    third value, ``picks`` int32 ``[N, C, routed layers, k]``: the experts
    each token fed was sent to in each routed layer, leading dense layers not
    counted, by the experts' own numbers (pad tokens' entries are garbage).
    A model with no routed layer returns the pair whatever is asked.

    A model with a learned indexer (``index_topk > 0``) asked ``with_picks``
    hands out BEFORE the picks what each query kept, where its block table
    holds more tokens than a query keeps: for one token a row (``C == 1``)
    ``selected`` int32 ``[N, layers, index_topk]``, the positions, -1 for
    none; for a chunk ``[N, layers, 2]`` int32, the cached tokens a row's
    queries scored and the ones they kept, and where ``with_selected`` asks (a
    reader outside the serving loop) ``[N, C, layers, ceil(P * bs / 32)]``
    int32 in their place, the mask packed 32 positions a word
    (``ops/dsa.py::pack_mask``).

    A model with ``first_dense_layers`` runs those first, each from its own
    ``params["dense_<i>"]``, then scans the routed stack ``params["layers"]``
    with the pools in the carry; layer ``l``'s pages are rows ``l*NB ...`` of
    its class's arrays either way. How the layers attend is ONE choice made
    before any is traced (``_ATTENTION``); a layer takes the pages of the class
    its kind writes by name and gives them back by name.

    A model with hyper-connections (``hc_mult > 0``) carries its ``hc_mult``
    residual streams ``[n, N, C, E]`` through the layers, each sublayer reading
    a learned mix of them and writing back through ``lp["attn_hc"]`` /
    ``lp["mlp_hc"]`` (``ops/mhc.py``); they are summed before the selection.

    ``all_positions=True`` returns the full ``[N, C, E]`` hidden states
    instead of the last-token selection — the speculative verify step needs
    a logit at EVERY draft position to accept/reject in one pass.

    A row's ``positions`` are consecutive from ``positions[:, 0]`` (a chunk of
    a prompt, one decode token, or a token and its drafts).
    """
    N, C = tokens.shape
    bs = block_size
    attends = attention_kind(cfg)
    L = cfg.attention_layers  # the layers that hold pages of the first class: all, but in a layer pattern
    NB = pools.kv.k.shape[0] // L
    ring_rows = 0 if pools.ring is None else pools.ring.k.shape[0]
    NR = ring_rows // max(cfg.sliding_layers, 1)  # ring pages a sliding layer
    valid = jnp.arange(C)[None, :] < new_lens[:, None]  # [N, C]
    w_page = w_slot = None
    if attends != "eva":
        # where each new token's row goes: (page of its layer-0 pool, slot in the
        # page). Pad tokens get page L*NB, out of range in every layer: dropped.
        page = jnp.take_along_axis(block_tables, positions // bs, axis=1)
        w_page = jnp.where(valid, page, L * NB).reshape(-1)
        w_slot = (positions % bs).reshape(-1)

    with jax.named_scope("embed"):
        x = _times(cfg.embedding_multiplier, jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(
            jnp.float32 if cfg.fp32_residual else cfg.dtype))
        if cfg.embed_norm:
            x = _apply_norm(params["embed_norm"], cfg, x)
        if cfg.position == "learned":
            x = x + jnp.take(params["pos_embed"], positions, axis=0).astype(cfg.dtype)
        if cfg.hc_mult:
            x = mhc.spread(x, cfg.hc_mult)  # [n, N, C, E]: the carry's x is the streams

    if "layers" not in params:
        raise ValueError("ragged inference requires scan_layers=True stacked params")

    routed = with_picks and cfg.num_experts > 0
    indexed = attends == "indexed"  # its layers hand out, beside the picks, what their queries kept
    D = cfg.first_dense_layers  # leading dense layers, run before the scan
    # the ONE choice of how this model's layers attend, made here for all of them
    attend = _ATTENTION[attends](cfg, _Call(positions, new_lens, block_tables, bs, L * NB, ring_rows,
                                         pools.kv.quant, with_selected, w_page, w_slot))

    def ffn(lp, h, dense):
        """(output, picks or None): the dense MLP, or the routed layer with
        the experts it sent each token to."""
        if cfg.num_experts > 0 and not dense:
            with reading(lp, "moe") as p:
                out, picks = _moe_with_picks(p, cfg, h)
            return out, picks if routed else None
        with reading(lp, "mlp") as p:
            return _mlp(p, cfg, h), None

    def mixed_at(lp, key, streams):
        """A sublayer's hyper-connection ``lp[key]``: its mix of every token and its read
        (on the chip, at sizes it takes, the kernel ``mhc_mix_read``: one pass over the streams)."""
        with reading(lp, key) as p:
            return mhc.mix_read(streams, p["phi"], p["b"], p["alpha"], norm_eps=cfg.norm_eps,
                                iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps, clamp=cfg.hc_res_clamp)

    def written(lp, key, streams, out, mixed):
        with reading(lp, key):  # (the kernel ``mhc_write`` where ``mhc_mix_read`` made the mix: over the streams)
            return mhc.write_back(streams, out, mixed)

    # Scopes for a device trace (HLO metadata only). ``pool_scan`` encloses
    # the layer scan; everything the body computes sits under ``layer`` (or
    # under ``kv_write`` or the kernel's own name inside it), so what reads
    # ``pool_scan`` innermost is the scan's own traffic. The pools ride in
    # the carry and are updated in place, so that should be next to nothing.
    # Inside ``layer`` each piece sits under the parameter key it reads
    # (``reading``): ``attn_norm``, ``attn`` > ``wq`` ..., ``mlp`` > ``w_up`` ...,
    # the names flax gives the same modules in training.
    @jax.named_scope("layer")
    def layer(carry, lp, first_page, dense=False, kind="attention"):
        x, pools = carry
        held = "ring" if kind == "sliding_attention" else "kv"  # the class of page a layer of this kind writes

        def attention(h):
            with reading(lp, "attn") as ap:
                out, pages, kept = attend(ap, h, getattr(pools, held), first_page, kind)
            return out, pools._replace(**{held: pages}), kept

        if cfg.hc_mult:
            # ``x`` is the streams: the two adds of a one-stream block become a
            # mixed read before each sublayer and a write-back after it (``ops/mhc.py``)
            mixed, u = mixed_at(lp, "attn_hc", x)
            attn_out, pools, _ = attention(_norm_at(lp, "attn_norm", cfg, u))
            x = written(lp, "attn_hc", x, attn_out, mixed)
            mixed, u = mixed_at(lp, "mlp_hc", x)
            out, picks = ffn(lp, _norm_at(lp, "mlp_norm", cfg, u), dense)
            return (written(lp, "mlp_hc", x, out, mixed), pools), picks
        h = _norm_at(lp, "attn_norm", cfg, x)
        attn_out, pools, kept = attention(h)
        if cfg.parallel_block:
            # falcon/phi-style: attn and FFN read the shared input norm;
            # gpt-neox-style (parallel_mlp_norm): FFN reads its own ln2(x)
            out, picks = ffn(lp, _norm_at(lp, "mlp_norm", cfg, x) if cfg.parallel_mlp_norm else h, dense)
            return (x + attn_out + out, pools), picks
        x = x + _times(cfg.residual_multiplier, attn_out)
        out, picks = ffn(lp, _norm_at(lp, "mlp_norm", cfg, x), dense)
        if indexed:
            picks = (picks, kept)
        return (x + _times(cfg.residual_multiplier, out), pools), picks

    if pools.state is not None:
        # a row fed from position 0 starts a sequence: whatever its slot holds is another's
        fresh = (positions[:, 0] == 0) & (new_lens > 0)

    @jax.named_scope("layer")
    def state_layer(carry, lp, s, kind):
        """A layer with recurrent state (Mamba-2 or Gated DeltaNet), the
        ``s``-th of its kind: its mixer reads and writes row ``s`` of the state
        pool and of the conv pool, in place (``StatePool``)."""
        x, pools = carry
        sp, cp = pools.state
        key, sizes = ("ssm", cfg.ssm) if kind == "mamba" else ("gdn", cfg.gdn)
        h = _norm_at(lp, key + "_pre_norm", cfg, x)
        with reading(lp, key) as mp:
            row, tail = ssm.PoolRow(sp, s, fresh), ssm.PoolRow(cp, s, fresh)
            if kind == "mamba":
                y, sp, cp = ssm.mix(_dense(mp, "ssm_in_proj", cfg, h), mp, sizes, cfg.norm_eps, state=row,
                                    tail=tail, new_lens=new_lens)
            else:
                def mixed(h, lens, tail, state):  # [q | k | v | z] as the product's float32 sums (ops/gdn.py)
                    return gdn.mix(_dense(mp, "gdn_in_proj", cfg, h, sums=jnp.float32),
                                   _dense(mp, "gdn_ba_proj", cfg, h), mp, sizes, cfg.norm_eps, state=state,
                                   tail=tail, new_lens=lens)

                group = gdn.group_rows(N, C, sizes.chunk_size, sizes.n_v_heads)
                if group == N:
                    y, sp, cp = mixed(h, new_lens, tail, row)
                else:  # a group of rows at a time: a (128, 256) prefill's float32 [q | k | v | z] whole are 1.6 GB
                    groups = jax.tree_util.tree_map(
                        lambda a: a.reshape((N // group, group) + a.shape[1:]),
                        (h, new_lens, ssm.tail_rows(tail, N, sizes.d_conv), gdn.pool_rows(row, N)))
                    y, left, tails = (a.reshape((N,) + a.shape[2:])
                                      for a in jax.lax.map(lambda a: mixed(*a), groups))
                    sp, cp = gdn.put_pool_rows(row, left), ssm.put_tail_rows(tail, tails)
            out = _dense(mp, key + "_out_proj", cfg, y)
        x = x + _times(cfg.residual_multiplier, out)
        out, picks = ffn(lp, _norm_at(lp, "mlp_norm", cfg, x), False)
        return (x + _times(cfg.residual_multiplier, out), pools._replace(state=StatePool(sp, cp))), picks

    # The scanned stack WITHOUT its routed experts' leaves, which the body
    # closes over whole and names by the scan's index (``ExpertStack``): the
    # decode product's kernel reads the picked experts where they lie, and a
    # custom call on the scan's slice would have the slice copied for it.
    if cfg.layer_types is not None:
        split = {key: _split_experts(lp) for key, lp in params["layers"].items()}
        layers = {key: rest for key, (rest, _) in split.items()}
        experts = {key: stack for key, (_, stack) in split.items()}
        stacked = any(stack is not None for stack in experts.values())
    else:
        layers, experts = _split_experts(params["layers"])
        stacked = experts is not None

    def period(carry, xs):
        """One period of a layer pattern, its layers unrolled: attention layer
        ``a`` (counted among its kind) has the pages from ``a * NB``, the
        ``s``-th other layer the ring's pages from ``s * NR`` (a sliding one) or
        row ``s`` of the state pool (Mamba-2 or Gated DeltaNet). A routed
        pattern's picks come out a layer of the period, in its order."""
        pp, first_a, first_s, *index = xs
        a = s = 0
        picked = []
        for j, kind in enumerate(cfg.period):
            lp = _with_experts(pp[f"layer_{j}"], experts[f"layer_{j}"], *index)
            if kind == "attention":
                carry, picks = layer(carry, lp, (first_a + a) * NB)
                a += 1
            elif kind == "sliding_attention":
                carry, picks = layer(carry, lp, (first_s + s) * NR, kind=kind)
                s += 1
            else:
                carry, picks = state_layer(carry, lp, first_s + s, kind)
                s += 1
            picked.append(picks)
        return carry, jnp.stack(picked) if routed else None

    carry = (x, pools)
    kept_dense = []
    dense_a = dense_s = 0  # the leading dense layers of each kind: the first pages of their classes
    for i in range(D):
        # a leading dense layer of a routed model: its own parameters, its own
        # pages (layer i's, counted among its kind in a pattern), outside the scan
        kind = "attention" if cfg.layer_types is None else cfg.layer_types[i]
        sliding = kind == "sliding_attention"
        carry, out = layer(carry, params[f"dense_{i}"], jnp.int32(dense_s * NR if sliding else dense_a * NB),
                           dense=True, kind=kind)
        dense_s, dense_a = dense_s + sliding, dense_a + (not sliding)
        if indexed:
            kept_dense.append(out[1])
    with jax.named_scope("pool_scan"):
        if cfg.layer_types is not None:
            kinds = cfg.period
            periods = jnp.arange((cfg.num_layers - D) // len(kinds), dtype=jnp.int32)
            first = lambda at, n: periods * n + at if at else periods * n  # noqa: E731  (past the leading layers')
            (x, pools), picks = jax.lax.scan(
                period, carry,
                (layers, first(dense_a, kinds.count("attention")),
                 first(dense_s, len(kinds) - kinds.count("attention"))) + ((periods,) if stacked else ()))
            if routed:  # [periods, layers of a period, N*C, k]: the layers in the model's order
                picks = picks.reshape((cfg.num_layers - D,) + picks.shape[2:])
        else:
            (x, pools), picks = jax.lax.scan(
                lambda c, xs: layer(c, _with_experts(xs[0], experts, *xs[2:]), xs[1]), carry,
                (layers, jnp.arange(D, L, dtype=jnp.int32) * NB)
                + ((jnp.arange(L - D, dtype=jnp.int32),) if stacked else ()))
    kept = None
    if indexed:
        picks, kept = picks  # the scanned layers': [layers - D, N, ...]
        if kept is not None:  # every layer's, a row: [N, (C,) layers, ...]
            kept = jnp.concatenate([jnp.stack(kept_dense), kept]) if D else kept
            kept = jnp.moveaxis(kept, 0, 2 if kept.ndim == 4 else 1)  # (a chunk's masks: [layers, N, C, words])
    if cfg.hc_mult:
        x = mhc.collapse(x)  # the streams summed, before the last-token selection and the head
    # picks: [routed layers, N*C, k] -> [N, C, routed layers, k]
    picks = None if picks is None else jnp.moveaxis(picks, 0, 1).reshape(N, C, cfg.routed_layers, -1)

    if not all_positions:
        x = jnp.take_along_axis(
            x, jnp.maximum(new_lens - 1, 0)[:, None, None], axis=1
        )[:, 0]  # [N, E]
    if routed and kept is not None:
        return x, pools, kept, picks
    return (x, pools, picks) if routed else (x, pools)


def _split_experts(lp):
    """A scanned layer's stacked parameters without its routed experts'
    leaves, and those leaves (None: no routed layer)."""
    if "moe" not in lp:
        return lp, None
    return {**lp, "moe": {key: p for key, p in lp["moe"].items() if key != "experts"}}, lp["moe"]["experts"]


def _with_experts(lp, stack, index=None):
    """``lp`` with its experts named as row ``index`` of ``stack``, where it has any."""
    if stack is None:
        return lp
    return {**lp, "moe": {**lp["moe"], "experts": ExpertStack(stack, index)}}


def ragged_forward(
    params,
    cfg: TransformerConfig,
    pools: Pools,
    tokens: jax.Array,  # [N, C] int32
    positions: jax.Array,  # [N, C] int32
    new_lens: jax.Array,  # [N] int32
    block_tables: jax.Array,  # [N, P] int32
    block_size: int,
    with_picks: bool = False,
    with_selected: bool = False,
) -> Tuple[jax.Array, ...]:
    """One mixed prefill/decode step -> (last-token logits [N, V], pools), and
    for a routed model asked ``with_picks`` the picks ``[N, C, routed layers,
    k]`` as the LAST value, after what an indexed model's queries kept where
    it hands that out (``_forward_hidden``).

    Reference analog: the whole FastGen model forward over a
    ``RaggedBatchWrapper`` (``inference/v2/engine_v2.py:107`` → model
    implementations → ragged kernels), as one XLA program. The final norm +
    LM head run on the [N, E] last-token hiddens only (norm is positionwise,
    so selecting first is the same math at 1/C the head cost).
    """
    last, pools, *picks = _forward_hidden(
        params, cfg, pools, tokens, positions, new_lens, block_tables, block_size,
        with_picks=with_picks, with_selected=with_selected)
    return (_logits(params, cfg, last), pools, *picks)


def ragged_decode_chain(
    params,
    cfg: TransformerConfig,
    pools: Pools,
    tokens: jax.Array,  # [N] int32 — last sampled token per row (next input)
    start_pos: jax.Array,  # [N] int32 — global position of that input token
    block_tables: jax.Array,  # [N, P] int32, pre-extended for the K-token window
    block_size: int,
    active: jax.Array,  # [N] bool — live rows (pad rows False)
    budgets: jax.Array,  # [N] int32 — max tokens the row may still emit (the chain: k_steps of them)
    rng: jax.Array,  # PRNG key, threaded through the scan and returned
    k_steps: int,
    eos_id: Optional[int] = None,
    *,
    do_sample: bool = False,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    with_picks: bool = False,
) -> Tuple[jax.Array, ...]:
    """K decode iterations + on-device sampling as ONE compiled program.

    The serving fast path: the host dispatches once and fetches once per K
    decoded tokens instead of shipping [N, vocab] logits to the host for
    every token. A
    ``lax.scan`` runs the single-token forward, samples the next token with
    the threaded PRNG key, writes the input token's KV through the
    pre-extended block table, and masks finished rows in-scan: a row goes
    inactive when it samples ``eos_id`` or exhausts its ``budgets`` entry,
    after which its KV writes drop (out-of-range page) and its emitted slots
    are -1.

    Returns ``(out_tokens [N, K], emitted [N], active [N], tok [N], pos [N],
    rng, pools)`` where ``out_tokens[i, :emitted[i]]`` are valid and
    ``emitted[i]`` is also the number of KV slots row i consumed (==
    seen_tokens advance).

    ``active``, ``tok`` and ``pos`` are the scan's carry as it ends: which
    rows are still live (no EOS, and ``budgets`` not used up: give a row's
    whole budget, not its share of this chain, for that to mean "goes on"),
    the token each would feed next and its position. They are what the NEXT
    chain over the same rows starts from, so a caller may dispatch it before
    this one's tokens are fetched (a chain ahead,
    ``InferenceEngineV2.decode_chain``): it passes them back as ``active``,
    ``tokens`` and ``start_pos``, with a block table that covers the next
    window and, in ``budgets``, what it knows by itself: what each row has
    left, 0 for a row that does not go on. A row starts live only with
    ``active`` AND a budget, so a row that ended here at an EOS or at its
    budget rides the next chain dead, as a pad row does: nothing written,
    slots -1, ``emitted`` 0. Started from the host's values or from a carry it
    is one program.

    A routed model asked ``with_picks`` returns two more: ``touched`` int32
    ``[K, routed layers, 3]``, of each step and routed layer how many distinct
    experts the rows LIVE at the step picked (what a step has to read of the
    experts), the visits those got, and how many distinct experts ANY row
    picked, dead and pad rows too (what the decode product reads: the count
    its kernel is handed, ``ops/pallas/moe_decode.py``), of a chip's share
    each among the experts HELD here; and
    ``picks`` int32 ``[K, N, routed layers, k]``, the experts each step's
    input token was sent to (rows not live at a step: garbage). A model with a
    learned indexer hands out between the two ``kept`` int32 ``[K, 2]``: of
    each step, summed over the live rows and the layers, the cached tokens
    their queries scored and the ones they kept.

    Observability contract: the chain boundary is the host's ONLY visibility
    quantum — the K in-scan tokens carry no host timestamps by design, so
    per-token latency (TPOT) is derived as (boundary delta) / ``emitted``
    by the request lifecycle layer (``inference/lifecycle.py``). Anything
    that needs per-token host stamps would reintroduce the per-token sync
    this program exists to eliminate.
    """

    def step(carry, _):
        pools, tok, pos, live, emitted, key = carry
        new_lens = live.astype(jnp.int32)
        last, pools, *picks = _forward_hidden(
            params, cfg, pools, tok[:, None], pos[:, None], new_lens,
            block_tables, block_size, with_picks=with_picks)
        logits = _logits(params, cfg, last)
        key, sub = jax.random.split(key)
        nxt = sample_logits(logits, sub, do_sample=do_sample,
                            temperature=temperature, top_k=top_k, top_p=top_p)
        emitted = emitted + new_lens
        out = jnp.where(live, nxt, -1)
        still = live & (emitted < budgets)
        if eos_id is not None:
            still = still & (nxt != eos_id)
        carry = (pools, jnp.where(live, nxt, tok), pos + new_lens, still, emitted, key)
        if not picks:
            return carry, out
        picked = picks[-1][:, 0]  # [N, routed layers, k]
        # (of a chip's share, by the held experts' own numbers: a pick of another chip's is no row of the one-hot)
        held = picked if cfg.expert_parallel is None else picked - cfg.first_expert
        fed = jax.nn.one_hot(held, cfg.num_experts, dtype=jnp.bool_)  # [N, routed layers, k, E]
        hit = fed & live[:, None, None, None]
        touched = jnp.stack([hit.any(axis=(0, 2)).sum(axis=-1), hit.sum(axis=(0, 2, 3)),
                             fed.any(axis=(0, 2)).sum(axis=-1)], axis=-1).astype(jnp.int32)  # [routed layers, 3]
        if len(picks) > 1:
            # an indexed model: the cached tokens the live rows' queries scored (every one up to their own
            # position, in every layer) and the ones they kept, as the selection itself says
            kept = ((picks[0] >= 0) & live[:, None, None]).sum()
            scored = (jnp.where(live, pos + 1, 0) * picks[0].shape[1]).sum()
            return carry, (out, touched, jnp.stack([scored, kept]).astype(jnp.int32), picked)
        return carry, (out, touched, picked)

    carry0 = (pools, tokens, start_pos, active & (budgets > 0),
              jnp.zeros_like(start_pos), rng)
    (pools, tok, pos, active, emitted, rng), outs = jax.lax.scan(
        step, carry0, None, length=k_steps)
    if isinstance(outs, tuple):
        outs, *routed = outs  # touched, (an indexed model's tokens scored and kept a step,) picks
        return (outs.T, emitted, active, tok, pos, rng, pools, *routed)
    return outs.T, emitted, active, tok, pos, rng, pools


class MigrationBuffer(NamedTuple):
    """Contiguous, block-table-ordered page buffer for KV-block migration
    (ISSUE 14): one request's pool pages — values AND scale pages, the PR-10
    layout travelling as a unit — gathered in block-table order so the
    destination can scatter them into an arbitrarily fragmented allocation
    with the block table rewritten. The bytes are the pool's bytes verbatim
    (int8/fp8 values stay int8/fp8, fp32 scales stay fp32): migration never
    re-quantizes, so the blake2b content identity of every block survives
    and prefix-cache entries stay valid at the destination.

    The shapes are the WIRE's, token-major with the heads apart, and older
    than the pool's page-major layout: a replica built before that change and
    one built after exchange the same documents. Row-major both are the same
    bytes, so export and import only reshape."""

    k: jax.Array  # [L, pages*bs, kvH, hd], pool value dtype
    v: jax.Array
    k_scale: Optional[jax.Array] = None  # [L, pages*bs, kvH, 1] fp32
    v_scale: Optional[jax.Array] = None


def _block_rows(pool: PagedKVPool, num_layers: int, blocks: jax.Array) -> jax.Array:
    """``[L, B]`` rows of the pool's arrays holding ``blocks`` [B] in every layer."""
    first = jnp.arange(num_layers, dtype=jnp.int32) * (pool.k.shape[0] // num_layers)
    return first[:, None] + blocks[None, :]


def export_pool_blocks(pool: PagedKVPool, blocks: jax.Array, num_layers: int,
                       kv_heads: int) -> MigrationBuffer:
    """Gather ``blocks`` (block ids, block-table order, [B] int32 traced) out
    of the pool into one contiguous :class:`MigrationBuffer`. A pure gather —
    the quantized bytes move verbatim; block ids ride as traced values so ONE
    compiled program serves every migration of the same page bucket. Pad
    entries (callers bucket B) may repeat any valid block; the host slices
    the valid prefix by ``n_blocks``."""
    rows = _block_rows(pool, num_layers, blocks)
    tokens = blocks.shape[0] * pool.block_size

    def g(a):
        return None if a is None else a[rows].reshape(num_layers, tokens, kv_heads, -1)

    return MigrationBuffer(*map(g, pool))


def import_pool_blocks(pool: PagedKVPool, buf: MigrationBuffer,
                       blocks: jax.Array, n_valid: jax.Array) -> PagedKVPool:
    """Scatter a :class:`MigrationBuffer` into ``blocks`` of the destination
    pool — the block-table rewrite made physical. ``blocks`` is the
    DESTINATION allocation (any fragmentation; ids need not be contiguous or
    ordered), ``n_valid`` masks the bucket's pad entries (their writes index
    out of bounds and drop). Dtypes must match the destination pool exactly:
    the scatter is verbatim bytes, never a convert — the caller validates
    layout compatibility so quantized pages are never re-quantized."""
    L, B = buf.k.shape[0], blocks.shape[0]
    rows = jnp.where(jnp.arange(B) < n_valid, _block_rows(pool, L, blocks), pool.k.shape[0])

    def s(dst, src):
        if dst is None:
            return None
        return dst.at[rows].set(src.reshape((L, B) + dst.shape[1:]), mode="drop")

    return PagedKVPool(*map(s, pool, buf))


def copy_pool_blocks(pool: PagedKVPool, src: jax.Array, dst: jax.Array,
                     num_layers: int) -> PagedKVPool:
    """Copy one block's page (values + scale pages together — the PR-10
    layout travels as a unit) from block ``src`` to block ``dst`` across
    every layer. The prefix cache's copy-on-write: a shared block diverging
    mid-block is cloned into a private block before the divergent token's
    KV write. ``src``/``dst`` are traced scalars, so ONE jitted program
    serves every COW event."""
    rows = _block_rows(pool, num_layers, jnp.stack([src, dst]))  # [L, 2]

    def cp(a):
        return None if a is None else a.at[rows[:, 1]].set(a[rows[:, 0]])

    return PagedKVPool(*map(cp, pool))


def fetch_pool_block(pool: PagedKVPool, block: jax.Array, num_layers: int):
    """One block's pages of every layer, ``[L, bs, kvH*hd]`` (scales
    ``[L, bs*kvH]``): the bytes the prefix cache's content digest is over."""
    rows = _block_rows(pool, num_layers, block[None])[:, 0]
    return tuple(None if a is None else a[rows] for a in pool)


def _ngram_propose(hist: jax.Array, hist_len: jax.Array, n_spec: int,
                   ngram: int) -> jax.Array:
    """Prompt-lookup draft proposal, fully on device: for each row find the
    LAST previous occurrence of the trailing ``ngram`` tokens in the row's
    history and propose the ``n_spec`` tokens that followed it. Rows with no
    match (or matches running off the valid history) fall back to repeating
    the current token — verification rejects bad drafts, so the fallback
    only costs acceptance, never correctness.

    hist: [N, H] token history (entries >= hist_len are ignored);
    hist_len: [N] tokens valid per row (the current input token is
    ``hist[hist_len - 1]``). Returns drafts [N, n_spec] int32.
    """
    N, H = hist.shape
    pat_idx = jnp.maximum(hist_len[:, None] - ngram + jnp.arange(ngram)[None, :], 0)
    pat = jnp.take_along_axis(hist, pat_idx, axis=1)  # [N, ngram]
    histp = jnp.pad(hist, ((0, 0), (0, ngram + n_spec)), constant_values=-1)
    ok = jnp.ones((N, H), bool)
    for i in range(ngram):
        ok = ok & (histp[:, i: i + H] == pat[:, i: i + 1])
    # window must be a PREVIOUS occurrence fully inside valid history
    ok = ok & (jnp.arange(H)[None, :] < (hist_len - ngram)[:, None])
    any_m = ok.any(axis=1)
    t_star = jnp.where(any_m, H - 1 - jnp.argmax(ok[:, ::-1], axis=1), 0)
    didx = t_star[:, None] + ngram + jnp.arange(n_spec)[None, :]
    drafts = jnp.take_along_axis(histp, didx, axis=1)
    cur = jnp.take_along_axis(hist, jnp.maximum(hist_len - 1, 0)[:, None], axis=1)
    # a draft slot is valid only INSIDE the row's history: positions in
    # [hist_len, H) are buffer zeros (not the -1 pad), which would otherwise
    # propose token id 0 on matches ending near the tail — exactly where a
    # repetitive text's proposer should shine
    valid = (didx < hist_len[:, None]) & (drafts >= 0)
    return jnp.where(any_m[:, None] & valid, drafts, cur).astype(jnp.int32)


def ragged_spec_decode_chain(
    params,
    cfg: TransformerConfig,
    pools: Pools,
    tokens: jax.Array,  # [N] int32 — last sampled token per row (next input)
    start_pos: jax.Array,  # [N] int32 — global position of that input token
    block_tables: jax.Array,  # [N, P], pre-extended for window + n_spec slack
    block_size: int,
    active: jax.Array,  # [N] bool
    budgets: jax.Array,  # [N] int32 — max tokens this chain may emit per row
    rng: jax.Array,
    k_steps: int,  # outer verify iterations (model forwards) per dispatch
    eos_id: Optional[int],
    history: jax.Array,  # [N, H] int32 — context incl. the input token
    hist_len: jax.Array,  # [N] int32 — valid history length per row
    *,
    n_spec: int,
    ngram: int = 2,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, Pools]:
    """Speculative K-step decode chain: greedy verify-and-accept over n-gram
    drafts, still ONE dispatch + ONE host sync per chain.

    Each of the ``k_steps`` scan iterations forwards ``1 + n_spec`` tokens
    (the current input plus proposed drafts) through the SAME ragged layer
    stack as the plain chain, takes greedy targets at every position, and
    accepts the longest draft prefix that matches — emitting between 1 and
    ``1 + n_spec`` tokens per model forward. Rejected-draft KV writes are
    position-addressed, so the next iteration's writes simply overwrite
    them; accepted-draft KV is already correct (the verify forward IS the
    target forward at those positions). Greedy only: acceptance compares
    against argmax targets, which keeps spec output token-identical to the
    plain chain by construction.

    Transient KV writes run ``n_spec`` positions past the last emitted
    token, so the caller pre-extends block tables for ``window + n_spec``
    tokens (see ``InferenceEngineV2.decode_spec_chain``).

    Returns ``(out_tokens [N, k_steps*(1+n_spec)] compacted, emitted [N],
    active [N], steps [N], rng, pools)`` — ``out_tokens[i, :emitted[i]]``
    valid, ``steps[i]`` = model forwards row i was live for (the
    accepted-tokens/forward telemetry denominator).
    """
    m = 1 + n_spec
    N = tokens.shape[0]
    idx = jnp.arange(m)[None, :]

    def step(carry, _):
        pools, tok, pos, live, emitted, hist, hlen, steps, key = carry
        drafts = _ngram_propose(hist, hlen, n_spec, ngram)  # [N, n_spec]
        inputs = jnp.concatenate([tok[:, None], drafts], axis=1)  # [N, m]
        positions = pos[:, None] + jnp.arange(m)[None, :]
        new_lens = jnp.where(live, m, 0)
        hs, pools = _forward_hidden(params, cfg, pools, inputs, positions,
                                   new_lens, block_tables, block_size,
                                   all_positions=True)
        logits = _logits(params, cfg, hs)  # [N, m, V]
        g = greedy_tokens(logits)  # [N, m] greedy targets
        # draft j accepted iff it matches the target at its previous
        # position AND every earlier draft was accepted (cumulative)
        match = (inputs[:, 1:] == g[:, :-1]).astype(jnp.int32)  # [N, n_spec]
        n_acc = jnp.cumprod(match, axis=1).sum(axis=1)
        e = jnp.minimum(n_acc + 1, budgets - emitted)
        has_eos = jnp.zeros((N,), bool)
        if eos_id is not None:
            is_eos = (g == eos_id) & (idx < e[:, None])
            has_eos = is_eos.any(axis=1)
            e = jnp.where(has_eos, jnp.argmax(is_eos, axis=1) + 1, e)
        e = jnp.where(live, e, 0)
        out = jnp.where((idx < e[:, None]) & live[:, None], g, -1)
        nxt = jnp.take_along_axis(g, jnp.maximum(e - 1, 0)[:, None], axis=1)[:, 0]
        # append the emitted tokens to the on-device history (the proposer's
        # source); masked slots scatter out of bounds and drop
        hidx = jnp.where(idx < e[:, None], hlen[:, None] + idx, hist.shape[1])
        hist = hist.at[jnp.arange(N)[:, None], hidx].set(g, mode="drop")
        emitted = emitted + e
        still = live & (emitted < budgets) & ~has_eos
        steps = steps + live.astype(jnp.int32)
        return (pools, jnp.where(live, nxt, tok), pos + e, still, emitted,
                hist, hlen + e, steps, key), out

    zeros = jnp.zeros_like(start_pos)
    carry0 = (pools, tokens, start_pos, active, zeros, history, hist_len,
              zeros, rng)
    (pools, _, _, active, emitted, _, _, steps, rng), outs = jax.lax.scan(
        step, carry0, None, length=k_steps)
    # compact: each iteration's emitted prefix packs to the row's front, so
    # the host contract stays out[i, :emitted[i]] exactly like the plain chain
    o = outs.transpose(1, 0, 2).reshape(N, k_steps * m)
    valid = o >= 0
    tgt = jnp.where(valid, jnp.cumsum(valid, axis=1) - 1, k_steps * m)
    compact = jnp.full((N, k_steps * m), -1, jnp.int32)
    compact = compact.at[jnp.arange(N)[:, None], tgt].set(o, mode="drop")
    return compact, emitted, active, steps, rng, pools
