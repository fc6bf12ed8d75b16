"""Paged (block-table) flash-decode attention as a Pallas TPU kernel.

TPU-native analog of FastGen's ``blocked_flash`` kernel
(``inference/v2/kernels/ragged_ops/blocked_flash/`` — paged attention over a
blocked KV cache) — the kernel the reference's 2.3x-vs-vLLM claim lives in
(``blogs/deepspeed-fastgen/README.md:28``).

Design: the WHOLE KV pool, every layer's pages in one page-major
``[pages, bs, kvH*hd]`` array, stays in HBM (``memory_space=ANY``) exactly as
``inference/paged.py`` stores it: the kernel takes it as it is, with no view
or copy made for it. The block table rides scalar prefetch and already points
into that array (the caller adds the layer's first page), so the kernel
issues manual DMAs of exactly the pages each sequence owns — no dense gather
ever materializes. Grid is ``(rows, page_chunks)``; each step copies
``pages_per_block`` pages (all kv heads of a page in one lane-dense slab)
into VMEM, runs one online-softmax update per kv head for all query heads in
its GQA group, and page-chunks past a row's live length are skipped entirely
(compute AND DMA — the guard wraps the copies).

Against the XLA fallback (gather pages to dense then masked attention) this
removes the gathered-copy write+read and the [rows, tokens] fp32 score
round-trip: decode becomes one streaming read of the live KV pages, which is
the bandwidth floor for paged attention.

The KV-insert+RoPE side of the reference's kernel pair
(``linear_blocked_kv_rotary``) is an XLA scatter of the new tokens' rows into
that same array at ``(page, slot in page)``, in place in the layer scan's
carry (``inference/paged.py``, scope ``kv_write``).

Quantized KV pools (int8/e4m3 values + per-(slot, head) fp32 scales — see
``inference/paged.py``): the scale pages DMA alongside the value pages and
dequantization happens on the VMEM tiles right after the block load, so the
full-precision pool never materializes anywhere — HBM holds the quantized
bytes, VMEM holds one dequantized page-chunk at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.compat import tpu_compiler_params

from deepspeed_tpu.ops.registry import register

_NEG_INF = float(jnp.finfo(jnp.float32).min)
_LANES = 8
DEFAULT_PAGES_PER_BLOCK = 8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _decode_kernel(bt_ref, ap_ref, *refs, ppcb, alibi=False, quantized=False):
    refs = list(refs)
    q_ref, qpos_ref = refs.pop(0), refs.pop(0)
    slopes_ref = refs.pop(0) if alibi else None
    (k_hbm, v_hbm) = refs.pop(0), refs.pop(0)
    ks_ref = vs_ref = None
    if quantized:
        ks_ref, vs_ref = refs.pop(0), refs.pop(0)
    o_ref = refs.pop(0)
    kbuf, vbuf, acc_ref, m_ref, l_ref, sem_k, sem_v = refs
    n = pl.program_id(0)
    pc = pl.program_id(1)
    npc = pl.num_programs(1)
    _, kvH, Cgp, hd = q_ref.shape
    bs = kbuf.shape[1]
    T = ppcb * bs

    @pl.when(pc == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _compute():
        # one DMA per live page, ALL kv heads at once: a page is a contiguous
        # lane-dense [bs, kvH*hd] slab of the pool as it is stored, so the copy
        # slices only the leading (untiled) page dim — Mosaic refuses any
        # slice of the tiled minor dims that is not (8, 128)-aligned, which
        # a per-head [bs, 1, hd] copy never is
        copies = []
        for i in range(ppcb):
            page = bt_ref[n, pc * ppcb + i]
            copies.append(pltpu.make_async_copy(k_hbm.at[page], kbuf.at[i], sem_k))
            copies.append(pltpu.make_async_copy(v_hbm.at[page], vbuf.at[i], sem_v))
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

        cdt = q_ref.dtype
        k_all, v_all = kbuf[...], vbuf[...]  # [ppcb, bs, kvH*hd]
        if quantized:
            # widen before the page-merge reshape: a 1-byte tile is 32 rows,
            # a 16-slot page is not, fp32's 8-row tile divides any page
            k_all, v_all = k_all.astype(jnp.float32), v_all.astype(jnp.float32)
        k_all = k_all.reshape(T, kvH * hd).astype(cdt)
        v_all = v_all.reshape(T, kvH * hd).astype(cdt)

        # causality over SEQUENCE positions: token j of this page-chunk is at
        # global position pc*T + j; visible iff <= the query's position
        j = pc * T + jax.lax.broadcasted_iota(jnp.int32, (Cgp, T), 1)
        visible = j <= qpos_ref[0]  # qpos: [Cgp, 1] column
        for kh in range(kvH):
            q = q_ref[0, kh]  # [Cgp, hd] (pre-scaled)
            k = k_all[:, kh * hd:(kh + 1) * hd]  # [T, hd]
            v = v_all[:, kh * hd:(kh + 1) * hd]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # [Cgp, T]
            if quantized:
                # fused dequant: int8/e4m3 values are exact in the compute
                # dtype, so the per-slot scale factors out of the head-dim
                # contraction — one [1, T] row multiply on the scores
                s = s * ks_ref[0, pl.ds(kh, 1), :]
            if alibi:
                # bloom convention slope * key-position (slot index ==
                # position); slopes arrive row-aligned with the (c, g) layout
                s = s + slopes_ref[kh] * j.astype(jnp.float32)
            s = jnp.where(visible, s, _NEG_INF)

            m_prev = jnp.max(m_ref[kh], axis=-1, keepdims=True)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            m_safe = jnp.where(m_cur == _NEG_INF, 0.0, m_cur)
            p = jnp.exp(s - m_safe)
            alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
            l_prev = jnp.max(l_ref[kh], axis=-1, keepdims=True)
            l_ref[kh] = jnp.broadcast_to(
                alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape[1:])
            m_ref[kh] = jnp.broadcast_to(m_cur, m_ref.shape[1:])
            if quantized:
                p = p * vs_ref[0, pl.ds(kh, 1), :]  # value scales fold into p
            acc_ref[kh] = acc_ref[kh] * alpha + jax.lax.dot_general(
                p.astype(cdt), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )

    # skip page-chunks entirely beyond the row's live pages (guard wraps the
    # DMAs too — dead pages cost no bandwidth)
    pl.when(pc * ppcb < ap_ref[n])(_compute)

    @pl.when(pc == npc - 1)
    def _finalize():
        l = jnp.max(l_ref[:], axis=-1, keepdims=True)
        o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@register("paged_attention", "pallas")
def flash_decode_paged(
    q: jax.Array,  # [N, C, H, hd]
    pool_k: jax.Array,  # [pages, bs, kvH*hd]: the whole pool, all layers
    pool_v: jax.Array,
    block_tables: jax.Array,  # [N, P] int32 pages of pool_k (layer offset added)
    q_positions: jax.Array,  # [N, C] int32
    block_size: int,
    new_lens: jax.Array = None,  # [N] live tokens (for page skipping)
    pages_per_block: int = DEFAULT_PAGES_PER_BLOCK,
    alibi_slopes: jax.Array = None,  # [H] fp32 (bloom ALiBi, fused in-kernel)
    k_scale: jax.Array = None,  # [pages, bs*kvH] fp32 — quantized pool scales
    v_scale: jax.Array = None,
) -> jax.Array:
    N, C, H, hd = q.shape
    kvH = pool_k.shape[2] // hd
    G = H // kvH
    P = block_tables.shape[1]
    bs = block_size
    ppcb = min(pages_per_block, P)
    Pp = _cdiv(P, ppcb) * ppcb
    if Pp != P:
        block_tables = jnp.pad(block_tables, ((0, 0), (0, Pp - P)))
    npc = Pp // ppcb
    T = ppcb * bs

    Cg = C * G
    Cgp = _cdiv(Cg, _LANES) * _LANES

    # [N, kvH, Cg, hd] query layout; rows are (c, g) pairs, padded to sublanes
    scale = jnp.asarray(hd ** -0.5, q.dtype)
    q5 = (q * scale).reshape(N, C, kvH, G, hd).transpose(0, 2, 1, 3, 4).reshape(N, kvH, Cg, hd)
    qpos_rows = jnp.broadcast_to(q_positions[:, :, None], (N, C, G)).reshape(N, Cg)
    if Cgp != Cg:
        q5 = jnp.pad(q5, ((0, 0), (0, 0), (0, Cgp - Cg), (0, 0)))
        # padded rows see nothing (position -1 masks every token)
        qpos_rows = jnp.pad(qpos_rows, ((0, 0), (0, Cgp - Cg)), constant_values=-1)

    # live pages per row: positions are ascending within the live prefix
    if new_lens is None:
        max_pos = jnp.max(q_positions, axis=1)
    else:
        last = jnp.maximum(new_lens - 1, 0)
        max_pos = jnp.take_along_axis(q_positions, last[:, None], axis=1)[:, 0]
    active_pages = (max_pos + 1 + bs - 1) // bs  # [N]

    alibi = alibi_slopes is not None
    operands = [q5, qpos_rows[:, :, None]]
    in_specs = [
        pl.BlockSpec((1, kvH, Cgp, hd), lambda n, pc, bt, ap: (n, 0, 0, 0)),
        # per-row scalars ride as [.., Cgp, 1] COLUMNS: a (1, Cgp) row block
        # breaks Mosaic's (8, 128) block rule; (Cgp, 1) has Cgp % 8 == 0 and
        # a full last dim, and is already the broadcast shape the mask needs
        pl.BlockSpec((1, Cgp, 1), lambda n, pc, bt, ap: (n, 0, 0)),
    ]
    if alibi:
        # row-aligned slopes: row (c, g) of kv head kh uses slope[kh*G + g]
        srows = jnp.broadcast_to(
            alibi_slopes.astype(jnp.float32).reshape(kvH, 1, G), (kvH, C, G)
        ).reshape(kvH, Cg)
        if Cgp != Cg:
            srows = jnp.pad(srows, ((0, 0), (0, Cgp - Cg)))
        operands.append(srows[:, :, None])
        in_specs.append(pl.BlockSpec((kvH, Cgp, 1), lambda n, pc, bt, ap: (0, 0, 0)))
    operands += [pool_k, pool_v]
    in_specs += [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    quantized = k_scale is not None
    if quantized:
        # scales are 1/hd of the pool: gather the rows' pages in XLA into
        # [N, kvH, Pp*bs] ROWS (slot index == position) and let the pipeline
        # fetch the page-chunk's [kvH, T] block — the kernel multiplies the
        # scores / probabilities by them, never the value tiles
        for sc in (k_scale, v_scale):
            operands.append(sc[block_tables].reshape(N, Pp * bs, kvH).transpose(0, 2, 1))
            in_specs.append(pl.BlockSpec((1, kvH, T), lambda n, pc, bt, ap: (n, 0, pc)))

    kernel = functools.partial(_decode_kernel, ppcb=ppcb, alibi=alibi, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        name="paged_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_tables, active_pages
            grid=(N, npc),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, kvH, Cgp, hd), lambda n, pc, bt, ap: (n, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((ppcb, bs, kvH * hd), pool_k.dtype),
                pltpu.VMEM((ppcb, bs, kvH * hd), pool_v.dtype),
                pltpu.VMEM((kvH, Cgp, hd), jnp.float32),
                pltpu.VMEM((kvH, Cgp, _LANES), jnp.float32),
                pltpu.VMEM((kvH, Cgp, _LANES), jnp.float32),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N, kvH, Cgp, hd), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=_interpret(),
    )(block_tables, active_pages, *operands)

    out = out[:, :, :Cg].reshape(N, kvH, C, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(N, C, H, hd)
