"""Paged (block-table) flash-decode attention as a Pallas TPU kernel.

TPU-native analog of FastGen's ``blocked_flash`` kernel
(``inference/v2/kernels/ragged_ops/blocked_flash/`` — paged attention over a
blocked KV cache) — the kernel the reference's 2.3x-vs-vLLM claim lives in
(``blogs/deepspeed-fastgen/README.md:28``).

Design: the WHOLE KV pool, every layer's pages in one page-major
``[pages, bs, kvH*hd]`` array, stays in HBM (``memory_space=ANY``) exactly as
``inference/paged.py`` stores it: the kernel takes it as it is, with no view
or copy made for it. The block table and each row's context length ride
scalar prefetch, and the table already points into that array (the caller
adds the layer's first page), so the kernel issues manual DMAs of exactly the
pages each sequence owns — no dense gather ever materializes.

The walk: the grid is the rows, in order; inside a step a loop runs over the
row's LIVE page-chunks, ``cdiv(pages of the row, pages a chunk)`` of them, so
a row of 130 tokens costs two iterations under a table of 128 columns or of
8,192, and a row with no page costs an empty step that writes zeros. Each page
is one DMA of a lane-dense ``[bs, kvH*hd]`` slab with all kv heads. K and V
have TWO slots each: before chunk ``i`` is waited for, chunk ``i + 1`` is
started into the other slot, and at a row's last chunk the NEXT row's first
chunk (its pages are in SMEM already), so one fetch a call is exposed and the
rest hide behind compute. Of a partial last chunk only the pages the row holds
are fetched; the values past the row's length (dead slots of its last page,
whatever the slot's other pages held before) are zeroed in the buffer, because
the scores there are masked but ``0 * NaN`` in ``p @ v`` is not.

A chunk is sized by its BYTES, from the call's shapes alone
(``_pages_a_chunk``): what a chunk costs beside its bytes (the loops' glue,
the accumulator read, scaled and written, the statistics: some 450 cycles of
scalar and vector code) is the same whatever a page holds, so a chunk takes as
many pages as make about 1 MiB of keys and values, never fewer than 8 nor more
than 32, whole eights under a wider table, else the table's columns (a ring of
9 pages is ONE chunk, not 8 + 1), fewer where the query side leaves less VMEM
(``_VMEM_BUDGET``); ``pages_per_block`` given as a number is that number. What
a PAGE costs beside its bytes is kept small: the table lies flat in SMEM (one
sum finds a row's entry), a full chunk is waited for with one wait a buffer (a
descriptor over the whole slot waits for the sum of its pages' bytes; a partial
chunk waits page by page), and the copies carry NO BOUNDS CHECK
(``disable_bounds_checks``: four halting compares a page were more scalar code
than the two copies). What guards the pool's edge is then the table alone: the
kernel fetches only a row's LIVE columns, which hold pages of the class's
allocator (``ragged.StateManager``: below its ``num_blocks``; dead columns are
0 and are not fetched) plus the layer's first page, and a class's array is its
layers times those pages (``cache.PagedKVPool``). The host's test of that is
``tests/unit/inference/test_mimo_v2.py::test_every_table_handed_to_a_step_...``;
a caller with a table of its own keeps the same promise.

The compute has two forms, chosen from the shapes alone. With a sublane tile
or more of query rows a kv head (``C*G >= 8``: prompts, chunks) each chunk
runs one online-softmax update per kv head for all query rows of its GQA
group. With fewer (decode, a few drafts) such a product is mostly padding and
its bookkeeping is paid ``kvH`` times a chunk; there every (query row, kv
head) pair becomes one row of a block-diagonal query ``[C*G*kvH, kvH*hd]``,
so ONE product against the lane-dense chunk gives every head's scores, one
update serves all heads, and the output is the diagonal blocks of one
``p @ v``. bf16 products, fp32 accumulation and fp32 statistics in both; a
head's running max and sum share one ref (the low and the high lanes), since
each alone pads to 128 lanes and a lane-dense layout costs more to address
than it saves.

Against the XLA fallback (gather pages to dense then masked attention) this
removes the gathered-copy write+read and the [rows, tokens] fp32 score
round-trip: decode becomes one streaming read of the live KV pages, which is
the bandwidth floor for paged attention.

The KV-insert+RoPE side of the reference's kernel pair
(``linear_blocked_kv_rotary``) is an XLA scatter of the new tokens' rows into
that same array at ``(page, slot in page)``, in place in the layer scan's
carry (``inference/paged.py``, scope ``kv_write``).

Quantized KV pools (int8/e4m3 values + per-(slot, head) fp32 scales — see
``inference/paged.py``): a chunk's scale rows are fetched beside its pages,
into slots of their own, and dequantization happens on the VMEM tiles right
after the load (the scales multiply scores and probabilities, never the value
tiles), so the full-precision pool never materializes anywhere — HBM holds
the quantized bytes, VMEM holds one dequantized page-chunk at a time.
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
_SUBLANES = 8
DEFAULT_PAGES_PER_BLOCK = 8  # the fewest pages a chunk of the kernel's own choosing (``_pages_a_chunk``)
# What a chunk of the kernel's own choosing holds of keys and values together, about, and the most pages it takes
# to get there: 8 pages of 128 KiB, 16 heads of 128 a page of 16 slots, are 2 cycles of bytes an instruction of the
# walk; a page of 40 KiB at 8 a chunk is 0.6 (PERF.md, PR 62).
_CHUNK_BYTES = 1 << 20
_MAX_PAGES_PER_BLOCK = 32
# What the kernel's own buffers may take of Mosaic's 16 MiB of scoped VMEM, by
# ``flash_decode_paged``'s count; the rest is the compiler's. The (64, 256)
# prefill of a 16 x 128 model counts 10 MiB with two 8-page slots of K and V.
_VMEM_BUDGET = 11 << 20


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _decode_kernel(bt_ref, ctx_ref, *refs, ppcb, P, bs, kvH, hd, Cg, dense, alibi, quantized, banded=False,
                   hdv, sink=False):
    refs = list(refs)
    low_ref = refs.pop(0) if banded else None  # (a third scalar operand: a row's first slot live to ANY of its queries)
    q_ref, qpos_ref = refs.pop(0), refs.pop(0)
    qlow_ref = refs.pop(0) if banded else None  # a query row's first live slot (a ring of pages)
    slopes_ref = refs.pop(0) if alibi else None
    sink_ref = refs.pop(0) if sink else None  # a query row's head's logit in the softmax's denominator
    k_hbm, v_hbm = refs.pop(0), refs.pop(0)
    ks_hbm = vs_hbm = ksbuf = vsbuf = None
    if quantized:
        ks_hbm, vs_hbm = refs.pop(0), refs.pop(0)
    o_ref, kbuf, vbuf = refs.pop(0), refs.pop(0), refs.pop(0)
    if quantized:
        ksbuf, vsbuf = refs.pop(0), refs.pop(0)
    acc_ref, ml_ref, sems, slot_ref = refs
    n = pl.program_id(0)
    N = pl.num_programs(0)
    D, Dv = kvH * hd, kvH * hdv
    T = ppcb * bs
    cdt = q_ref.dtype

    def pages_of(row):  # a row's live pages
        return _cdiv(ctx_ref[row], bs)

    def chunk_dma(row, chunk, slot, start):
        """Start, or wait for, the copies of one page-chunk of ``row`` into
        ``slot``: one DMA per LIVE page (all kv heads at once: a page is a
        contiguous lane-dense [bs, kvH*hd] slab of the pool as it is stored, so
        the copy slices only the leading, untiled page dim), and for a
        quantized pool the chunk's [kvH, T] scale rows. A FULL chunk is waited
        for at once, a buffer: a descriptor over the whole slot waits for the
        sum of its pages' bytes."""
        live = jnp.clip(pages_of(row) - chunk * ppcb, 0, ppcb)
        buffers = ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1))
        first = row * P + chunk * ppcb  # the chunk's first entry of the table, which lies flat: one sum a page

        def page(i):
            # (a wait reads no table: its descriptor gives the bytes alone)
            p = bt_ref[first + i] if start else 0
            for hbm, buf, which in buffers:
                c = pltpu.make_async_copy(hbm.at[p], buf.at[slot, i], sems.at[slot, which])
                c.start() if start else c.wait()

        if start:
            pl.loop(0, live)(page)
        else:
            full = live == ppcb

            @pl.when(full)
            def _():
                for _, buf, which in buffers:
                    pltpu.make_async_copy(buf.at[slot], buf.at[slot], sems.at[slot, which]).wait()

            pl.loop(0, jnp.where(full, 0, live))(page)
        if quantized:
            for hbm, buf, which in ((ks_hbm, ksbuf, 2), (vs_hbm, vsbuf, 3)):
                c = pltpu.make_async_copy(hbm.at[row, chunk], buf.at[slot], sems.at[slot, which])
                pl.when(live > 0)(c.start if start else c.wait)

    ctx = ctx_ref[n]
    nc = _cdiv(pages_of(n), ppcb)  # the row's page-chunks: the walk's trip count
    slot0 = jnp.where(n == 0, 0, slot_ref[0])  # where the row's first chunk was sent

    @pl.when(n == 0)
    def _first():
        chunk_dma(0, 0, 0, start=True)

    # A head's running max and sum share one ref (a [rows, 1] column pads to
    # 128 lanes whatever it holds, so two refs are twice the VMEM): the max in
    # the low half of its 8 lanes, the sum, which is never negative, in the
    # high half, each read back by a masked max over the lanes.
    low = jax.lax.broadcasted_iota(jnp.int32, ml_ref.shape[1:], 1) < _SUBLANES // 2

    def stats(g):
        ml = ml_ref[g]
        return (jnp.max(jnp.where(low, ml, _NEG_INF), axis=-1, keepdims=True),
                jnp.max(jnp.where(low, 0.0, ml), axis=-1, keepdims=True))

    acc_ref[...] = jnp.zeros_like(acc_ref)
    ml_ref[...] = jnp.broadcast_to(jnp.where(low, _NEG_INF, 0.0), ml_ref.shape)

    if dense:
        # every (query row, kv head) pair is one row of a block-diagonal query
        # [W, kvH*hd]: row w = r*kvH + kh holds query row r's head kh in lanes
        # [kh*hd, (kh+1)*hd) and zeros elsewhere, so ONE product against the
        # lane-dense chunk gives every head's scores
        W = acc_ref.shape[1]
        row = jax.lax.broadcasted_iota(jnp.int32, (W, D), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (W, D), 1)
        qbd = jnp.zeros((W, D), jnp.float32)  # a select on 32-bit lanes; narrowed once
        diag = []
        for r in range(Cg):
            kh = row - r * kvH
            on = (kh >= 0) & (kh < kvH) & (lane >= kh * hd) & (lane < (kh + 1) * hd)
            diag.append(on)
            q_r = q_ref[0, r:r + 1, :].astype(jnp.float32)
            qbd = jnp.where(on, jnp.broadcast_to(q_r, (W, D)), qbd)
        qbd = qbd.astype(cdt)
        if hdv != hd:  # the output's diagonal blocks are ``hdv`` wide, of ``Dv`` lanes
            row = jax.lax.broadcasted_iota(jnp.int32, (W, Dv), 0)
            lane = jax.lax.broadcasted_iota(jnp.int32, (W, Dv), 1)
            diag = [(row - r * kvH >= 0) & (row - r * kvH < kvH) & (lane >= (row - r * kvH) * hdv)
                    & (lane < (row - r * kvH + 1) * hdv) for r in range(Cg)]

    def update(g, q, k, v, visible, j, scales, slopes):
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [rows, T]
        if quantized:
            # fused dequant: int8/e4m3 values are exact in the compute dtype,
            # so the per-slot scale factors out of the head-dim contraction —
            # one row multiply on the scores
            s = s * scales[0]
        if alibi:
            # bloom convention slope * key-position (slot index == position)
            s = s + slopes * j.astype(jnp.float32)
        s = jnp.where(visible, s, _NEG_INF)

        m_prev, l_prev = stats(g)  # [rows, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_cur == _NEG_INF, 0.0, m_cur)
        p = jnp.exp(s - m_safe)
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        ml_ref[g] = jnp.where(low, m_cur, alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True))
        if quantized:
            # value scales fold into p; a dead slot's scale is whatever its
            # page holds, and 0 * NaN is NaN
            p = jnp.where(visible, p * scales[1], 0.0)
        acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
            p.astype(cdt), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    def compute(c, slot):
        # What lies past the row's length is masked out of the scores below,
        # but 0 * NaN is NaN in p @ v. Only a row's last chunk holds any: the
        # dead slots of its last page, and pages of the chunk that were not
        # fetched (whatever the slot held before). Zero the values there.
        @pl.when(c == nc - 1)
        def _():
            @pl.loop((ctx - c * T) // bs, ppcb)
            def _(i):
                pos = c * T + i * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, Dv), 0)
                vbuf[slot, i] = jnp.where(pos < ctx, vbuf[slot, i], jnp.zeros((), vbuf.dtype))

        if banded:
            # likewise the slots of the walk's first pages that the window has left behind: masked out of the
            # scores, and whatever they hold (an older block of the ring) must not reach p @ v as 0 * NaN
            @pl.when(c == 0)
            def _():
                @pl.loop(0, jnp.minimum(_cdiv(low_ref[n], bs), ppcb))
                def _(i):
                    pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, Dv), 0)
                    vbuf[slot, i] = jnp.where(pos >= low_ref[n], vbuf[slot, i], jnp.zeros((), vbuf.dtype))

        k_all, v_all = kbuf[slot], vbuf[slot]  # [ppcb, bs, kvH*hd]
        if quantized:
            # widen before the page-merge reshape: a 1-byte tile is 32 rows,
            # a 16-slot page is not, fp32's 8-row tile divides any page
            k_all, v_all = k_all.astype(jnp.float32), v_all.astype(jnp.float32)
        k_all = k_all.reshape(T, D).astype(cdt)
        v_all = v_all.reshape(T, Dv).astype(cdt)

        # causality over SEQUENCE positions: token j of this page-chunk is at
        # global position c*T + j; visible iff <= the query row's position
        rows = qpos_ref.shape[1]
        j = c * T + jax.lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        visible = j <= qpos_ref[0]  # qpos: [rows, 1] column
        if banded:  # the slots of the walk's first page that the window has left behind
            visible = visible & (j >= qlow_ref[0])
        for g in range(acc_ref.shape[0]):
            if dense:
                # row r*kvH + kh takes head kh's scales
                pad = [jnp.zeros((W - Cg * kvH, T), jnp.float32)] * (W > Cg * kvH)
                q, lanes, vlanes = qbd, slice(None), slice(None)
                tile = lambda b: jnp.concatenate([b[slot, :kvH]] * Cg + pad)  # noqa: E731
            else:
                q, lanes, vlanes = q_ref[0, g], slice(g * hd, (g + 1) * hd), slice(g * hdv, (g + 1) * hdv)
                tile = lambda b: b[slot, pl.ds(g, 1), :]  # noqa: E731
            update(g, q, k_all[:, lanes], v_all[:, vlanes], visible, j,
                   [tile(b) for b in (ksbuf, vsbuf)] if quantized else None,
                   slopes_ref[g] if alibi else None)

    @pl.loop(0, nc)
    def _walk(c):
        slot = (slot0 + c) % 2
        # the next chunk to fetch: this row's, or at its last chunk the next
        # row's first (its pages are in SMEM already)
        more = c + 1 < nc
        nrow = jnp.where(more, n, n + 1)

        @pl.when(nrow < N)
        def _():
            chunk_dma(nrow, jnp.where(more, c + 1, 0), 1 - slot, start=True)

        chunk_dma(n, c, slot, start=False)
        compute(c, slot)

    # a row with no page fetches nothing; it still owes the next row its
    # first chunk
    @pl.when((nc == 0) & (n + 1 < N))
    def _():
        chunk_dma(n + 1, 0, slot0, start=True)

    slot_ref[0] = (slot0 + nc) % 2

    def normalised(g):
        m, l = stats(g)
        if sink_ref is None:
            return acc_ref[g] / jnp.where(l == 0.0, 1.0, l)
        # the sink joins the sum and weighs no value: acc / (l + e^(sink - m)), both terms taken against the
        # larger of m and the sink so that neither power overflows; a row that saw no key stays zeros
        s = sink_ref[g]  # [rows, 1]
        top = jnp.maximum(jnp.where(m == _NEG_INF, s, m), s)
        shrink = jnp.where(m == _NEG_INF, 0.0, jnp.exp(m - top))
        return acc_ref[g] * shrink / (l * shrink + jnp.exp(s - top))

    if dense:
        # row w's output is its own head's lanes of out[w]: keep the diagonal
        # blocks, and fold the kv heads' rows of query row r into one row
        out = normalised(0)
        for r in range(Cg):
            o_ref[0, r:r + 1, :] = jnp.sum(
                jnp.where(diag[r], out, 0.0), axis=0, keepdims=True).astype(o_ref.dtype)
    else:
        for g in range(kvH):
            o_ref[0, g] = normalised(g).astype(o_ref.dtype)


def _pages_a_chunk(page_bytes: int, columns: int, query_side: int, pages_per_block: int = None) -> int:
    """The pages of a chunk, from the call's shapes alone. Told none (``pages_per_block`` None), as many as hold
    about ``_CHUNK_BYTES`` at ``page_bytes`` a page (its keys and values together, a quantized pool's scales beside
    them): what a chunk costs beside its bytes (the glue around the loops, the accumulator read, scaled and
    written, the statistics) is paid once however wide it is, and a narrow page brings few bytes to pay it with.
    Never fewer than ``DEFAULT_PAGES_PER_BLOCK`` nor more than ``_MAX_PAGES_PER_BLOCK``; whole eights where the
    table is wider than that (a chunk's ``T`` then stays whole 128-lane tiles at 16 slots a page), else all the
    table's ``columns``, one chunk. A number keeps meaning that number. Either way no more than the table holds,
    and halved while the query side and the two slots of the chunk's pages pass ``_VMEM_BUDGET``."""
    if pages_per_block is None:
        pages = min(max(_CHUNK_BYTES // page_bytes, DEFAULT_PAGES_PER_BLOCK), _MAX_PAGES_PER_BLOCK)
        pages_per_block = pages // 8 * 8 if columns > pages else columns
    ppcb = max(1, min(pages_per_block, columns))
    while ppcb > 1 and query_side + 2 * ppcb * page_bytes > _VMEM_BUDGET:
        ppcb //= 2
    return ppcb


def _query_side_bytes(heads: int, rows: int, width: int, itemsize: int) -> int:
    """VMEM of a grid step's query side: the blocks of q and of the output,
    each double-buffered by the pipeline, the fp32 accumulator, and the
    statistics; a minor dimension under 128 pads to 128 lanes."""
    lanes = _cdiv(width, 128) * 128
    return heads * rows * (4 * lanes * itemsize + lanes * 4 + 128 * 4)


@register("paged_attention", "pallas")
def flash_decode_paged(
    q: jax.Array,  # [N, C, H, hd]
    pool_k: jax.Array,  # [pages, bs, kvH*hd]: the whole pool, all layers
    pool_v: jax.Array,
    block_tables: jax.Array,  # [N, P] int32 pages of pool_k (layer offset added)
    q_positions: jax.Array,  # [N, C] int32
    block_size: int,
    new_lens: jax.Array = None,  # [N] live tokens (for page skipping)
    pages_per_block: int = None,  # None: ``_pages_a_chunk``'s rule on a page's bytes and the table's width
    alibi_slopes: jax.Array = None,  # [H] fp32 (bloom ALiBi, fused in-kernel)
    k_scale: jax.Array = None,  # [pages, bs*kvH] fp32 — quantized pool scales
    v_scale: jax.Array = None,
    first_live: jax.Array = None,  # [N, C] int32: slots before it are dead (a ring of pages; kernel ``swa_paged_attn``)
    sink: jax.Array = None,  # [H]: a logit a query head in the softmax's denominator alone
) -> jax.Array:
    """``first_live`` is for a row whose table is a RING of pages rolled so that
    its oldest live page comes first (``inference/paged.py``, a sliding layer):
    slot index is then position less the first page's first position,
    ``q_positions`` are given in those units, and of the first page the slots
    before ``first_live`` hold positions the window has left behind: masked,
    as the slots past the query are. None is the kernel as it always was.

    A value may be narrower than its key: ``pool_v`` ``[pages, bs, kvH*hdv]``
    beside ``pool_k`` ``[pages, bs, kvH*hd]``, each page a lane-dense slab of
    its own width (a key of 192 lies in a page as it is, ``kvH * 192`` lanes,
    whole 128-lane tiles for an even ``kvH``: nothing is padded), the output
    ``[N, C, H, hdv]``. A head's lanes then start off a tile's edge, so every
    (query row, kv head) pair is a row of the block-diagonal query whenever the
    pairs fit one 128-row pass, and no product slices a head out of a page.
    ``sink`` adds ``exp(sink_h)`` to the running denominator at the walk's end."""
    N, C, H, hd = q.shape
    D, Dv = pool_k.shape[2], pool_v.shape[2]
    kvH = D // hd
    hdv = Dv // kvH
    G = H // kvH
    P = block_tables.shape[1]
    bs = block_size
    Cg = C * G
    alibi = alibi_slopes is not None
    quantized = k_scale is not None

    # The compute's form, from the shapes alone (module docstring): one
    # block-diagonal query for all heads where a head has under a sublane tile
    # of query rows and the (row, head) pairs fit one 128-row pass.
    dense = (Cg < _SUBLANES or hdv != hd) and Cg * kvH <= 128
    page_bytes = bs * (D + Dv) * pool_k.dtype.itemsize + (2 * bs * kvH * 4 if quantized else 0)  # keys, values(, scales)
    if (not dense and C > 1
            and _query_side_bytes(kvH, _cdiv(Cg, _SUBLANES) * _SUBLANES, hd, q.dtype.itemsize)
            + 2 * page_bytes > _VMEM_BUDGET):
        # A row's query block is too large for VMEM beside one page a slot (a
        # 256-token chunk of 32 heads over 8 of head_dim 64 is [8, 1024, 64],
        # and 64 lanes pad to 128): the chunk's first and second half are two
        # calls, each over the row's pages up to its own last token.
        h = _cdiv(C, 2)
        lens = (None, None) if new_lens is None else (jnp.clip(new_lens, 0, h), jnp.clip(new_lens - h, 0, C - h))
        return jnp.concatenate([
            flash_decode_paged(q[:, at], pool_k, pool_v, block_tables, q_positions[:, at], bs, n,
                               pages_per_block, alibi_slopes, k_scale, v_scale,
                               None if first_live is None else first_live[:, at], sink)
            for at, n in zip((slice(0, h), slice(h, C)), lens)], axis=1)
    scale = jnp.asarray(hd ** -0.5, q.dtype)
    qg = (q * scale).reshape(N, C, kvH, G, hd)
    qpos_rows = jnp.broadcast_to(q_positions[:, :, None], (N, C, G)).reshape(N, Cg)
    srows = None
    if alibi:
        # row-aligned slopes: row (c, g) of kv head kh uses slope[kh*G + g]
        srows = jnp.broadcast_to(
            alibi_slopes.astype(jnp.float32).reshape(kvH, 1, G), (kvH, C, G)).reshape(kvH, Cg)
    if sink is not None:  # a head's sink, row-aligned as the slopes are
        sink_rows = jnp.broadcast_to(sink.astype(jnp.float32).reshape(kvH, 1, G), (kvH, C, G)).reshape(kvH, Cg)
    if dense:
        # [N, Cg, kvH*hd]: rows are (c, g) pairs, lanes (kv head, dim): for
        # G == 1 the query as it comes
        rows = _cdiv(Cg * kvH, _SUBLANES) * _SUBLANES  # (r, kh) pairs, padded to sublanes
        q_op = qg.transpose(0, 1, 3, 2, 4).reshape(N, Cg, D)
        q_block, o_block, heads = (1, Cg, D), (1, Cg, Dv), 1
        qpos_rows = jnp.repeat(qpos_rows, kvH, axis=1)  # row r*kvH + kh
        if alibi:
            srows = srows.T.reshape(1, Cg * kvH)
        if sink is not None:
            sink_rows = sink_rows.T.reshape(1, Cg * kvH)
    else:
        # [N, kvH, Cg, hd]: rows are (c, g) pairs, padded to sublanes
        rows = _cdiv(Cg, _SUBLANES) * _SUBLANES
        q_op = qg.transpose(0, 2, 1, 3, 4).reshape(N, kvH, Cg, hd)
        q_op = jnp.pad(q_op, ((0, 0), (0, 0), (0, rows - Cg), (0, 0)))
        q_block, o_block, heads = (1, kvH, rows, hd), (1, kvH, rows, hdv), kvH
    pad = rows - qpos_rows.shape[1]
    # padded rows see nothing (position -1 masks every token)
    qpos_rows = jnp.pad(qpos_rows, ((0, 0), (0, pad)), constant_values=-1)
    banded = first_live is not None
    if banded:  # laid out as the positions are: a row a (query, group member[, kv head]) pair
        qlow_rows = jnp.broadcast_to(first_live[:, :, None], (N, C, G)).reshape(N, Cg)
        qlow_rows = jnp.pad(jnp.repeat(qlow_rows, kvH, axis=1) if dense else qlow_rows, ((0, 0), (0, pad)))
    zeros = (0,) * (len(q_block) - 1)

    # a row's context, in tokens: positions are ascending within the live prefix
    if new_lens is None:
        max_pos = jnp.max(q_positions, axis=1)
    else:
        last = jnp.maximum(new_lens - 1, 0)
        max_pos = jnp.take_along_axis(q_positions, last[:, None], axis=1)[:, 0]
    ctx_lens = (max_pos + 1).astype(jnp.int32)  # [N]

    # The page-chunk (``_pages_a_chunk``): fewer pages where the query side (its
    # block and the output's, double-buffered by the pipeline; the fp32
    # accumulator; the statistics, which pad to 128 lanes) leaves less of the
    # budget for the two slots of K and of V.
    query_side = _query_side_bytes(heads, rows, max(D, Dv) // heads, q.dtype.itemsize)
    ppcb = _pages_a_chunk(page_bytes, P, query_side, pages_per_block)
    T = ppcb * bs

    operands = [q_op, qpos_rows[:, :, None]]
    in_specs = [
        pl.BlockSpec(q_block, lambda n, *_: (n,) + zeros),
        # per-row scalars ride as [.., rows, 1] COLUMNS: a (1, rows) row block
        # breaks Mosaic's (8, 128) block rule; (rows, 1) has rows % 8 == 0 and
        # a full last dim, and is already the broadcast shape the mask needs
        pl.BlockSpec((1, rows, 1), lambda n, *_: (n, 0, 0)),
    ]
    if banded:
        operands.append(qlow_rows[:, :, None])
        in_specs.append(pl.BlockSpec((1, rows, 1), lambda n, *_: (n, 0, 0)))
    if alibi:
        srows = jnp.pad(srows, ((0, 0), (0, rows - srows.shape[1])))
        operands.append(srows[:, :, None])
        in_specs.append(pl.BlockSpec((heads, rows, 1), lambda n, *_: (0, 0, 0)))
    if sink is not None:
        sink_rows = jnp.pad(sink_rows, ((0, 0), (0, rows - sink_rows.shape[1])))
        operands.append(sink_rows[:, :, None])
        in_specs.append(pl.BlockSpec((heads, rows, 1), lambda n, *_: (0, 0, 0)))
    operands += [pool_k, pool_v]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
    scratch = [
        pltpu.VMEM((2, ppcb, bs, D), pool_k.dtype),  # two slots: one computes, one fills
        pltpu.VMEM((2, ppcb, bs, Dv), pool_v.dtype),
    ]
    if quantized:
        # scales are 1/hd of the pool: gather the rows' pages in XLA into
        # [N, chunks, kvH, T] ROWS (slot index == position); the kernel fetches
        # a chunk's [kvH, T] beside its pages and multiplies the scores /
        # probabilities by them, never the value tiles
        # (the rows padded to whole sublane tiles: a chunk's [kvH, T] slab of 12 heads is no aligned slice of
        # the operand once T passes a lane tile)
        npc = _cdiv(P, ppcb)
        bt_pad = jnp.pad(block_tables, ((0, 0), (0, npc * ppcb - P)))
        heads_pad = _cdiv(kvH, _SUBLANES) * _SUBLANES - kvH
        for sc in (k_scale, v_scale):
            scale_rows = sc[bt_pad].reshape(N, npc, T, kvH).transpose(0, 1, 3, 2)
            operands.append(jnp.pad(scale_rows, ((0, 0), (0, 0), (0, heads_pad), (0, 0))))
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            scratch.append(pltpu.VMEM((2, kvH + heads_pad, T), jnp.float32))

    kernel = functools.partial(_decode_kernel, ppcb=ppcb, P=P, bs=bs, kvH=kvH, hd=hd, Cg=Cg,
                               dense=dense, alibi=alibi, quantized=quantized, banded=banded, hdv=hdv,
                               sink=sink is not None)
    out = pl.pallas_call(
        kernel,
        name="swa_paged_attn" if banded else "paged_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 if banded else 2,  # block_tables, ctx_lens(, a row's first live slot)
            grid=(N,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(o_block, lambda n, *_: (n,) + zeros),
            scratch_shapes=scratch + [
                pltpu.VMEM((heads, rows, Dv // heads), jnp.float32),
                pltpu.VMEM((heads, rows, _SUBLANES), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 4 if quantized else 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q_op.shape[:-1] + o_block[-1:], q.dtype),
        # rows in order: each starts the next one's first fetch. No bounds check on the pages' copies (four halts
        # a page, more scalar code than the copies themselves): the pool's edge is the tables' to keep (module
        # docstring)
        compiler_params=tpu_compiler_params(dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        interpret=_interpret(),
    )(block_tables.reshape(N * P), ctx_lens, *((jnp.min(first_live, axis=1).astype(jnp.int32),) if banded else ()), *operands)

    if dense:
        out = out.reshape(N, C, G, kvH, hdv).transpose(0, 1, 3, 2, 4)
    else:
        out = out[:, :, :Cg].reshape(N, kvH, C, G, hdv).transpose(0, 2, 1, 3, 4)
    return out.reshape(N, C, H, hdv)


# --------------------------------------------------------------- latent pages
#
# Latent attention in its absorbed form (``inference/paged.py``): a token's
# cache row is ONE slab shared by every head, ``[latent | rotary key | pad]``;
# a head's key is the whole slab and its value the slab's first ``v_width``
# columns. So a page is fetched ONCE for all heads, into one buffer that the
# score product reads whole and the value product reads the front of: this is
# multi-query attention whose values alias its keys. The walk over a row's
# live pages and the two-slot prefetch are the kernel's above; what differs is
# the one buffer, and the query side: all heads of a few query tokens are the
# rows of one product, and a chunk of a prompt is cut into tiles of ``tq``
# tokens, each a grid step with its own context length (what the tile's last
# live token may see), because ``C * H`` rows of a 256-token chunk do not fit
# VMEM. A row's later tiles fetch its pages again: at 1.25 KB a token that is
# noise beside a prompt's other work.
#
# The tile and the page-chunk come from the call's shapes alone
# (``_latent_form``). One token or a few (decode, a token and its drafts:
# ``C`` under ``_LATENT_Q_TILE``) are one tile against chunks of
# ``LATENT_PAGES_PER_BLOCK`` pages. A prompt takes the largest tile and then
# the widest chunk that fit ``_LATENT_VMEM_BUDGET``: what a chunk-step costs
# beside its two products (the page DMAs' scalar loops, the keys pushed to the
# MXU as weights, the accumulator read, scaled and written) is then spread
# over more scores, and a row's pages are fetched again by fewer tiles. The
# kernel's body is the same at every shape.
#
# Under a per-query mask (an indexer's choice; ``dsa_paged_attn``) the walk
# and the softmax are the same code (``_latent_walk``) and the step is its
# own: its rows go head by head, so that the mask's tile, one row a TOKEN,
# lies over every head's block of scores as it is, and it makes no causal
# compare, which the mask carries. Its tile and chunk come from
# ``_masked_latent_form``, under a count of its own bytes.
_LATENT_Q_TILE = 16
LATENT_PAGES_PER_BLOCK = 16
# Mosaic's default scope of VMEM, which a prompt's grid step has to fit by
# ``_latent_vmem_bytes``' count. The kernel asks for no limit of its own: the
# chip's 128 MiB are shared, and what a kernel scopes past the default the
# compiler takes from the arrays it keeps in fast memory for the WHOLE program.
# At 48 MiB here the xing prefill's dispatch gather lost its 112 MiB source's
# place there and went from 0.72 to 3.55 ms a layer-call, most of what the
# kernel had gained (PERF.md, PR 52).
_LATENT_VMEM_BUDGET = 16 << 20


def _latent_walk(bt_ref, ctx_ref, k_hbm, kbuf, acc_ref, m_ref, l_ref, sems, slot_ref, *,
                 ppcb, bs, tiles, v_width, cdt, step):
    """A grid step of the latent kernels, all but the scores: the walk over the
    page-chunks the step's queries may see (two slots, the next chunk in
    flight, the dead slots of a last chunk zeroed) and the online softmax over
    what ``step()``'s ``scores(c, k)`` gives for chunk ``c``, float32
    ``[rows, T]`` with ``_NEG_INF`` where a row does not attend (``step`` is
    called once, after the scratch is reset: there the causal step loads its
    query). Returns the normalised output, float32 ``[rows, v_width]``; which
    query a row is, is the caller's."""
    i = pl.program_id(0)
    steps = pl.num_programs(0)
    T = ppcb * bs
    W = kbuf.shape[-1]

    def pages_of(step):  # the pages a grid step's queries may see
        return _cdiv(ctx_ref[step], bs)

    def chunk_dma(step, chunk, slot, start):
        """Start, or wait for, the copies of one page-chunk of ``step``'s
        sequence into ``slot``: one DMA per LIVE page, a contiguous lane-dense
        ``[bs, W]`` slab of the pool as it is stored."""
        live = jnp.clip(pages_of(step) - chunk * ppcb, 0, ppcb)

        def page(j):
            p = bt_ref[step // tiles, chunk * ppcb + j]
            c = pltpu.make_async_copy(k_hbm.at[p], kbuf.at[slot, j], sems.at[slot])
            c.start() if start else c.wait()

        pl.loop(0, live)(page)

    ctx = ctx_ref[i]
    nc = _cdiv(pages_of(i), ppcb)
    slot0 = jnp.where(i == 0, 0, slot_ref[0])

    @pl.when(i == 0)
    def _first():
        chunk_dma(0, 0, 0, start=True)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    scores = step()

    def compute(c, slot):
        # past the context the scores are masked, but 0 * NaN is NaN in p @ v:
        # zero the dead slots of the last page and the pages never fetched
        @pl.when(c == nc - 1)
        def _():
            @pl.loop((ctx - c * T) // bs, ppcb)
            def _(j):
                pos = c * T + j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, W), 0)
                kbuf[slot, j] = jnp.where(pos < ctx, kbuf[slot, j], jnp.zeros((), kbuf.dtype))

        k = kbuf[slot].reshape(T, W).astype(cdt)
        s = scores(c, k)
        m_prev = m_ref[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_cur == _NEG_INF, 0.0, m_cur)
        p = jnp.exp(s - m_safe)
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_ref[...] = jnp.broadcast_to(alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
                                      l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(cdt), k[:, :v_width], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.loop(0, nc)
    def _walk(c):
        slot = (slot0 + c) % 2
        # the next chunk to fetch: this step's, or at its last the next step's first
        more = c + 1 < nc
        nstep = jnp.where(more, i, i + 1)

        @pl.when(nstep < steps)
        def _():
            chunk_dma(nstep, jnp.where(more, c + 1, 0), 1 - slot, start=True)

        chunk_dma(i, c, slot, start=False)
        compute(c, slot)

    # a step with nothing to see fetches nothing; it still owes the next its first chunk
    @pl.when((nc == 0) & (i + 1 < steps))
    def _():
        chunk_dma(i + 1, 0, slot0, start=True)

    slot_ref[0] = (slot0 + nc) % 2
    l = l_ref[:, :1]
    return acc_ref[...] / jnp.where(l == 0.0, 1.0, l)


def _latent_kernel(bt_ref, ctx_ref, q_ref, qpos_ref, k_hbm, o_ref, *scratch, **form):
    """The causal step: rows token by token (row ``t * H + h``), each against
    every position at or before its own (``qpos_ref`` [1, rows, 1]; -1 on a
    padded row)."""

    def step():
        q = q_ref[0]  # [rows, W], pre-scaled; held across the walk
        rows = q.shape[0]

        def scores(c, k):
            T = k.shape[0]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)  # [rows, T]
            j = c * T + jax.lax.broadcasted_iota(jnp.int32, (rows, T), 1)
            return jnp.where(j <= qpos_ref[0], s, _NEG_INF)

        return scores

    out = _latent_walk(bt_ref, ctx_ref, k_hbm, *scratch, cdt=q_ref.dtype, step=step, **form)
    o_ref[0] = out.astype(o_ref.dtype)


def _masked_latent_kernel(bt_ref, ctx_ref, q_ref, mask_ref, k_hbm, o_ref, *scratch, **form):
    """The step under a per-query mask (an indexer's choice): rows HEAD BY HEAD
    (row ``h * tq + t``; ``q_ref`` [1, H, tq, W]), so the scores are ``H``
    blocks of ``[tq, T]`` and the mask's tile ``[tq, T]`` (0/1, one row a TOKEN)
    lies over each as it is, a bias broadcast over the leading dimension. No
    causal compare: the mask marks no position past its query's own. The query
    is read from its block every chunk, not held across the walk: the room
    that frees is the chunk's."""
    H, tq, W = q_ref.shape[1:]

    def scores(c, k):
        T = k.shape[0]
        s = jax.lax.dot_general(q_ref[0].reshape(H * tq, W), k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [H * tq, T]
        kept = mask_ref[0, :, pl.ds(pl.multiple_of(c * T, T), T)]  # [tq, T]
        bias = (1.0 - kept.astype(jnp.float32)) * _NEG_INF  # (a score + _NEG_INF rounds to _NEG_INF)
        return (s.reshape(H, tq, T) + bias[None]).reshape(H * tq, T)

    out = _latent_walk(bt_ref, ctx_ref, k_hbm, *scratch, cdt=q_ref.dtype, step=lambda: scores, **form)
    o_ref[0] = out.astype(o_ref.dtype).reshape(H, tq, out.shape[-1])


def _latent_context(q_positions, new_lens, tile: int):
    """``[N, C // tile]``: how many tokens each tile of queries may see, the
    position after its last LIVE token's; 0 for a tile with none."""
    N, C = q_positions.shape
    live = jnp.ones((N, C), bool) if new_lens is None else jnp.arange(C)[None, :] < new_lens[:, None]
    seen = jnp.where(live, q_positions + 1, 0).reshape(N, C // tile, tile)
    return seen.max(axis=-1).astype(jnp.int32)


def _whole_tiles(q, q_positions, new_lens, Cp: int):
    """A call's queries [N, C, H, W], positions and live counts with the queries
    padded to ``Cp``, whole tiles: a padded query stands at position -1 and is
    not live."""
    N, C = q_positions.shape
    if Cp != C:
        q = jnp.pad(q, ((0, 0), (0, Cp - C), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, Cp - C)), constant_values=-1)
        if new_lens is None:
            new_lens = jnp.full((N,), C, jnp.int32)
    return q, q_positions, new_lens


def _latent_scratch(rows: int, ppcb: int, bs: int, W: int, v_width: int, pool_dtype) -> list:
    """``_latent_walk``'s scratch: the pages' two slots (one computes, one
    fills), the accumulator, the running max and sum, the slots' semaphores
    and where the next step finds its first chunk."""
    return [
        pltpu.VMEM((2, ppcb, bs, W), pool_dtype),
        pltpu.VMEM((rows, v_width), jnp.float32),
        pltpu.VMEM((rows, 128), jnp.float32),
        pltpu.VMEM((rows, 128), jnp.float32),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SMEM((1,), jnp.int32),
    ]


def _latent_vmem_bytes(rows: int, T: int, W: int, v_width: int, itemsize: int) -> int:
    """VMEM of a grid step of ``rows`` query rows against chunks of ``T``
    tokens, as the chip's compiler scopes it: the blocks of q, of the positions
    and of the output, each double-buffered by the pipeline; the fp32
    accumulator and the two statistics; q held across the walk, a chunk's
    value product before it is added, and two columns of the update's
    statistics (a column pads to 128 lanes); the two slots of pages; and ONE
    float32 copy of a chunk-step's scores (the compiler reuses it). Fitted to
    the compiler's own verdicts at 32 heads over tiles of 16 to 56 tokens and
    chunks of 8 to 64 pages, all of which it reproduces
    (``tests/unit/ops/test_chip_compile.py`` holds the ones at its edge)."""
    column = 128 * 4
    blocks = 2 * (W * itemsize + column + v_width * itemsize)
    scratch = v_width * 4 + 2 * column
    held = W * itemsize + v_width * 4 + 2 * column
    return rows * (blocks + scratch + held) + 2 * T * W * itemsize + rows * T * 4


def _latent_form(C: int, H: int, W: int, v_width: int, itemsize: int, P: int, bs: int) -> tuple[int, int]:
    """(query tokens a tile, pages a chunk) from the call's shapes (the comment
    above): at 32 heads and 2,048 tokens a row, tiles of 32 tokens (1,024 rows)
    and chunks of 32 pages; at 20 heads and 256 tokens, tiles of 32 (640 rows),
    chunks of 16."""
    ppcb = max(1, min(LATENT_PAGES_PER_BLOCK, P))
    if C < _LATENT_Q_TILE:  # decode, a token and its drafts
        return C, ppcb

    def fits(tq, ppcb):
        return _latent_vmem_bytes(tq * H, ppcb * bs, W, v_width, itemsize) <= _LATENT_VMEM_BUDGET

    # the most tokens a tile, by doubling, of the call's own: the fixed cost of a chunk-step is
    # paid a tile, and a row's pages are fetched again a tile
    tq = _LATENT_Q_TILE
    while 2 * tq <= C and fits(2 * tq, ppcb):
        tq *= 2
    # then the widest chunk, no wider than the call's tokens: a whole prompt sees no more
    # columns than it brings, and the last chunk's dead columns are multiplied all the same
    while 2 * ppcb <= P and 2 * ppcb * bs <= C and fits(tq, 2 * ppcb):
        ppcb *= 2
    return tq, ppcb


def _masked_latent_vmem_bytes(H: int, tq: int, T: int, W: int, v_width: int, itemsize: int, columns: int) -> int:
    """``_latent_vmem_bytes`` of the step under a mask, ``H * tq`` rows against
    chunks of ``T`` tokens under a mask ``columns`` wide: the blocks of q and of
    the output as there, no block of positions and no q held across the walk;
    the mask's tile, double-buffered; and what a chunk-step holds besides its
    float32 scores comes to a quarter more of them and three and a half
    columns a row. Fitted to the compiler's verdicts at 16 to 64 heads over
    tiles of 16 to 128 tokens and chunks of 8 to 256 pages of a row of 8,192
    tokens, 42 forms, all of which it reproduces
    (``tests/unit/ops/test_chip_compile.py`` holds the ones at its edge); a
    shorter row leaves the compiler a little more room, which this leaves
    unused."""
    rows, column = H * tq, 128 * 4
    blocks = 2 * (W * itemsize + v_width * itemsize)
    scratch = v_width * 4 + 2 * column
    held = 7 * column // 2
    return (rows * (blocks + scratch + held) + 2 * tq * columns * itemsize + 2 * T * W * itemsize
            + rows * T * 5)


def _masked_latent_form(C: int, H: int, W: int, v_width: int, itemsize: int, P: int, bs: int,
                        columns: int = 0) -> tuple[int, int]:
    """``_latent_form`` of the step under a mask ``columns`` wide: (query tokens
    a tile, a whole number of the mask's 16-row tiles; pages a chunk). At 64
    heads and 8,192 tokens a row, tiles of 16 tokens (1,024 rows) against
    chunks of 32 pages."""
    # (a chunk is whole 128-lane tiles of the mask's columns whatever the table holds)
    tq, ppcb = _LATENT_Q_TILE, LATENT_PAGES_PER_BLOCK

    def fits(tq, ppcb):
        reach = _cdiv(P, ppcb) * ppcb * bs  # every chunk the walk can reach: the mask is padded to it
        return _masked_latent_vmem_bytes(H, tq, ppcb * bs, W, v_width, itemsize,
                                         max(columns, reach)) <= _LATENT_VMEM_BUDGET

    while 2 * tq <= C and fits(2 * tq, ppcb):
        tq *= 2
    while 2 * ppcb <= P and 2 * ppcb * bs <= C and fits(tq, 2 * ppcb):
        ppcb *= 2
    return tq, ppcb


@register("latent_paged_attention", "pallas")
def flash_decode_latent(
    q: jax.Array,  # [N, C, H, W]: a head's query against the whole slab, NOT yet scaled
    pool: jax.Array,  # [pages, bs, W]: the whole latent pool, all layers
    block_tables: jax.Array,  # [N, P] int32 pages of pool (layer offset added)
    q_positions: jax.Array,  # [N, C] int32
    block_size: int,
    scale: float,
    v_width: int,  # a token's value: the slab's first v_width columns
    new_lens: jax.Array = None,  # [N] live tokens
    mask: jax.Array = None,  # [N, C, >= P*bs] bool: the positions a query attends (an indexer's choice)
) -> jax.Array:
    """-> [N, C, H, v_width]. One fetch of a page serves every head. Under
    ``mask`` a query attends the positions it marks and no others, and the mask
    marks none past the query's own (an indexer scores no later position:
    ``ops/dsa.py``): the same walk over the row's pages up to the tile's last
    query, the mask's tile fetched beside the queries' (``dsa_paged_attn``)."""
    N, C, H, W = q.shape
    bs = block_size
    if mask is not None:
        return _flash_decode_latent_masked(q, pool, block_tables, q_positions, bs, scale, v_width, new_lens, mask)
    tq, ppcb = _latent_form(C, H, W, v_width, q.dtype.itemsize, block_tables.shape[1], bs)
    Cp = _cdiv(C, tq) * tq
    tiles = Cp // tq
    rows = _cdiv(tq * H, 16) * 16  # whole sublane tiles of the 16-bit query
    q, q_positions, new_lens = _whole_tiles(q * jnp.asarray(scale, q.dtype), q_positions, new_lens, Cp)
    ctx = _latent_context(q_positions, new_lens, tq).reshape(N * tiles)
    q_op = q.reshape(N * tiles, tq * H, W)
    qpos = jnp.broadcast_to(q_positions.reshape(N * tiles, tq, 1), (N * tiles, tq, H))
    qpos = qpos.reshape(N * tiles, tq * H)
    pad = rows - tq * H
    q_op = jnp.pad(q_op, ((0, 0), (0, pad), (0, 0)))
    qpos = jnp.pad(qpos, ((0, 0), (0, pad)), constant_values=-1)  # padded rows see nothing

    out = pl.pallas_call(
        functools.partial(_latent_kernel, ppcb=ppcb, bs=bs, tiles=tiles, v_width=v_width),
        name="mla_paged_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_tables, the tiles' contexts
            grid=(N * tiles,),
            in_specs=[
                pl.BlockSpec((1, rows, W), lambda i, bt, cl: (i, 0, 0)),
                pl.BlockSpec((1, rows, 1), lambda i, bt, cl: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, rows, v_width), lambda i, bt, cl: (i, 0, 0)),
            scratch_shapes=_latent_scratch(rows, ppcb, bs, W, v_width, pool.dtype),
        ),
        out_shape=jax.ShapeDtypeStruct((N * tiles, rows, v_width), q.dtype),
        # steps in order: each starts the next one's first fetch
        compiler_params=tpu_compiler_params(dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(block_tables, ctx, q_op, qpos[:, :, None], pool)
    return out[:, : tq * H].reshape(N, Cp, H, v_width)[:, :C]


def _flash_decode_latent_masked(q, pool, block_tables, q_positions, bs, scale, v_width, new_lens, mask):
    """``flash_decode_latent`` under ``mask``: the kernel takes the queries head
    by head, ``[N, H, C, W]`` in blocks of ``(1, H, tq, W)``, and gives
    ``[N, H, C, v_width]`` back (the layouts the absorbed products around it
    have: both are batched over heads), so a step's rows are ``h * tq + t``
    and the mask's ``[tq, T]`` tile lies over every head's block of scores."""
    N, C, H, W = q.shape
    P = block_tables.shape[1]
    tq, ppcb = _masked_latent_form(C, H, W, v_width, q.dtype.itemsize, P, bs, mask.shape[-1])
    Cp = _cdiv(C, tq) * tq
    tiles = Cp // tq
    T = ppcb * bs
    columns = _cdiv(P, ppcb) * T  # every chunk the walk can reach
    q, q_positions, new_lens = _whole_tiles(q * jnp.asarray(scale, q.dtype), q_positions, new_lens, Cp)
    q = q.transpose(0, 2, 1, 3)
    mask = mask.astype(q.dtype)
    if mask.shape[-1] < columns or Cp != C:  # a padded query attends nothing
        mask = jnp.pad(mask, ((0, 0), (0, Cp - C), (0, max(columns - mask.shape[-1], 0))))
    ctx = _latent_context(q_positions, new_lens, tq).reshape(N * tiles)
    rows = H * tq
    tile = lambda i, bt, cl: (i // tiles, 0, i % tiles, 0)  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_masked_latent_kernel, ppcb=ppcb, bs=bs, tiles=tiles, v_width=v_width),
        name="dsa_paged_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_tables, the tiles' contexts
            grid=(N * tiles,),
            in_specs=[
                pl.BlockSpec((1, H, tq, W), tile),
                pl.BlockSpec((1, tq, mask.shape[-1]), lambda i, bt, cl: (i // tiles, i % tiles, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, tq, v_width), tile),
            scratch_shapes=_latent_scratch(rows, ppcb, bs, W, v_width, pool.dtype),
        ),
        out_shape=jax.ShapeDtypeStruct((N, H, Cp, v_width), q.dtype),
        # steps in order: each starts the next one's first fetch
        compiler_params=tpu_compiler_params(dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(block_tables, ctx, q, mask, pool)
    return out[:, :, :C].transpose(0, 2, 1, 3)
