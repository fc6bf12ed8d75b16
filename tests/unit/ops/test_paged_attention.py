"""Pallas paged flash-decode kernel vs the dense-gather XLA fallback
(reference inference/v2/kernels/ragged_ops/blocked_flash/)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.registry import dispatch
import deepspeed_tpu.ops.pallas.paged_attention as pa
import deepspeed_tpu.inference.paged  # noqa: F401  (registers the xla impl)


PAGES = 64  # pages of one layer's pool; the pool holds LAYERS of them
LAYERS = 3


def _setup(N=3, C=4, H=8, kvH=2, hd=32, P=6, bs=16, seed=0, layer=0):
    """The pool as ``inference/paged.py`` stores it and hands it to either
    implementation: every layer's pages in ONE rank-3 ``[L*pages, bs, kvH*hd]``
    array, and a block table that already points at ``layer``'s pages in it."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (N, C, H, hd), jnp.float32)
    pool_k = jax.random.normal(ks[1], (LAYERS * PAGES, bs, kvH * hd), jnp.float32)
    pool_v = jax.random.normal(ks[2], (LAYERS * PAGES, bs, kvH * hd), jnp.float32)
    # distinct random pages per row
    bt = jax.random.permutation(ks[3], PAGES)[: N * P].reshape(N, P).astype(jnp.int32)
    bt = bt + layer * PAGES
    # rows with different live lengths: row n ends at position end_n
    ends = jnp.asarray([5, 37, 90])[:N]
    positions = jnp.stack([jnp.arange(C) + e - C + 1 for e in ends]).astype(jnp.int32)
    new_lens = jnp.full((N,), C, jnp.int32)
    return q, pool_k, pool_v, bt, positions, new_lens, bs


def _plain_reference(q, pool_k, pool_v, bt, pos, bs, slopes=None):
    """Token by token in numpy, from the layout's definition alone: position j
    of row n lives in page ``bt[n, j // bs]`` at slot ``j % bs``, head ``kh``
    in lanes ``[kh*hd, (kh+1)*hd)`` of that slot's row."""
    q, pool_k, pool_v, bt, pos = map(np.asarray, (q, pool_k, pool_v, bt, pos))
    N, C, H, hd = q.shape
    kvH = pool_k.shape[-1] // hd
    G, hdv = H // kvH, pool_v.shape[-1] // kvH  # (a value may be narrower than its key)
    out = np.zeros((N, C, H, hdv), q.dtype)
    for n in range(N):
        for c in range(C):
            js = np.arange(pos[n, c] + 1)
            for h in range(H):
                k = pool_k[bt[n, js // bs], js % bs, (h // G) * hd:(h // G + 1) * hd]
                v = pool_v[bt[n, js // bs], js % bs, (h // G) * hdv:(h // G + 1) * hdv]
                s = k @ q[n, c, h] / np.sqrt(hd)
                if slopes is not None:
                    s = s + np.asarray(slopes)[h] * js
                w = np.exp(s - s.max())
                out[n, c, h] = (w / w.sum()) @ v
    return out


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("kvH,alibi", [(8, False), (2, False), (2, True), (8, True)],
                         ids=["mha", "gqa", "gqa-alibi", "mha-alibi"])
def test_whole_pool_with_layer_offset_matches_plain_reference(impl, kvH, alibi):
    """Both implementations read a layer's pages out of the whole rank-3 pool
    through an offset block table, and agree with a reference that knows only
    where the layout says a token's keys and values are."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    q, pk, pv, bt, pos, lens, bs = _setup(H=8, kvH=kvH, hd=16, layer=2)
    assert int(bt.min()) >= 2 * PAGES  # nothing of layer 0 or 1 is addressed
    slopes = alibi_slopes(8) if alibi else None
    got = dispatch("paged_attention", impl)(q, pk, pv, bt, pos, bs, new_lens=lens,
                                            alibi_slopes=slopes)
    want = _plain_reference(q, pk, pv, bt, pos, bs, slopes)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("ppcb", [1, 2, 8])
def test_paged_pallas_matches_xla(ppcb, layer):
    q, pk, pv, bt, pos, lens, bs = _setup(layer=layer)
    xla = dispatch("paged_attention", "xla")
    pallas = dispatch("paged_attention", "pallas")
    want = xla(q, pk, pv, bt, pos, bs)
    got = pallas(q, pk, pv, bt, pos, bs, new_lens=lens, pages_per_block=ppcb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_paged_pallas_decode_single_token():
    q, pk, pv, bt, pos, lens, bs = _setup(C=1, layer=1)
    xla = dispatch("paged_attention", "xla")
    pallas = dispatch("paged_attention", "pallas")
    want = xla(q, pk, pv, bt, pos, bs)
    got = pallas(q, pk, pv, bt, pos, bs, new_lens=lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 1])
def test_paged_pallas_gqa_grouping(layer):
    q, pk, pv, bt, pos, lens, bs = _setup(H=8, kvH=4, hd=16, layer=layer)
    want = dispatch("paged_attention", "xla")(q, pk, pv, bt, pos, bs)
    got = dispatch("paged_attention", "pallas")(q, pk, pv, bt, pos, bs, new_lens=lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_ragged_forward_uses_kernel_consistently():
    """v2 ragged_forward parity between forced impls (engine path sanity)."""
    from deepspeed_tpu.inference.cache import Pools, init_pool
    from deepspeed_tpu.inference.paged import ragged_forward
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, hidden_size=32, intermediate_size=48,
                            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=64,
                            dtype=jnp.float32)
    module = CausalLM(cfg)
    batch = {"input_ids": jnp.zeros((1, 8), jnp.int32)}
    params = module.init({"params": jax.random.PRNGKey(0)}, batch, train=False)["params"]
    pool = Pools(init_pool(cfg, num_blocks=8, block_size=16, dtype=jnp.float32))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 8)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(8), (2, 8)).astype(jnp.int32)
    new_lens = jnp.asarray([8, 5], jnp.int32)
    bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)

    logits, _ = ragged_forward(params, cfg, pool, tokens, positions, new_lens, bt, 16)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("kvH,ppcb", [(2, 8), (8, 8), (2, 2)])  # GQA/MHA + multi-chunk
def test_paged_pallas_alibi_matches_xla(kvH, ppcb):
    """ALiBi fused into the decode kernel (slope * key-position on the
    existing position iota) — bloom keeps the Pallas fast path."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    q, pk, pv, bt, pos, lens, bs = _setup(H=8, kvH=kvH, hd=16, layer=1)
    slopes = alibi_slopes(8)
    xla = dispatch("paged_attention", "xla")
    pallas = dispatch("paged_attention", "pallas")
    want = xla(q, pk, pv, bt, pos, bs, alibi_slopes=slopes)
    got = pallas(q, pk, pv, bt, pos, bs, new_lens=lens, alibi_slopes=slopes,
                 pages_per_block=ppcb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# The walk over a row's live pages (ISSUE 30). Everything no row may read is
# NaN: every page no row owns (the table's dead entries point at one), and the
# dead slots of every row's last page. The XLA fallback gathers those and is
# not proof against them (0 * NaN in p @ v), so the plain reference, which
# reads live positions only, is what the kernel is held to.


def _planted(ends, C=1, H=8, kvH=8, hd=16, bs=16, layer=1, wide=1, quant=None, seed=0, hdv=None, PAGES=PAGES):
    """Rows that end at positions ``ends`` (-1: a row with no page), their
    pages scattered over ``layer``'s share of the whole pool (``PAGES`` a
    layer), the table ``wide`` times as wide as the longest row needs, a value
    ``hdv`` wide (None: as its key). Returns the kernel's arguments, its
    keywords, and the pool in floats for the plain reference."""
    from deepspeed_tpu.inference.paged import _kv_block_quant

    rng = np.random.default_rng(seed)
    N = len(ends)
    ends = np.asarray(ends)
    need = np.maximum(ends, 0) // bs + 1
    P = int(need.max()) * wide
    free = rng.permutation(PAGES) + layer * PAGES
    dead, free = free[0], free[1:]
    bt = np.full((N, P), dead, np.int32)
    live = np.zeros((LAYERS * PAGES, bs), bool)
    for n, e in enumerate(ends):
        bt[n, :need[n]], free = free[:need[n]], free[need[n]:]
        js = np.arange(e + 1)
        live[bt[n, js // bs], js % bs] = True
    q = jnp.asarray(rng.standard_normal((N, C, H, hd)), jnp.float32)
    pos = np.stack([np.arange(C) + e - C + 1 for e in ends]).astype(np.int32)
    pos = np.where(ends[:, None] < 0, -1, pos)
    kw, pools, floats = {"new_lens": jnp.full((N,), C, jnp.int32)}, [], []
    for name, hd in (("k_scale", hd), ("v_scale", hdv or hd)):
        D = kvH * hd
        x = jnp.asarray(rng.standard_normal((LAYERS * PAGES * bs, kvH, hd)), jnp.float32)
        if quant is None:
            values = np.asarray(x).reshape(-1, bs, D)
            floats.append(values)
            pools.append(jnp.asarray(np.where(live[:, :, None], values, np.nan)))
            continue
        vq, sc = _kv_block_quant(x, quant)
        vq, sc = np.asarray(vq.astype(jnp.float32)), np.asarray(sc)
        floats.append((vq.reshape(-1, kvH, hd) * sc[:, :, None]).reshape(-1, bs, D))
        # an int8 cannot be NaN: its dead slots hold the largest value there is
        vq = np.where(live.reshape(-1, 1), vq, np.nan if quant == "fp8" else 127.0)
        pools.append(jnp.asarray(vq.reshape(-1, bs, D)).astype(
            jnp.float8_e4m3fn if quant == "fp8" else jnp.int8))
        kw[name] = jnp.asarray(np.where(live.reshape(-1, 1), sc, np.nan).reshape(-1, bs * kvH))
    return (q, *pools, jnp.asarray(bt), jnp.asarray(pos), bs), kw, floats


def _held_to_plain_reference(args, kw, floats, slopes=None, **more):
    q, _, _, bt, pos, bs = args
    got = np.asarray(dispatch("paged_attention", "pallas")(*args, alibi_slopes=slopes, **kw, **more))
    rows = np.asarray(pos)[:, -1] >= 0
    assert not got[~rows].any()  # a row with no page writes zeros
    want = _plain_reference(q[rows], *floats, bt[rows], pos[rows], bs, slopes)
    np.testing.assert_allclose(got[rows], want, rtol=2e-5, atol=2e-5)
    return got


@pytest.mark.parametrize("ppcb", [1, 2, 8, None])
def test_lengths_at_every_edge(ppcb):
    """No page, one token, exactly a page, one over, exactly a chunk, one
    over, two chunks, one over: side by side, so each row's first fetch is
    started by the row before it. Told no chunk the kernel takes the rule's:
    at 16 KiB a page the most it takes, 32 pages."""
    bs = 16
    T = bs * (ppcb or pa._MAX_PAGES_PER_BLOCK)
    ends = [-1, 0, bs - 1, bs, T - 1, T, 2 * T - 1, 2 * T, -1, 1]
    args, kw, floats = _planted(ends, PAGES=4 * PAGES)
    assert _chunk_of(args, kw, pages_per_block=ppcb) == T // bs
    _held_to_plain_reference(args, kw, floats, pages_per_block=ppcb)


def _tool(name):
    """``tools/<name>.py`` as a module."""
    import importlib.util
    import os
    import sys

    spec = importlib.util.spec_from_file_location(name, os.path.join(
        os.path.dirname(__file__), "..", "..", "..", "tools", name + ".py"))
    tool = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = tool  # (a dataclass looks its module up)
    spec.loader.exec_module(tool)
    return tool


def _bench_tool():
    return _tool("paged_kernel_bench")


def _chunk_of(args, kw, **more) -> int:
    """The pages a chunk of the kernel's call, as the bench tool reads them off its K slots ``[2, pages, bs, D]``."""
    return _bench_tool().chunk_of(lambda *a: dispatch("paged_attention", "pallas")(*a, args[-1], **kw, **more), *args[:-1])


@pytest.mark.parametrize("name,pages", [("pythia", 8), ("eva", 8), ("command-a-plus", 16), ("mimo-global", 24),
                                        ("qwen3-next", 32), ("mimo-ring", 9)])
def test_a_chunk_is_sized_by_its_bytes_at_the_six_geometries_the_cells_run(name, pages):
    """``_pages_a_chunk`` on ``tools/paged_kernel_bench.py``'s geometries: about 1 MiB of keys and values, never
    under 8 pages, whole eights under a wider table, and the ring's 9 columns one chunk."""
    g = _bench_tool().GEOMETRIES[name]
    assert pa._pages_a_chunk(g.page_bytes, g.columns, 1 << 20) == pages


KIB = 1 << 10


@pytest.mark.parametrize("page,columns,query_side,told,pages", [
    (32 * KIB, 5, 0, None, 5), (40 * KIB, 25, 0, None, 25), (40 * KIB, 26, 0, None, 24), (512 * KIB, 160, 0, None, 8),
    (4 * KIB, 1024, 0, None, 32), (128 * KIB, 128, 9 << 20, None, 8), (128 * KIB, 128, 19 << 19, None, 4),
    (32 * KIB, 64, 43 << 18, None, 4), (40 * KIB, 194, 0, 2, 2), (40 * KIB, 5, 0, 8, 5), (4 * KIB, 1024, 0, 64, 64),
    (128 * KIB, 128, 19 << 19, 8, 4), (128 * KIB, 128, 11 << 20, 8, 1),
], ids=["a-table-narrower-than-the-rule-is-one-chunk", "a-table-as-wide-as-the-rule", "one-column-wider-whole-eights",
        "never-fewer-than-eight", "never-more-than-the-cap", "the-budget-leaves-it", "the-budget-halves-it",
        "the-budget-halves-it-three-times", "told-two", "told-more-than-the-table", "told-past-the-cap",
        "told-eight-and-halved", "nothing-left-is-one-page"])
def test_the_chunk_s_rule_at_its_edges(page, columns, query_side, told, pages):
    assert pa._pages_a_chunk(page, columns, query_side, told) == pages


TWO_WIDTH = dict(H=4, kvH=2, hd=192, hdv=128, PAGES=2 * PAGES)  # MiMo's global kind at half its heads: 40 KiB a page in float32


@pytest.mark.parametrize("ends", [(383, 400, 383), (384, 400, 384), (385, 400, 385), (15, 400, 15), (-1, 400, -1),
                                  (-1, 383, 0, 384, 15, 385, -1, 400)],
                         ids=["T-1", "T", "T+1", "one-page", "none", "side-by-side"])
def test_the_default_chunk_at_both_sides_of_its_edge_for_a_key_wider_than_its_value(ends):
    """Keys of 192 columns beside values of 128 under the chunk the rule gives (24 pages, ``T`` = 384): a row one
    token short of a chunk, a whole chunk, one over (a second chunk of one page, 23 never fetched), one page,
    none, each before and after a row of two chunks. NaN lies in every dead slot of a last page and in every page of a slot that was not fetched; held to
    the layout's definition and to the XLA fallback on the pool without the NaN."""
    args, kw, floats = _planted(list(ends), wide=2, **TWO_WIDTH)
    assert _chunk_of(args, kw) == 24
    got = _held_to_plain_reference(args, kw, floats)
    q, _, _, bt, pos, bs = args
    want = dispatch("paged_attention", "xla")(q, *map(jnp.asarray, floats), bt, pos, bs, **kw)
    rows = np.asarray(ends) >= 0
    np.testing.assert_allclose(got[rows], np.asarray(want)[rows], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("C,H,kvH,alibi,quant", [
    (1, 8, 8, False, None), (1, 8, 2, False, None), (4, 8, 8, False, None),
    (3, 8, 2, False, None), (32, 8, 8, False, None), (1, 8, 8, True, None),
    (1, 8, 2, True, None), (4, 8, 8, True, None), (1, 8, 8, False, "int8"),
    (1, 8, 2, False, "fp8"), (4, 8, 4, False, "int8"), (16, 8, 2, False, "fp8"),
], ids=["decode-mha", "decode-gqa", "drafts-mha", "drafts-gqa", "chunk-mha", "decode-mha-alibi",
        "decode-gqa-alibi", "drafts-mha-alibi", "decode-mha-int8", "decode-gqa-e4m3",
        "drafts-gqa-int8", "chunk-gqa-e4m3"])
def test_rows_of_very_different_lengths_side_by_side(C, H, kvH, alibi, quant):
    """Both forms of the compute (a product a kv head; one block-diagonal
    query for every head, where a head has fewer than 8 query rows), with
    scales and ALiBi, at a nonzero layer offset, under a table twice as wide
    as the longest row."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    ends = [C - 1, 250, C + 4, 129, 31]
    slopes = alibi_slopes(H) if alibi else None
    args, kw, floats = _planted(ends, C=C, H=H, kvH=kvH, layer=2, wide=2, quant=quant)
    _held_to_plain_reference(args, kw, floats, slopes)


@pytest.mark.parametrize("ppcb", [8, None], ids=["eight", "default"])
@pytest.mark.parametrize("C", [1, 4, 16])
def test_a_rows_output_does_not_depend_on_the_tables_width(C, ppcb):
    """Bit for bit, under any two tables wider than a chunk (told none, 32 pages here): the walk follows the row.
    A table NARROWER than the rule's chunk is one chunk of all its columns, another order of the same sums: held
    to the reference like the rest."""
    ends = [C + 40, 200, C - 1]
    narrow = _held_to_plain_reference(*_planted(ends, C=C, wide=3), pages_per_block=ppcb)
    wide = _held_to_plain_reference(*_planted(ends, C=C, wide=8), pages_per_block=ppcb)
    np.testing.assert_array_equal(narrow, wide)
    one_chunk, kw, floats = _planted(ends, C=C, wide=1)
    assert _chunk_of(one_chunk, kw, pages_per_block=ppcb) == (ppcb or one_chunk[3].shape[1])
    np.testing.assert_allclose(_held_to_plain_reference(one_chunk, kw, floats, pages_per_block=ppcb), wide,
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("C,kvH,quant", [(1, 8, None), (16, 2, None), (1, 2, "fp8")],
                         ids=["decode", "chunk", "decode-e4m3"])
def test_under_the_chips_own_rules_for_memory_and_dma(monkeypatch, C, kvH, quant):
    """Pallas' TPU interpreter, not the plain one: scratch memory starts as
    NaN (the plain one zeroes it), a DMA lands when it is waited for and not
    when it is started, and a read of a buffer that a copy still in flight
    writes is a race. So a slot read before its wait, or a page of a slot that
    was never fetched, shows."""
    from jax.experimental.pallas import tpu as pltpu

    import deepspeed_tpu.ops.pallas.paged_attention as pa

    monkeypatch.setattr(pa, "_interpret", lambda: pltpu.InterpretParams(
        detect_races=True, dma_execution_mode="on_wait", uninitialized_memory="nan"))
    ends = [C - 1, 250, -1, C + 4, 129]
    _held_to_plain_reference(*_planted(ends, C=C, kvH=kvH, layer=2, wide=2, quant=quant))
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as interpreter

    assert not interpreter.races.races_found


@pytest.mark.parametrize("name,chunk", [("pythia", 8), ("eva", 8), ("mimo-global", 24), ("mimo-ring", 9)])
def test_the_bench_tool_draws_a_cell_s_step_and_reads_the_chunk_at_a_toy_of_its_geometry(monkeypatch, name, chunk):
    """``tools/paged_kernel_bench.py::measure`` (a time comes only from a chip: here it is run for its draw, its
    keys and the chunk it reads off the kernel): the geometry at 4 rows and a few hundred keys, a page's bytes kept."""
    import dataclasses

    from benchmarks.lib import peaks

    tool = _bench_tool()
    g = tool.GEOMETRIES[name]
    toy = dataclasses.replace(g, rows=4, heads=g.heads // g.kv_heads * 2, kv_heads=2, key=g.key * g.kv_heads // 2,
                              value=g.value * g.kv_heads // 2, columns=min(g.columns, 40),
                              prompt=(200, 400) if not g.band else g.prompt, decoded=100, eva_window=g.eva_window and 256)
    assert toy.page_bytes == g.page_bytes
    monkeypatch.setitem(tool.GEOMETRIES, name, toy)
    monkeypatch.setitem(peaks.DEVICE_PEAKS, jax.devices()[0].device_kind, peaks.DEVICE_PEAKS["TPU v5 lite"])
    pos, low, keys, table = tool.draw(toy, 3)
    assert ((table != 0).sum(axis=1) == pos // tool.BS + 1).all() and len(set(table[table != 0])) == (table != 0).sum()
    assert (keys == (128 if g.band else pos + 1)).all() and (low > 0).any() == bool(g.band)
    line = tool.measure(pa.flash_decode_paged, name, seed=3, calls=2, repeats=1)
    assert line["pages_a_chunk"] == chunk and line["finite"] and line["mean_keys"] == keys.mean()
    assert line["page_kib"] == g.page_bytes / 1024 and 0 < line["roofline_pct"]
    assert tool.measure(pa.flash_decode_paged, name, seed=3, calls=1, repeats=1, pages_per_block=2)["pages_a_chunk"] == 2


def test_the_bundles_tool_cuts_a_dump_into_loop_bodies_and_counts_their_instructions():
    """``tools/kernel_bundles.py::regions`` on lines in the compiler's own form (the compile itself is the chip
    compiler's, in a subprocess, and no test's): a loop body starts a region, a branch's empty delay slots stay
    with it, and a bounds check's halts, the copies and the products are counted where they are."""
    tool = _tool("kernel_bundles")
    text = """= control target key start
LB: loop body
     0   :  { %s1 = smov 0  ;;  %15 = dma.vmem_to_smem %s2 }
   0x1   :  { %9 = dma.done.wait [#allocation8], 4112 }
   0x2 LB: > { %s3 = sadd.s32 1, %s1 }
   0x3   : > { %143 = sbr.rel (%p2) target bundleno = 9 (0x9), region = 28 }
   0x4   :  {}
   0x5   :  {}
   0x6 LB: >> { %7 = dma.hbm_to_vmem [thread:$0]  %s4, 768, %s5, %s6  ;;  %8 = shalt.err (%p9) }
   0x7   : >> { %10 = dma.hbm_to_vmem [thread:$0]  %s4, 512, %s5, %s6  ;;  %11 = shalt.err (%p9)  ;;  %12 = shalt.err (%p9) }
   0x8 LB: >> { %13 = dma.done.wait %s6, 768 }
   0x9   : > { %v1 = vmatpush.bf16.xpose.msra.mxu0 %v0  ;;  %v2 = vmatmul.bf16.gmra.mxu0 %v0  ;;  %v3 = vmatmul.bf16.gmra.mxu1 %v0 }
"""
    found = tool.regions(text)
    assert [(r["first"], r["depth"], r["bundles"], r["empty"]) for r in found] == [
        ("0", 0, 2, 0), ("0x2", 1, 4, 2), ("0x6", 2, 2, 0), ("0x8", 2, 1, 0), ("0x9", 1, 1, 0)]
    assert [(r["dma"], r["dma_wait"], r["shalt_err"], r["vmatpush"], r["vmatmul"]) for r in found] == [
        (1, 1, 0, 0, 0), (0, 0, 0, 0, 0), (2, 0, 3, 0, 0), (0, 1, 0, 0, 0), (0, 0, 0, 1, 2)]
