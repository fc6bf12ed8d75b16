"""One KV pool, stored page-major and updated in place (ISSUE 26).

  1. census — in the three serving programs (``step``, ``chain``, the
     speculative chain) and for every pool dtype, nothing as large as one
     layer of the pool is produced except the pool itself, carried through:
     scattered into, and re-shaped by its leading dims alone. The compiled
     program hands the donated pool back as its output.
  2. layout — what leaves the pool (``export_pool_blocks``, the wire's
     ``MigrationBuffer``, the prefix cache's block digest) is, byte for byte,
     what the dense v1 engine computes for the same tokens, laid out
     token-major with the heads apart as it always was; import into a
     fragmented allocation and copy-on-write keep it.

The chip's compiler decides for itself whether a carried array is updated in
place; PERF.md (section 6, PR 26) quotes its ``memory_analysis()`` for the
benchmark's shapes. This file pins what the CPU can see.
"""

import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngineV2
from deepspeed_tpu.inference.model import decode_step, init_cache, prefill
from deepspeed_tpu.inference.paged import (
    _page_writer,
    copy_pool_blocks,
    export_pool_blocks,
)

from .test_inference_v2 import make_model

BS, NB, ROWS, K = 4, 128, 4, 3
POOLS = ["bf16", "int8", "fp8"]


def _engine(cfg, params, **over):
    # fp32 weights and activations: whatever is bf16/int8/fp8 in a program is the pool's
    base = {"dtype": "fp32", "kv_block_size": BS, "num_kv_blocks": NB, "chunk_bucket": 8,
            "max_seq_len": 32, "hbm_check": "off"}
    base.update(over)
    return InferenceEngineV2(cfg, params, base)


# ------------------------------------------------------------------- census
CARRIERS = {"pjit", "jit", "scan", "while", "cond", "closed_call", "core_call",
            "custom_jvp_call", "custom_vjp_call", "remat", "checkpoint"}


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for x in (val if isinstance(val, (list, tuple)) else (val,)):
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _pool_sized_products(jaxpr, pool, layers):
    """(primitive, shape) of every equation output as large as one layer of
    the pool's values (by dtype: only the pool is stored in it) or of its
    scales (by their ``[pages, bs*kvH]`` rows), other than the ways the pool is
    allowed through a program: carried by a loop or a call, scattered into,
    or re-shaped with its rows left alone. A scan may hold
    it in its carry only: as ``xs`` it is sliced by layer, as ``ys`` a second
    one is stacked up beside it."""
    rows, bs, D = pool.k.shape
    nb = rows // layers

    def pool_sized(a):
        if not hasattr(a, "shape"):
            return False
        values = a.dtype == pool.k.dtype and a.size >= nb * bs * D
        scales = (pool.k_scale is not None and a.dtype == jnp.float32 and 2 <= a.ndim <= 3
                  and a.shape[-1] == pool.k_scale.shape[-1]
                  and math.prod(a.shape[:-1]) >= nb)
        return values or scales

    out = []
    for eqn in _equations(jaxpr):
        name = eqn.primitive.name
        if name == "scan":
            held = eqn.params["num_consts"] + eqn.params["num_carry"]
            sliced = [("scan_xs", v.aval) for v in eqn.invars[held:]]
            stacked = [("scan_ys", v.aval) for v in eqn.outvars[eqn.params["num_carry"]:]]
            out += [(how, tuple(a.shape)) for how, a in sliced + stacked if pool_sized(a)]
        if name in CARRIERS or name == "scatter":
            continue
        for v in eqn.outvars:
            a = v.aval
            page_kept = name == "reshape" and eqn.invars[0].aval.shape[-1:] == a.shape[-1:]  # (a kernel's scalars too)
            if pool_sized(a) and not page_kept:
                out.append((name, tuple(a.shape)))
    return out


def _program(eng, which):
    """The engine's own jitted program and arguments of the shapes it is
    dispatched with."""
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    tables = i32(ROWS, eng.max_pages)
    if which == "step":
        chunk = eng.config.chunk_bucket
        return eng._step_fn(ROWS, chunk), (
            eng.params, eng.pools, i32(ROWS, chunk), i32(ROWS, chunk), i32(ROWS), tables)
    chain_args = (eng.params, eng.pools, i32(ROWS), i32(ROWS), tables, jnp.ones((ROWS,), bool),
                  jnp.full((ROWS,), K, jnp.int32), jax.random.PRNGKey(0))
    if which == "chain":
        return eng._chain_fn(ROWS, K, None, (("do_sample", False),)), chain_args
    return eng._spec_chain_fn(ROWS, K, None), chain_args + (
        i32(ROWS, eng.max_seq_len), jnp.ones((ROWS,), jnp.int32))


@pytest.mark.parametrize("kvd", POOLS)
@pytest.mark.parametrize("which", ["step", "chain", "spec_chain"])
def test_programs_carry_one_pool_and_hand_it_back(which, kvd):
    cfg, _, params = make_model()
    eng = _engine(cfg, params, kv_cache_dtype=kvd, spec_decode=2 if which == "spec_chain" else 0)
    pool = eng.pool
    assert pool.k.shape == (cfg.num_layers * NB, BS, cfg.kv_heads * cfg.dims_per_head)
    # a batch's gathered pages, and its attention weights in the pool's dtype
    # (XLA fallback), must be smaller than a layer, or the census could not
    # tell them from one
    assert 2 * ROWS * eng.max_pages < NB
    fn, args = _program(eng, which)

    jaxpr = jax.make_jaxpr(fn)(*args)
    assert not _pool_sized_products(jaxpr.jaxpr, pool, cfg.num_layers)
    # the census sees the pool: it is scattered into, whole, in every program
    assert any(e.primitive.name == "scatter" and e.outvars[0].aval.shape == pool.k.shape
               for e in _equations(jaxpr.jaxpr))

    compiled = fn.lower(*args).compile()
    leaves = jax.tree_util.tree_leaves(pool)
    first = len(jax.tree_util.tree_leaves(eng.params))  # the pool's leaves come next
    aliased = {int(p) for p in re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)",
                                          compiled.as_text().split("entry_computation_layout")[0])}
    assert aliased == set(range(first, first + len(leaves))), aliased
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(a.nbytes for a in leaves)


def test_census_flags_a_layer_sliced_out_of_the_pool():
    """The teeth: the scan this PR removed (a layer's pool as ``xs``, stacked
    back as ``ys``) is exactly what the census refuses."""
    cfg, _, params = make_model()
    pool = _engine(cfg, params, kv_cache_dtype="bf16").pool

    by_layer = (cfg.num_layers, NB) + pool.k.shape[1:]

    def old_scan(k):
        def body(c, layer_k):
            return c, layer_k.at[0, 0].set(1)

        return jax.lax.scan(body, 0, k)[1]

    jaxpr = jax.make_jaxpr(old_scan)(pool.k.reshape(by_layer))
    found = _pool_sized_products(jaxpr.jaxpr, pool, cfg.num_layers)
    assert sorted(found) == [("scan_xs", by_layer), ("scan_ys", by_layer)]


# ------------------------------------------------------------------- layout
def _v1_keys_values(cfg, params, tokens, kv_dtype):
    """Keys and values of ``tokens`` from the dense v1 model: prefill of all
    but the last few, then token by token. ``[L, T, kvH, hd]`` each."""
    split = len(tokens) - 3
    cache = init_cache(cfg, 1, 32, kv_dtype)
    ids = jnp.asarray(tokens[None, :split], jnp.int32)
    _, cache = prefill(params, cfg, cache, ids, jnp.ones_like(ids, bool))
    for t in tokens[split:]:
        _, cache = decode_step(params, cfg, cache, jnp.asarray([t], jnp.int32))
    return np.asarray(cache.k[:, 0, :len(tokens)]), np.asarray(cache.v[:, 0, :len(tokens)])


def _prefill_then_chain(eng, prompt, uid=0):
    """``step`` writes the prompt's keys and values, ``chain`` K more tokens'.
    Returns the tokens whose keys and values the pool now holds for ``uid``."""
    logits = eng.put([uid], [prompt])
    first = int(np.argmax(logits[0]))
    out, emitted, _ = eng.decode_chain([uid], [first], [K], K, jax.random.PRNGKey(0))
    assert emitted[0] == K
    return np.concatenate([prompt, [first], out[0, :K - 1]]).astype(np.int32)


def _blocks(eng, uid=0):
    seq = eng.state.get(uid)
    return np.asarray(seq.blocks[:seq.n_blocks], np.int32)


def _digest_of(buf, i):
    """blake2b over block ``i`` of a MigrationBuffer, as the prefix cache has
    always taken it: k, v, then the scales, each ``[L, bs, kvH, *]``."""
    h = hashlib.blake2b(digest_size=16)
    for arr in buf:
        if arr is not None:
            h.update(np.asarray(arr[:, i * BS:(i + 1) * BS]).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("kvd", ["bf16", "fp32"])
def test_exported_pages_are_the_dense_engine_s_keys_and_values(kvd):
    """After a prefill and a chain, a request's pages — scattered over a
    fragmented allocation, read back through ``export_pool_blocks`` — are
    v1's keys and values for the same tokens in ``[L, tokens, kvH, hd]``.
    In a bf16 pool layer 0, which depends on the embedding alone, is identical
    to the byte; the deeper layers see each engine's own attention arithmetic,
    and an fp32 pool each engine's own order of summation, and agree to the
    pool dtype's last places."""
    cfg, _, params = make_model()
    eng = _engine(cfg, params, kv_cache_dtype=kvd)
    rng = np.random.RandomState(1)
    eng.put([7], [rng.randint(0, cfg.vocab_size, (6,))])  # someone else's pages first
    eng.put([8], [rng.randint(0, cfg.vocab_size, (3,))])
    eng.flush(7)  # and a hole before ours
    tokens = _prefill_then_chain(eng, rng.randint(0, cfg.vocab_size, (7,)))
    blocks = _blocks(eng)
    assert list(blocks) != sorted(blocks) or np.any(np.diff(blocks) != 1)  # fragmented

    buf = export_pool_blocks(eng.pool, jnp.asarray(blocks), cfg.num_layers, cfg.kv_heads)
    T = len(tokens)
    assert buf.k.shape == (cfg.num_layers, len(blocks) * BS, cfg.kv_heads, cfg.dims_per_head)
    want_k, want_v = _v1_keys_values(cfg, params, tokens, eng.pool.k.dtype)
    got_k, got_v = np.asarray(buf.k[:, :T]), np.asarray(buf.v[:, :T])
    if kvd == "bf16":
        assert got_k[0].tobytes() == want_k[0].tobytes()
        assert got_v[0].tobytes() == want_v[0].tobytes()
    tol = max(4 * float(jnp.finfo(eng.pool.k.dtype).eps), 2e-5)
    for got, want in ((got_k, want_k), (got_v, want_v)):
        np.testing.assert_allclose(got.astype(np.float32), want.astype(np.float32),
                                   rtol=tol, atol=tol)
    # ... and the digest of a block is taken over just these bytes
    for i, b in enumerate(blocks):
        assert eng._block_content_hash(int(b)) == _digest_of(buf, i)


@pytest.mark.parametrize("kvd", POOLS)
def test_import_into_fragmented_allocation_keeps_bytes_and_digests(kvd):
    cfg, _, params = make_model()
    src, dst = (_engine(cfg, params, kv_cache_dtype=kvd) for _ in range(2))
    rng = np.random.RandomState(2)
    _prefill_then_chain(src, rng.randint(0, cfg.vocab_size, (9,)))
    for uid, n in ((20, 5), (21, 2), (22, 7)):  # fragment the destination
        dst.put([uid], [rng.randint(0, cfg.vocab_size, (n,))])
    dst.flush(21)
    export = src.export_request(0)
    assert dst.import_request(0, export)
    src_blocks, dst_blocks = _blocks(src), _blocks(dst)
    assert list(src_blocks) != list(dst_blocks)
    again = dst.export_request(0)["buffer"]
    n = len(src_blocks) * BS
    for a, b in zip(export["buffer"], again):
        assert (a is None) == (b is None)
        if a is not None:
            assert np.asarray(a[:, :n]).tobytes() == np.asarray(b[:, :n]).tobytes()
    assert ([src._block_content_hash(int(b)) for b in src_blocks]
            == [dst._block_content_hash(int(b)) for b in dst_blocks])


@pytest.mark.parametrize("kvd", POOLS)
def test_copy_on_write_clones_one_page_of_every_layer(kvd):
    cfg, _, params = make_model()
    eng = _engine(cfg, params, kv_cache_dtype=kvd)
    _prefill_then_chain(eng, np.arange(9) % cfg.vocab_size)
    src = int(_blocks(eng)[1])
    dst = next(b for b in range(NB) if b not in set(_blocks(eng)))
    by_layer = lambda a: np.asarray(a).reshape((cfg.num_layers, NB) + a.shape[1:])  # noqa: E731
    before = [None if a is None else by_layer(a) for a in eng.pool]
    after = copy_pool_blocks(eng.pool, jnp.int32(src), jnp.int32(dst), cfg.num_layers)
    for old, new in zip(before, after):
        if old is None:
            assert new is None
            continue
        new = by_layer(new)
        assert new[:, dst].tobytes() == old[:, src].tobytes() and old[:, src].any()
        keep = np.arange(NB) != dst
        assert new[:, keep].tobytes() == old[:, keep].tobytes()


@pytest.mark.parametrize("C,starts,lens", [
    (1, (0, 5, 7, 30), (1, 1, 0, 1)),      # decode: one token a row, one row idle
    (8, (0, 3, 12, 9), (8, 5, 8, 0)),      # a chunk off the page boundary, one cut short
    (3, (2, 3, 17, 6), (3, 3, 3, 0)),      # a token and two drafts across a boundary
], ids=["decode", "chunk", "spec"])
def test_pages_equal_a_token_by_token_write(C, starts, lens):
    """A chunk's keys and values, and always the scales, are written a page
    at a time (gather, lay the new tokens over their slots, put back); the
    result is what writing each token's row at ``(page, slot)`` would give,
    and nothing else moves. Both array forms: ``[pages, bs, X]`` values and
    ``[pages, bs*X]`` scale rows."""
    X, pages, layer_first = 2, 32, 32  # the second of two layers
    rng = np.random.RandomState(0)
    N = len(starts)
    tables = rng.permutation(pages)[: N * 8].reshape(N, 8).astype(np.int32)
    positions = np.asarray(starts, np.int32)[:, None] + np.arange(C, dtype=np.int32)
    new = rng.randn(N * C, X).astype(np.float32)
    pool = rng.randn(2 * pages, BS, X).astype(np.float32)

    put = _page_writer(jnp.asarray(tables), jnp.asarray(positions),
                       jnp.asarray(lens, jnp.int32), BS, 2 * pages)
    want = pool.copy()
    for n in range(N):
        for c in range(lens[n]):
            p = positions[n, c]
            want[layer_first + tables[n, p // BS], p % BS] = new[n * C + c]
    for shape in (pool.shape, (2 * pages, BS * X)):
        got = np.asarray(put(jnp.asarray(pool.reshape(shape)), jnp.asarray(new),
                             jnp.int32(layer_first)))
        assert got.shape == shape and got.tobytes() == want.tobytes()
    assert (want != pool).any() and (want[:layer_first] == pool[:layer_first]).all()


# ------------------------------------------------- the state pool beside it
def _state_sized_products(jaxpr, state_pool):
    """(primitive, shape) of every equation output of the state pool's whole
    shape (either array's) other than the ways it is allowed through a
    program: carried by a loop or a call, and updated in place, a layer's row
    of it at a time (``dynamic_update_slice``). A scan may hold it in its carry
    only."""
    whole = {tuple(a.shape) for a in state_pool}
    out = []
    for eqn in _equations(jaxpr):
        name = eqn.primitive.name
        if name == "scan":
            held = eqn.params["num_consts"] + eqn.params["num_carry"]
            out += [("scan_xs", tuple(v.aval.shape)) for v in eqn.invars[held:] if tuple(v.aval.shape) in whole]
            out += [("scan_ys", tuple(v.aval.shape)) for v in eqn.outvars[eqn.params["num_carry"]:]
                    if tuple(v.aval.shape) in whole]
        if name in CARRIERS or name == "dynamic_update_slice":
            continue
        out += [(name, tuple(v.aval.shape)) for v in eqn.outvars
                if hasattr(v.aval, "shape") and tuple(v.aval.shape) in whole]
    return out


@pytest.mark.parametrize("which", ["step", "chain", "prefill", "chain_conv_kernel"])
def test_programs_update_the_state_pool_in_place_and_hand_both_pools_back(which, monkeypatch):
    """A model with state-space layers: its programs take the page pool AND the
    state pool in the pool's place (``cache.Pools``), donated. Nothing of
    the state pool's whole shape (either array's) is produced but its in-place
    update, a layer's row at a time, and the compiled program hands both pools
    back aliased. The conv pool: the toy's 160 channels are no lane tile, so
    off the TPU AND on it a decode step's convolution takes XLA's form there, a
    ``dynamic_update_slice`` a layer as a prompt's; ``chain_conv_kernel`` makes
    the chain take the kernel ``conv_update`` (interpret mode), whose aliased
    output is then the only product of the conv pool's whole shape."""
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import conv_update

    from .test_hybrid import toy_params

    cfg, params = toy_params(jnp.float32)
    kernel = which == "chain_conv_kernel"
    if kernel:
        assert not conv_update.takes(cfg.ssm.conv_dim, ROWS, cfg.ssm.d_inner)
        monkeypatch.setattr(registry, "_default_backend", lambda: "tpu")
        monkeypatch.setattr(conv_update, "takes", lambda *sizes: True)
        which = "chain"
    eng = _engine(cfg, params, max_seqs=ROWS, row_bucket=ROWS, kv_cache_dtype="bf16")
    pools = eng.pools
    assert pools.state.ssm.shape == (cfg.ssm_layers, ROWS, 1, 16, 128) and pools.ring is None
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    tables, chunk = i32(ROWS, eng.max_pages), eng.config.chunk_bucket
    if which == "chain":
        fn = eng._chain_fn(ROWS, K, None, (("do_sample", False),))
        args = (eng.params, pools, i32(ROWS), i32(ROWS), tables, jnp.ones((ROWS,), bool),
                jnp.full((ROWS,), K, jnp.int32), jax.random.PRNGKey(0))
    else:
        args = (eng.params, pools, i32(ROWS, chunk), i32(ROWS, chunk), i32(ROWS), tables)
        fn = eng._step_fn(ROWS, chunk)
        if which == "prefill":
            fn, args = eng._sample_step_fn(ROWS, chunk, (("do_sample", False),)), args + (jax.random.PRNGKey(0),)

    jaxpr = jax.make_jaxpr(fn)(*args)
    products = _state_sized_products(jaxpr.jaxpr, pools.state)
    assert products == [("pallas_call", pools.state.conv.shape)] * (cfg.period.count("mamba") if kernel else 0)
    assert not _pool_sized_products(jaxpr.jaxpr, pools.kv, cfg.attention_layers)
    # the census sees the pools: every state-space layer of a period updates each, whole
    for pool, by_kernel in ((pools.state.ssm, False), (pools.state.conv, kernel)):
        updates = [e for e in _equations(jaxpr.jaxpr) if e.primitive.name == "dynamic_update_slice"
                   and e.outvars[0].aval.shape == pool.shape]
        assert len(updates) == (0 if by_kernel else cfg.period.count("mamba"))
    kernels = [e for e in _equations(jaxpr.jaxpr) if e.primitive.name == "pallas_call"
               and "conv_update" in str(e.params["name"])]
    assert len(kernels) == (cfg.period.count("mamba") if kernel else 0)
    assert all(dict(e.params["input_output_aliases"]) == {1: 0} for e in kernels)  # the pool, in place

    compiled = fn.lower(*args).compile()
    leaves = jax.tree_util.tree_leaves(pools)
    first = len(jax.tree_util.tree_leaves(eng.params))  # the pools' leaves come next
    aliased = {int(p) for p in re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)",
                                          compiled.as_text().split("entry_computation_layout")[0])}
    assert aliased == set(range(first, first + len(leaves))), aliased
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(a.nbytes for a in leaves)


def test_census_flags_a_state_pool_sliced_by_layer():
    """The teeth: a scan that takes the state pool as ``xs`` (a layer's row
    sliced out) and stacks it back as ``ys`` is what the census refuses."""
    from deepspeed_tpu.inference.paged import StatePool

    pool = StatePool(jnp.zeros((4, 2, 3, 5, 7)), jnp.zeros((4, 2, 11), jnp.bfloat16))
    jaxpr = jax.make_jaxpr(lambda a: jax.lax.scan(lambda c, row: (c, row + 1), 0, a)[1])(pool.ssm)
    found = _state_sized_products(jaxpr.jaxpr, pool)
    assert ("scan_xs", pool.ssm.shape) in found and ("scan_ys", pool.ssm.shape) in found
