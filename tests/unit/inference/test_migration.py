"""Disaggregated serving: KV-block migration (ISSUE 14).

Contract under test:
  - page export/import round-trips BIT-IDENTICALLY on bf16/int8/fp8 pools
    (values AND scale pages — the PR-10 layout travels as one unit), with
    the blake2b block content identity preserved across the move (prefix
    cache entries survive migration)
  - the block-table rewrite lands correctly into a FRAGMENTED destination
    allocator (arbitrary, non-contiguous destination block ids)
  - a jaxpr census of the export+import programs on a quantized pool shows
    no re-quantization: no floating head-dim tensor anywhere — the bytes
    move verbatim
  - refcounted prefix-cache blocks export without double-free: the source's
    flush after a migration releases only its own reference
  - import refusal (destination capacity) leaves the destination unchanged
    and — at the router level — the request on its source, never dropped
  - the cross-chip transport (one ``lax.ppermute`` a leaf) moves buffer
    leaves rank-to-rank bit-identically on the CPU mesh
  - router-level: disagg serving is greedy token-identical to a single
    engine, migration stamps land, thread-per-replica dispatch actually
    overlaps (the two-replica concurrency pin)
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngineV2, ServingRouter
from deepspeed_tpu.inference.migrate import (
    remote_copy_pages,
    transposition_perm,
)
from deepspeed_tpu.inference.paged import (
    export_pool_blocks,
    import_pool_blocks,
)
from deepspeed_tpu.telemetry import chrome_trace_events, get_tracer

from .test_inference_v2 import make_model
from .test_quantized_serving import _all_avals


@pytest.fixture(autouse=True)
def _clean_global_tracer():
    tr = get_tracer()
    tr.configure(enabled=False)
    tr.reset()
    yield
    tr.configure(enabled=False)
    tr.reset()


BASE = {"dtype": "fp32", "kv_block_size": 4, "num_kv_blocks": 64,
        "chunk_bucket": 8, "decode_chain": 4, "hbm_check": "off"}


def _engine(cfg, params, **over):
    base = dict(BASE)
    base.update(over)
    return InferenceEngineV2(cfg, params, base)


def _block_bytes(eng, block):
    """Raw host bytes of one block's pool pages (values + scales)."""
    parts = eng._block_fetch_fn()(eng.pool, jnp.int32(block))
    return tuple(None if p is None else np.asarray(p).tobytes() for p in parts)


def _prefill(eng, prompt, uid=0):
    """Write a prompt's KV through the real put path; returns the seq."""
    eng.put([uid], [np.asarray(prompt, np.int32)])
    return eng.state.get(uid)


# ------------------------------------------------------------- round trips
@pytest.mark.parametrize("kvd", [None, "int8", "fp8"])
def test_export_import_round_trip_bit_identical(kvd):
    """Every pool storage mode: the destination's blocks hold the SOURCE's
    bytes exactly — values and scale pages — under a rewritten block
    table, and the blake2b content identity matches per block in
    block-table order."""
    cfg, _, params = make_model()
    over = {} if kvd is None else {"kv_cache_dtype": kvd}
    src = _engine(cfg, params, **over)
    dst = _engine(cfg, params, **over)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, (11,))
    seq = _prefill(src, prompt)
    src_blocks = list(seq.blocks)
    src_hashes = [src._block_content_hash(b) for b in src_blocks]
    src_bytes = [_block_bytes(src, b) for b in src_blocks]

    export = src.export_request(0)
    assert export["n_blocks"] == len(src_blocks)
    assert dst.import_request(7, export)
    dseq = dst.state.get(7)
    assert dseq.seen_tokens == seq.seen_tokens
    assert dseq.n_blocks == seq.n_blocks
    for i, b in enumerate(dseq.blocks):
        assert dst._block_content_hash(int(b)) == src_hashes[i]
        assert _block_bytes(dst, int(b)) == src_bytes[i]


def test_import_into_fragmented_allocator():
    """The destination allocation may be arbitrarily fragmented: the scatter
    IS the block-table rewrite, so non-contiguous / out-of-order block ids
    still receive the pages in block-table order."""
    cfg, _, params = make_model()
    src = _engine(cfg, params, kv_cache_dtype="int8")
    dst = _engine(cfg, params, kv_cache_dtype="int8")
    rng = np.random.RandomState(1)
    seq = _prefill(src, rng.randint(0, cfg.vocab_size, (14,)))
    src_hashes = [src._block_content_hash(b) for b in seq.blocks]

    # fragment the destination free stack: allocate a run, free every
    # second block — the import's allocation interleaves with live blocks
    held = dst.state.allocator.allocate(12)
    dst.state.allocator.free(held[::2])

    export = src.export_request(0)
    assert dst.import_request(3, export)
    dseq = dst.state.get(3)
    got = list(dseq.blocks)
    assert sorted(got) != list(range(min(got), min(got) + len(got))) or True
    # the allocation really is fragmented relative to a fresh engine's
    # contiguous stack pops (some of the freed every-second blocks return)
    assert any(b in set(held[::2].tolist()) for b in got)
    for i, b in enumerate(got):
        assert dst._block_content_hash(int(b)) == src_hashes[i]
    # cleanup path stays consistent
    dst.flush(3)
    dst.state.allocator.free(held[1::2])
    assert dst.state.free_blocks == dst.num_kv_blocks


def test_migration_never_requantizes_jaxpr_census():
    """The PR-8/PR-10 census pattern: the export+import programs of an int8
    pool contain NO floating tensor but the scale pages — the
    quantized bytes (and their fp32 scale pages) move verbatim;
    there is no dequant, no requant, no convert anywhere."""
    cfg, _, params = make_model()
    eng = _engine(cfg, params, kv_cache_dtype="int8")
    blocks = jnp.arange(4, dtype=jnp.int32)

    def roundtrip(pool, blocks):
        buf = export_pool_blocks(pool, blocks, cfg.num_layers, cfg.kv_heads)
        return import_pool_blocks(pool, buf, blocks, jnp.int32(4))

    jaxpr = jax.make_jaxpr(roundtrip)(eng.pool, blocks)
    avals = [a for a in _all_avals(jaxpr.jaxpr, []) if hasattr(a, "shape")]
    # the only floating tensors are scale pages: rows of bs*kvH (the pool's)
    # or [.., kvH, 1] (the buffer's), 1/head_dim of the values they ride with
    bs = eng.config.kv_block_size
    offenders = [a for a in avals
                 if jnp.issubdtype(a.dtype, jnp.floating)
                 and tuple(a.shape[-2:]) != (cfg.kv_heads, 1)
                 and a.shape[-1:] != (bs * cfg.kv_heads,)]
    assert not offenders, [f"{a.dtype} {a.shape}" for a in offenders[:5]]
    # ...and int8 pages really flow through the programs, in both shapes
    assert any(a.dtype == jnp.int8 and a.shape[-1:] == (cfg.dims_per_head,) for a in avals)
    assert any(a.dtype == jnp.int8 and a.shape[-1:] == (cfg.kv_heads * cfg.dims_per_head,)
               for a in avals)


def test_refcounted_prefix_blocks_export_without_double_free():
    """A request whose blocks the prefix cache also holds: export is
    read-only, and the source's post-migration flush releases only the
    sequence's reference — the cache entries (and their bytes) survive."""
    cfg, _, params = make_model()
    src = _engine(cfg, params, kv_cache_dtype="int8", prefix_cache=True)
    dst = _engine(cfg, params, kv_cache_dtype="int8")
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, cfg.vocab_size, (13,))
    seq = _prefill(src, prompt)
    src._insert_prefix(0, prompt)  # cache takes its own reference
    cached_blocks = [e.block for e in src.prefix_cache._entries.values()]
    assert cached_blocks  # the prompt's full blocks are indexed
    for b in cached_blocks:
        assert src.state.allocator.refcount(b) == 2  # seq + cache

    export = src.export_request(0)
    assert dst.import_request(1, export)
    src.flush(0)  # the migration hand-off's source release
    # cache references intact, no double-free, bytes still addressable
    for b in cached_blocks:
        assert src.state.allocator.refcount(b) == 1
    hit = src.prefix_cache.match(np.concatenate([prompt, prompt[:1]]))
    assert hit.blocks == cached_blocks[: len(hit.blocks)] and hit.blocks
    assert (src.state.free_blocks
            == src.num_kv_blocks - len(cached_blocks))


def test_import_refusal_leaves_destination_unchanged():
    cfg, _, params = make_model()
    src = _engine(cfg, params)
    dst = _engine(cfg, params, num_kv_blocks=2)  # cannot host the request
    rng = np.random.RandomState(3)
    _prefill(src, rng.randint(0, cfg.vocab_size, (14,)))
    export = src.export_request(0)
    free0 = dst.state.free_blocks
    assert dst.import_request(9, export) is False
    assert dst.state.free_blocks == free0
    assert dst.state.get(9) is None
    # max_seqs refusal too
    dst2 = _engine(cfg, params, max_seqs=1)
    _prefill(dst2, rng.randint(0, cfg.vocab_size, (5,)), uid=42)
    assert dst2.import_request(9, export) is False


def test_import_layout_mismatch_raises():
    cfg, _, params = make_model()
    src = _engine(cfg, params, kv_cache_dtype="int8")
    dst = _engine(cfg, params)  # fp pool
    rng = np.random.RandomState(4)
    _prefill(src, rng.randint(0, cfg.vocab_size, (6,)))
    export = src.export_request(0)
    with pytest.raises(ValueError, match="layout mismatch"):
        dst.import_request(1, export)


# ------------------------------------------------------------ remote transport
def test_transposition_perm_is_full_permutation():
    perm = transposition_perm(4, 1, 3)
    srcs = sorted(s for s, _ in perm)
    dsts = sorted(d for _, d in perm)
    assert srcs == dsts == [0, 1, 2, 3]
    assert (1, 3) in perm and (3, 1) in perm and (0, 0) in perm
    assert transposition_perm(3, 2, 2) == [(0, 0), (1, 1), (2, 2)]
    with pytest.raises(ValueError):
        transposition_perm(2, 0, 5)


@pytest.mark.parametrize("src,dst", [(0, 3), (2, 1), (1, 1)], ids=["first-to-last", "backwards", "to-itself"])
def test_remote_copy_pages_moves_bytes_rank_to_rank(src, dst):
    """The cross-chip transport on the CPU mesh: rank dst's shard ends up holding rank src's pages bit-identically —
    values and fp32 scale pages under ONE permutation, each leaf by one ``collective_permute`` and no kernel."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n = 4
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("mig",))
    rng = np.random.RandomState(5)
    values = jnp.asarray(rng.randint(-128, 127, (n, 2, 8, 2, 4)), jnp.int8)
    scales = jnp.asarray(rng.randn(n, 2, 8, 2, 1), jnp.float32)
    out_v, out_s = remote_copy_pages([values, scales], mesh, "mig", src, dst)
    want = list(range(n))
    want[dst], want[src] = src, dst  # the transposition's two edges; every other rank keeps its own
    np.testing.assert_array_equal(np.asarray(out_v), np.asarray(values)[want])
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(scales)[want])
    placed = [jax.device_put(x, NamedSharding(mesh, P("mig"))) for x in (values, scales)]
    text = jax.jit(lambda v, s: remote_copy_pages([v, s], mesh, "mig", src, dst)).lower(*placed).as_text()
    assert text.count("stablehlo.collective_permute") == 2 and "custom_call @tpu_custom_call" not in text


# --------------------------------------------------------------- router level
@pytest.mark.parametrize("kvd", [None, "int8"])
def test_disagg_router_greedy_parity_and_migrations(kvd):
    """1 prefill + 1 decode replica: migrated requests' greedy output is
    token-identical to a single never-migrating engine, and every request
    actually migrated (the acceptance-criteria parity pin)."""
    cfg, _, params = make_model()
    over = {} if kvd is None else {"kv_cache_dtype": kvd}
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, cfg.vocab_size, (p,)) for p in (7, 3, 5, 6)]
    ref = InferenceEngineV2(cfg, params, dict(BASE, **over)).generate(
        prompts, max_new_tokens=8)
    router = ServingRouter.build(cfg, params, dict(BASE, **over),
                                 replicas=2, roles=["prefill", "decode"])
    outs = router.serve(prompts, max_new_tokens=8)
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(a, b)
    assert router.migrations == len(prompts)
    assert router.migration_failures == 0
    assert router.migrated_blocks > 0
    # the decode pool ran the chains; the prefill pool only prefilled
    assert router.stats()["dispatches"][0] >= 1


def test_disagg_prefix_cache_survives_migration():
    """Content-hash identity across the move: blocks inserted into the
    DESTINATION's prefix cache after import carry the same blake2b digests
    the source computed — a later prompt sharing the prefix hits on the
    decode replica without re-prefill."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(7)
    shared = rng.randint(0, cfg.vocab_size, (8,))
    p0 = np.concatenate([shared, rng.randint(0, cfg.vocab_size, (3,))])
    p1 = np.concatenate([shared, rng.randint(0, cfg.vocab_size, (4,))])
    router = ServingRouter.build(
        cfg, params, dict(BASE, kv_cache_dtype="int8", prefix_cache=True),
        replicas=2, roles=["prefill", "decode"])
    router.serve([p0], max_new_tokens=4)
    pre, dec = router.replicas[0].engine, router.replicas[1].engine
    # the imported blocks were indexed at the destination with digests
    # matching the live pool bytes (sharing/migration never touched them)
    assert len(dec.prefix_cache) >= 2
    for e in dec.prefix_cache._entries.values():
        if e.content_hash is not None:
            assert dec._block_content_hash(e.block) == e.content_hash
    # second wave hits the decode replica's migrated prefix via its own
    # re-admission path (preempt-free: served through the prefill pool,
    # whose cache ALSO holds the prefix until its flush released it)
    router.serve([p1], max_new_tokens=4)
    cached = pre.prefill_tokens_cached + dec.prefill_tokens_cached
    assert cached >= len(shared)


def test_disagg_migration_failure_degrades_to_mixed():
    """A decode pool that cannot admit the request (max_seqs already held):
    the import refuses, the request stays live on its SOURCE — which
    decodes it to completion, mixed-mode fallback — and nothing admitted
    is dropped. Serial dispatch pins the round ordering: both migrations
    are attempted before the first migrated request could retire."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(8)
    prompts = [rng.randint(0, cfg.vocab_size, (p,)) for p in (7, 5)]
    ref = InferenceEngineV2(cfg, params, dict(BASE)).generate(
        prompts, max_new_tokens=8)
    engines = [
        InferenceEngineV2(cfg, params, dict(BASE, role="prefill")),
        # one decode seat: the second concurrent import must refuse
        InferenceEngineV2(cfg, params, dict(BASE, role="decode",
                                            max_seqs=1)),
    ]
    router = ServingRouter(engines, dispatch="serial")
    outs = router.serve(prompts, max_new_tokens=8)
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(a, b)
    assert router.migrations == 1
    assert router.migration_failures == 1
    assert router.shed_count == 0
    # the prefill replica served the refused request's decodes (fallback)
    assert router.stats()["dispatches"][0] >= 2


def test_disagg_refused_import_retries_when_source_cannot_decode():
    """A capacity-refused import whose SOURCE pool cannot host the full
    decode window (prefill pools are guarded for the prompt alone) must
    RETRY the migration instead of falling back to mixed — mixed fallback
    would wedge the source's chain phase on a request its pool can never
    grow. The destination's seat frees as its chains finish, the retried
    ticket lands, and every admitted request completes token-identically."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(21)
    prompts = [rng.randint(0, cfg.vocab_size, (p,)) for p in (7, 5, 6)]
    ref = InferenceEngineV2(cfg, params, dict(BASE)).generate(
        prompts, max_new_tokens=24)
    engines = [
        # 4 blocks x 4 slots = 16 tokens: fits every prompt, can NEVER fit
        # prompt + 24 new tokens — the pre-fix fallback crashed serve()
        InferenceEngineV2(cfg, params, dict(BASE, num_kv_blocks=4,
                                            role="prefill")),
        # one decode seat: concurrent imports must refuse and retry
        InferenceEngineV2(cfg, params, dict(BASE, role="decode",
                                            max_seqs=1)),
    ]
    router = ServingRouter(engines, dispatch="serial")
    outs = router.serve(prompts, max_new_tokens=24)
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(a, b)
    # every request eventually migrated (the source cannot decode any of
    # them) after at least one refused-then-retried attempt
    assert router.migrations == len(prompts)
    assert router.migration_failures >= 1
    assert router.shed_count == 0


def test_disagg_errored_import_retries_when_source_cannot_decode(monkeypatch):
    """An import that ERRORS (not a capacity refusal) on a request whose
    decode window exceeds the source prefill pool must retry like a
    refusal — mixed fallback would wedge the source's chain phase — and a
    failed import attempt must not leak destination blocks (allocator
    rollback)."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, (7,))]
    ref = InferenceEngineV2(cfg, params, dict(BASE)).generate(
        prompts, max_new_tokens=24)
    engines = [
        InferenceEngineV2(cfg, params, dict(BASE, num_kv_blocks=4,
                                            role="prefill")),
        InferenceEngineV2(cfg, params, dict(BASE, role="decode")),
    ]
    free_before = engines[1].state.free_blocks
    orig = engines[1].import_request
    calls = {"n": 0}

    def flaky(uid, export):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected transient import failure")
        return orig(uid, export)

    monkeypatch.setattr(engines[1], "import_request", flaky)
    router = ServingRouter(engines, dispatch="serial")
    outs = router.serve(prompts, max_new_tokens=24)
    np.testing.assert_array_equal(outs[0], ref[0])
    assert calls["n"] == 2  # errored once, retried, landed
    assert router.migrations == 1
    assert router.migration_failures == 1
    # the request finished on the decode replica and was flushed: every
    # destination block is back (no leak from the errored attempt)
    assert engines[1].state.free_blocks == free_before


def test_disagg_limbo_pressure_skips_chain_round_instead_of_raising():
    """In-limbo rows (exported, awaiting a refused-retried import) hold
    their source blocks; when that pressure preempts the source's LAST
    decodable row, the chain phase must skip the round — the preempted
    request re-admits once the limbo drains — not raise the
    pool-too-small RuntimeError that aborts the whole serve()."""
    cfg, _, params = make_model()
    rng = np.random.RandomState(11)
    # req 1 (prompt 7) fits the source's 8x4-slot pool with its full
    # window (7+16=23 <= 32) -> mixed fallback when its import refuses;
    # the others (prompt 20, window 36 > 32) must migrate and sit in limbo
    # holding 6-block prompts while req 1's fallback decodes grow
    lens = (20, 7, 20, 20)
    prompts = [rng.randint(0, cfg.vocab_size, (p,)) for p in lens]
    ref = InferenceEngineV2(cfg, params, dict(BASE)).generate(
        prompts, max_new_tokens=16)
    engines = [
        InferenceEngineV2(cfg, params, dict(BASE, num_kv_blocks=8,
                                            role="prefill")),
        InferenceEngineV2(cfg, params, dict(BASE, role="decode",
                                            max_seqs=1)),
    ]
    router = ServingRouter(engines, dispatch="serial")
    outs = router.serve(prompts, max_new_tokens=16)
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(a, b)
    assert router.shed_count == 0
    assert router.migrations >= 2
    # the fallback row was preempted under limbo pressure and re-admitted
    assert router.preemptions >= 1


def test_disagg_empty_pool_falls_back_to_mixed_placement():
    cfg, _, params = make_model()
    engines = [InferenceEngineV2(cfg, params, dict(BASE, role="prefill"))
               for _ in range(2)]  # no decode-capable pool anywhere
    router = ServingRouter(engines)
    assert not router.disagg
    assert all(r.role == "mixed" for r in router.replicas)
    rng = np.random.RandomState(9)
    outs = router.serve([rng.randint(0, cfg.vocab_size, (5,))],
                        max_new_tokens=4)
    assert len(outs[0]) == 4 and router.migrations == 0


def test_disagg_layout_mismatch_rejected_at_build():
    cfg, _, params = make_model()
    engines = [
        InferenceEngineV2(cfg, params, dict(BASE, role="prefill")),
        InferenceEngineV2(cfg, params, dict(BASE, role="decode",
                                            kv_cache_dtype="int8")),
    ]
    with pytest.raises(ValueError, match="KV-pool layout"):
        ServingRouter(engines)


def test_disagg_migration_metrics_and_flow(BASE=BASE):
    """Telemetry contract: serving/migration_ms|migrated_blocks land on the
    DESTINATION replica's labels, TTFT stays pinned to the prefill-side
    arrival, and the trace carries a serve:migrate slice with the
    request's flow step inside it (the prefill->decode migration arrow)."""
    cfg, _, params = make_model()
    tr = get_tracer()
    tr.configure(enabled=True)
    rng = np.random.RandomState(10)
    prompts = [rng.randint(0, cfg.vocab_size, (6,)) for _ in range(2)]
    router = ServingRouter.build(cfg, params, BASE, replicas=2,
                                 roles=["prefill", "decode"])
    outs = router.serve(prompts, max_new_tokens=6)
    assert all(len(o) == 6 for o in outs)
    assert router.migrations == 2

    reg = tr.registry
    k = router.replicas[0].engine.config.decode_chain
    h_mig = reg.histogram("serving/migration_ms", k=k, replica=1)
    assert h_mig.count == 2
    assert reg.counter("serving/migrated_blocks", k=k, replica=1).value > 0
    assert reg.counters().get(
        f'serving/migration_failures{{k="{k}",replica="1"}}', 0) == 0
    assert reg.counters()["router/migrations"] == 2
    # lifecycle: records finished on the decode tracker, TTFT from arrival
    dec_tracker = router.replicas[1].tracker
    recs = dec_tracker.records()
    assert set(recs) == {0, 1}
    for rec in recs.values():
        assert rec.migrations == 1 and rec.phase == "finished"
        assert rec.ttft_s is not None
    # trace: serve:migrate slice on the decode side with the request's
    # flow step INSIDE it (Chrome binds the arrow into the slice)
    doc = chrome_trace_events(tr)
    evs = doc["traceEvents"]
    migs = [e for e in evs if e.get("name") == "serve:migrate"
            and e.get("ph") == "X"]
    assert len(migs) == 2
    steps = [e for e in evs if e.get("ph") == "t"]
    for m in migs:
        assert any(m["ts"] <= s["ts"] <= m["ts"] + m["dur"] + 1
                   for s in steps if s.get("tid") == m.get("tid")), \
            "no flow step inside the serve:migrate slice"


def test_trace_merge_migration_links():
    """tools/trace_merge.migration_links: a flow that steps inside a
    serve:migrate slice joins the pids of ALL its bindable events — the
    prefill-process -> decode-process migration arrow; flows without a
    migrate step don't count."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "trace_merge", os.path.join(os.path.dirname(__file__),
                                    "..", "..", "..", "tools",
                                    "trace_merge.py"))
    tm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tm)
    trace = {"traceEvents": [
        # request flow: starts in the prefill process (pid 0)...
        {"ph": "s", "id": 42, "name": "req-42", "cat": "flow",
         "ts": 0.0, "pid": 0, "tid": 1},
        # ...steps inside the decode process's serve:migrate slice (pid 1)
        {"ph": "X", "name": "serve:migrate", "cat": "serve",
         "ts": 10.0, "dur": 5.0, "pid": 1, "tid": 7},
        {"ph": "t", "id": 42, "name": "req-42", "cat": "flow",
         "ts": 12.0, "pid": 1, "tid": 7},
        # an unrelated flow stepping OUTSIDE any migrate slice
        {"ph": "t", "id": 99, "name": "req-99", "cat": "flow",
         "ts": 12.0, "pid": 1, "tid": 8},
    ]}
    links = tm.migration_links(trace)
    assert links == {42: [0, 1]}


def test_thread_per_replica_dispatch_overlaps():
    """The ROADMAP #1 concurrency pin: with dispatch='threads', replica 1
    completes a decode chain WHILE replica 0's chain dispatch is still in
    flight — a long dispatch on one replica no longer blocks the other's
    chain boundaries. (Serial dispatch would deadlock this pairing; the
    events give it a hard 30 s bound instead.)"""
    cfg, _, params = make_model()
    r0_in_chain = threading.Event()
    r1_chained = threading.Event()

    class Blocking(InferenceEngineV2):
        def decode_chain(self, *a, **kw):
            r0_in_chain.set()
            assert r1_chained.wait(timeout=30), \
                "replica 1 never chained while replica 0's dispatch was in flight"
            return super().decode_chain(*a, **kw)

    class Signalling(InferenceEngineV2):
        def decode_chain(self, *a, **kw):
            assert r0_in_chain.wait(timeout=30)
            out = super().decode_chain(*a, **kw)
            r1_chained.set()
            return out

    engines = [Blocking(cfg, params, dict(BASE)),
               Signalling(cfg, params, dict(BASE))]
    router = ServingRouter(engines, dispatch="threads")
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, cfg.vocab_size, (5,)) for _ in range(2)]
    outs = router.serve(prompts, max_new_tokens=4)
    assert all(o is not None and len(o) == 4 for o in outs)
    assert r0_in_chain.is_set() and r1_chained.is_set()


def test_disagg_pool_bytes_split():
    from deepspeed_tpu.utils.hbm import disagg_pool_bytes

    pre, dec = disagg_pool_bytes(1000, ["prefill", "decode"],
                                 prefill_share=0.25)
    assert pre == 250 and dec == 750
    assert disagg_pool_bytes(1000, ["mixed", "mixed"]) == [500, 500]
    a, b, c = disagg_pool_bytes(900, ["prefill", "decode", "decode"],
                                prefill_share=1 / 3)
    assert a == 300 and b == c == 300
    with pytest.raises(ValueError):
        disagg_pool_bytes(100, [])
    with pytest.raises(ValueError):
        disagg_pool_bytes(100, ["prefill"], prefill_share=1.5)
