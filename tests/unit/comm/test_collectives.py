"""collectives/: algorithm x codec equivalence, selector, overlap, EF.

Correctness bar (ISSUE 3 acceptance): on the forced 8-device CPU mesh every
hop-composed algorithm matches the ``jax.lax`` baseline collective —
bit-level for passthrough codecs (integer-valued payloads make every
summation order exact), bounded relative error for the int8/fp8 wire codecs
— including non-divisible payloads (internal chunk padding) and block sizes
that do not divide the chunk (codec padding). The selector answers repeated
(op, bytes, axis-size) queries from its cache, measured mode consumes the
``benchmark --sweep`` decision table, and a ``ring2d``+``int8`` all-reduce
runs inside a jitted train step with its hops visible in the exported trace.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu.comm as dist
from deepspeed_tpu import collectives, telemetry
from deepspeed_tpu.collectives import codecs as codecs_mod
from deepspeed_tpu.collectives import overlap, selector
from deepspeed_tpu.utils.compat import shard_map

ALGS = ("ring", "bidir", "rhd", "ring2d")
CODECS = ("none", "fp32", "bf16", "int8", "fp8")
BLOCK = 32


@pytest.fixture
def mesh8():
    devs = jax.devices()[:8]
    return Mesh(np.array(devs), ("dp",))


@pytest.fixture(autouse=True)
def _reset_selector():
    selector.configure()
    yield
    selector.configure()


def _run(mesh, f, *xs, in_specs=None, out_specs=None):
    in_specs = in_specs if in_specs is not None else tuple(P("dp") for _ in xs)
    out_specs = out_specs if out_specs is not None else P("dp")
    return jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))(*xs)


def _int_payload(shape, seed=0):
    """Integer-valued fp32: every summation order is exact, so passthrough
    codecs can be checked bit-level even through reductions."""
    return jnp.asarray(np.random.default_rng(seed).integers(-8, 9, shape), jnp.float32)


# ------------------------------------------------------- algorithm x codec


@pytest.mark.parametrize("alg", ALGS)
@pytest.mark.parametrize("codec", CODECS)
def test_all_reduce_matrix_vs_lax(mesh8, alg, codec):
    x = _int_payload((8, 96 + 7))  # 103: not divisible by 8 -> padding path

    def f(v):
        return collectives.all_reduce(v[0], "dp", algorithm=alg, codec=codec,
                                      block_size=BLOCK)[None]

    out = np.asarray(_run(mesh8, f, x)).reshape(8, -1)
    expected = np.tile(np.asarray(x).sum(0, keepdims=True), (8, 1))
    if codec in ("none", "fp32"):
        np.testing.assert_array_equal(out, expected)
    elif codec == "bf16":
        np.testing.assert_allclose(out, expected, rtol=0.05, atol=1.0)
    else:  # int8 / fp8: blockwise-quantized partial sums
        scale = np.abs(expected).max() + 1e-9
        assert np.abs(out - expected).max() / scale < 0.15, codec


@pytest.mark.parametrize("alg", ("ring", "bidir", "rhd"))
@pytest.mark.parametrize("codec", CODECS)
def test_all_gather_matrix_vs_lax(mesh8, alg, codec):
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 37)).astype(jnp.float32)

    def f(v):
        return collectives.all_gather(v[0], "dp", algorithm=alg, codec=codec,
                                      block_size=BLOCK)[None]

    out = np.asarray(_run(mesh8, f, x))[0].reshape(8, 37)
    expected = np.asarray(
        jax.jit(shard_map(lambda v: jax.lax.all_gather(v[0], "dp")[None],
                          mesh=mesh8, in_specs=P("dp"), out_specs=P("dp"),
                          check_vma=False))(x))[0]
    if codec in ("none", "fp32"):
        np.testing.assert_array_equal(out, expected)  # pure data movement
    elif codec == "bf16":
        np.testing.assert_allclose(out, expected, rtol=0.01, atol=0.01)
    else:  # encode-once forwarding: ONE quantization regardless of hops
        scale = np.abs(expected).max() + 1e-9
        # int8: 1/254 of block max; fp8 E4M3: ~2^-3 relative (3 mantissa bits)
        tol = 0.01 if codec == "int8" else 0.05
        assert np.abs(out - expected).max() / scale < tol, codec


@pytest.mark.parametrize("alg", ("ring", "bidir", "rhd"))
@pytest.mark.parametrize("codec", ("none", "int8"))
def test_reduce_scatter_matrix_vs_lax(mesh8, alg, codec):
    x = _int_payload((8, 96), seed=2)  # 96 = 8 * 12

    def f(v):
        return collectives.reduce_scatter(v[0], "dp", algorithm=alg, codec=codec,
                                          block_size=BLOCK)[None]

    out = np.asarray(_run(mesh8, f, x)).reshape(8, 12)
    expected = np.asarray(x).sum(0).reshape(8, 12)
    if codec == "none":
        np.testing.assert_array_equal(out, expected)
    else:
        scale = np.abs(expected).max() + 1e-9
        assert np.abs(out - expected).max() / scale < 0.15


@pytest.mark.parametrize("alg", ALGS)
def test_lossy_all_reduce_ranks_agree(mesh8, alg):
    """Every rank must end with IDENTICAL bytes after a lossy all-reduce —
    the sender's own block goes through the same encode/decode as its
    peers' copies, or data-parallel replicas silently drift apart."""
    x = jax.random.normal(jax.random.PRNGKey(12), (8, 96)).astype(jnp.float32)
    out = np.asarray(_run(
        mesh8, lambda v: collectives.all_reduce(v[0], "dp", algorithm=alg,
                                                codec="int8", block_size=32)[None],
        x)).reshape(8, -1)
    for r in range(1, 8):
        np.testing.assert_array_equal(out[r], out[0], err_msg=alg)


def test_bf16_all_reduce_accumulates_fp32(mesh8):
    """Partial sums must carry fp32 through the hop chain: a bf16
    accumulator would round every hop, drifting past lax.psum's error as
    the world grows."""
    x = (jax.random.normal(jax.random.PRNGKey(9), (8, 1024)) * 3).astype(jnp.bfloat16)
    ref = np.asarray(x).astype(np.float64).sum(0)
    lax_err = np.abs(np.asarray(_run(
        mesh8, lambda v: jax.lax.psum(v[0], "dp")[None], x))[0].astype(np.float64)
        - ref).max()
    for alg in ALGS:
        got = np.asarray(_run(
            mesh8, lambda v, a=alg: collectives.all_reduce(v[0], "dp", algorithm=a)[None],
            x))[0].astype(np.float64)
        assert np.abs(got - ref).max() <= lax_err + 1e-9, alg


def test_reduce_scatter_rejects_non_divisible(mesh8):
    x = jnp.ones((8, 97), jnp.float32)
    with pytest.raises(ValueError, match="not divisible"):
        _run(mesh8, lambda v: collectives.reduce_scatter(v[0], "dp")[None], x)


def test_codec_block_not_dividing_chunk(mesh8):
    """Chunk length 13 with block 32: the codec pads each row internally and
    strips it — output length must survive exactly."""
    x = _int_payload((8, 8 * 13), seed=3)
    out = np.asarray(_run(
        mesh8,
        lambda v: collectives.all_reduce(v[0], "dp", algorithm="ring",
                                         codec="int8", block_size=32)[None],
        x)).reshape(8, -1)
    expected = np.tile(np.asarray(x).sum(0, keepdims=True), (8, 1))
    assert out.shape == expected.shape
    scale = np.abs(expected).max() + 1e-9
    assert np.abs(out - expected).max() / scale < 0.15


def test_hierarchical_all_reduce_multi_axis():
    """Mesh-axis-factored hierarchy (the hpZ shape): all_reduce over the
    ('fsdp', 'dp') tuple == global sum over both axes."""
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("dp", "fsdp"))
    x = _int_payload((4, 2, 24), seed=4)

    def f(v):
        return collectives.all_reduce(v[0, 0], ("fsdp", "dp"), codec="none")[None, None]

    out = np.asarray(_run(
        mesh, f, x, in_specs=(P("dp", "fsdp"),), out_specs=P("dp", "fsdp")))
    expected = np.asarray(x).sum((0, 1))
    for u in range(4):
        for v in range(2):
            np.testing.assert_array_equal(out[u, v], expected)


def test_codec_roundtrip_all():
    """encode_rows/decode_rows invariants for every registered codec."""
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 45)).astype(jnp.float32)
    for name in CODECS:
        c = codecs_mod.get_codec(name, 16)
        back = np.asarray(c.decode_rows(c.encode_rows(x), 45, jnp.float32))
        assert back.shape == (3, 45)
        tol = 0.0 if name in ("none", "fp32") else 0.2
        assert np.abs(back - np.asarray(x)).max() <= tol + 1e-6, name
    with pytest.raises(ValueError, match="unknown codec"):
        codecs_mod.get_codec("int3")


# ------------------------------------------------------------ facade wiring


def test_facade_default_is_lax_baseline(mesh8):
    """No algorithm/codec arguments -> byte-identical lax lowering (the
    subsystem must be invisible until asked for)."""
    x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)
    out = _run(mesh8, lambda v: dist.all_reduce(v, "dp"), x)
    np.testing.assert_array_equal(
        np.asarray(out), np.tile(np.asarray(x).sum(0, keepdims=True), (8, 1)))


def test_facade_auto_consults_selector(mesh8):
    selector.configure(codecs=("none",))
    before = selector.cache_info()["misses"]
    x = jnp.ones((8, 64), jnp.float32)
    _run(mesh8, lambda v: dist.all_reduce(v[0], "dp", algorithm="auto")[None], x)
    info = selector.cache_info()
    assert info["misses"] == before + 1 and info["entries"] >= 1


# ---------------------------------------------------------------- selector


def test_forced_codec_bypasses_lax_floor():
    """An explicit codec is a quantization request the native lowering
    cannot serve: the small-payload lax floor must not swallow it."""
    d = selector.select("all_reduce", 1024, 8, codec="int8")
    assert d.algorithm != "lax" and d.codec == "int8"
    # ...while un-forced tiny queries still floor to lax
    assert selector.select("all_reduce", 1024, 8).algorithm == "lax"


def test_config_concrete_algorithm_with_auto_codec():
    """codec 'auto' + a concrete algorithm: the selector still picks the
    wire among the configured candidates (here int8 for a big payload,
    exact under min_quant_bytes)."""
    selector.configure(codecs=("none", "int8"))
    assert selector.pick_codec("all_reduce", 1 << 22, 8, "ring2d") == "int8"
    assert selector.pick_codec("all_reduce", 1 << 10, 8, "ring2d") == "none"


def test_selector_caches_repeated_queries():
    d1 = selector.select("all_reduce", 1 << 20, 8)
    d2 = selector.select("all_reduce", 1 << 20, 8)
    assert d1 is d2  # the cached Decision object itself
    info = selector.cache_info()
    assert info["hits"] >= 1 and info["entries"] == 1
    # a different bytes bucket is a fresh decision
    d3 = selector.select("all_reduce", 1 << 24, 8)
    assert d3 is not d1 and selector.cache_info()["entries"] == 2


def test_selector_model_latency_vs_bandwidth_regimes():
    """Alpha-beta model sanity. Exact-wire candidates can never beat the
    native baseline (same bytes + hop latency => lax). Quantized routing:
    small payloads go latency-optimal (rhd, log2(n) hops); huge payloads
    prefer a bandwidth-optimal ring variant."""
    selector.configure(alpha_us=5.0, beta_us_per_mb=10.0, codecs=("none",))
    assert selector.select("all_reduce", 16, 8).algorithm == "lax"  # floor
    assert selector.select("all_reduce", 1 << 28, 8).algorithm == "lax"  # no wire win
    selector.configure(alpha_us=5.0, beta_us_per_mb=10.0)
    small = selector.select("all_reduce", 1 << 13, 8, codec="int8")
    large = selector.select("all_reduce", 1 << 28, 8, codec="int8")
    assert small.algorithm == "rhd", small
    assert large.algorithm in ("ring", "bidir", "ring2d"), large
    # non-power-of-two world can never pick rhd
    odd = selector.select("all_reduce", 1 << 13, 6, codec="int8")
    assert odd.algorithm != "rhd"


def test_selector_all_lossy_codecs_small_payload():
    """codecs=["int8"] (no exact entry) + a payload under min_quant_bytes
    must fall back to the exact wire, not crash with an empty candidate
    set."""
    selector.configure(codecs=("int8",), min_quant_bytes=1 << 16)
    d = selector.select("all_reduce", 1024, 8)
    assert d.codec == "none"
    big = selector.select("all_reduce", 1 << 22, 8)
    assert big.codec == "int8"


def test_facade_config_default_routing(mesh8):
    """The collectives config block's algorithm/codec become the facade
    default: a plain dist.all_reduce call (no arguments) routes through the
    configured algorithm — and reverts to lax when unset."""
    selector.configure(facade_algorithm="ring", facade_codec="int8")
    tracer = telemetry.configure(enabled=True)
    tracer.reset()
    try:
        x = _int_payload((8, 64), seed=11)
        out = _run(mesh8, lambda v: dist.all_reduce(v[0], "dp")[None], x)
        expected = np.tile(np.asarray(x).sum(0, keepdims=True), (8, 1))
        scale = np.abs(expected).max() + 1e-9
        assert np.abs(np.asarray(out).reshape(8, -1) - expected).max() / scale < 0.15
        facade = next(e for e in tracer.events() if e.get("name") == "comm:all_reduce_sum")
        assert facade["args"]["algorithm"] == "ring"
        assert facade["args"]["codec"] == "int8"
        # unset -> plain lax lowering again, no routing tags
        selector.configure()
        tracer.reset()
        _run(mesh8, lambda v: dist.all_reduce(v[0], "dp")[None], x)
        facade = next(e for e in tracer.events() if e.get("name") == "comm:all_reduce_sum")
        assert "algorithm" not in facade.get("args", {})
    finally:
        telemetry.configure(enabled=False)


def test_facade_default_skips_unsupported_shapes(mesh8):
    """Default-routed calls must stay on the lax lowering for max/min
    reductions and non-float payloads (the algorithmic path cannot serve
    them); explicit requests surface the library's own error instead."""
    selector.configure(facade_algorithm="auto", facade_codec="int8",
                       codecs=("none", "int8"))
    x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)
    out = _run(mesh8, lambda v: dist.all_reduce(v, "dp", op="max"), x)
    np.testing.assert_array_equal(
        np.asarray(out), np.tile(np.asarray(x).max(0, keepdims=True), (8, 1)))
    # int payloads: excluded from default routing (native lowering, exact)
    xi = jnp.arange(16, dtype=jnp.int32).reshape(8, 2)
    gi = _run(mesh8, lambda v: dist.all_gather(v[0], "dp")[None], xi,
              in_specs=(P("dp"),))
    np.testing.assert_array_equal(np.asarray(gi)[0].reshape(8, 2), np.asarray(xi))
    with pytest.raises(ValueError, match="unsupported by algorithmic"):
        _run(mesh8, lambda v: dist.all_reduce(v, "dp", op="max", algorithm="ring"), x)


def test_engine_disabled_resets_facade_defaults(mesh8):
    """A previously-installed facade default must not leak into an engine
    constructed with collectives disabled (the config block's 'disabled =>
    unchanged program' promise)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, causal_lm_spec

    selector.configure(facade_algorithm="ring2d", facade_codec="int8")
    tc = TransformerConfig(vocab_size=32, hidden_size=16, intermediate_size=32,
                           num_layers=1, num_heads=2, max_seq_len=16)
    deepspeed_tpu.initialize(
        model=causal_lm_spec(tc, example_seq_len=8),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "steps_per_print": 10_000})
    assert selector.get_config().facade_algorithm is None


def test_error_feedback_requires_ring():
    with pytest.raises(ValueError, match="ring"):
        collectives.reduce_scatter(jnp.ones((8, 8)), "dp", algorithm="rhd",
                                   err=jnp.zeros((8, 8)))


def test_selector_explicit_model_mode_ignores_table(tmp_path):
    table = [{"op": "all_reduce", "world": 8, "size_mb": 1.0,
              "algorithm": "ring2d", "codec": "int8", "latency_ms": 0.5}]
    path = tmp_path / "table.json"
    path.write_text(json.dumps(table))
    selector.configure(mode="model", decision_table=str(path))
    d = selector.select("all_reduce", 1_000_000, 8)
    assert d.source == "model"


def test_selector_measured_mode_uses_decision_table(tmp_path):
    table = [
        {"op": "all_reduce", "world": 8, "size_mb": 1.0, "algorithm": "ring2d",
         "codec": "int8", "latency_ms": 0.5},
        {"op": "all_reduce", "world": 8, "size_mb": 1.0, "algorithm": "ring",
         "codec": "none", "latency_ms": 2.0},
    ]
    path = tmp_path / "table.json"
    path.write_text(json.dumps(table))
    # measured rows only rank codecs the config authorizes
    selector.configure(decision_table=str(path), codecs=("none", "int8"))
    d = selector.select("all_reduce", 1_000_000, 8)
    assert d.source == "measured" and d.algorithm == "ring2d" and d.codec == "int8"
    # ...and never a lossy wire under min_quant_bytes (model-path parity)
    small = selector.select("all_reduce", 1024, 8)
    assert small.codec == "none"
    # ops absent from the table fall back to the model
    d2 = selector.select("all_gather", 1_000_000, 8)
    assert d2.source == "model"


def test_measured_lax_decision_stays_on_lax_lowering(mesh8, tmp_path):
    """A measured 'don't bother' verdict (algorithm='lax' row wins) must
    fall back to the plain lowering through the facade, not crash the
    algorithmic dispatch."""
    table = [{"op": "all_reduce", "world": 8, "size_mb": 0.001,
              "algorithm": "lax", "codec": "none", "latency_ms": 0.1},
             {"op": "all_reduce", "world": 8, "size_mb": 0.001,
              "algorithm": "ring", "codec": "none", "latency_ms": 9.9}]
    path = tmp_path / "lax.json"
    path.write_text(json.dumps(table))
    selector.configure(decision_table=str(path))
    assert selector.select("all_reduce", 1000, 8).algorithm == "lax"
    x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)
    out = _run(mesh8, lambda v: dist.all_reduce(v, "dp", algorithm="auto"), x)
    np.testing.assert_array_equal(
        np.asarray(out), np.tile(np.asarray(x).sum(0, keepdims=True), (8, 1)))


def test_benchmark_sweep_feeds_selector(tmp_path):
    """--sweep emits rows the selector's measured mode consumes."""
    from deepspeed_tpu.comm.benchmark import run_sweep

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("dp",))
    rows = run_sweep(ops=("all_reduce",), sizes_mb=[0.01], mesh=mesh,
                     algorithms=["lax", "ring"], codecs=["none"],
                     iters=2, warmup=1)
    assert {r["algorithm"] for r in rows} == {"lax", "ring"}
    assert all(r["latency_ms"] > 0 and r["world"] == 4 for r in rows)
    path = tmp_path / "sweep.json"
    path.write_text(json.dumps(rows))
    selector.configure(decision_table=str(path))
    d = selector.select("all_reduce", 10_000, 4)
    assert d.source == "measured"
    assert d.algorithm in ("lax", "ring")


# ------------------------------------------------------------ error feedback


def test_error_feedback_average_converges(mesh8):
    """LoCo property: with the residual carried across calls, the RUNNING
    AVERAGE of int8 reduce-scatter outputs converges toward the exact sum
    (the compensation telescopes); without EF the quantization bias is
    constant and the average never improves."""
    n, L = 8, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (n, n * L)).astype(jnp.float32) * 3.0

    def f_ef(v, err):
        out, new_err = collectives.reduce_scatter(
            v[0], "dp", algorithm="ring", codec="int8", block_size=32, err=err[0])
        return out[None], new_err[None]

    step = jax.jit(shard_map(f_ef, mesh=mesh8, in_specs=(P("dp"), P("dp")),
                             out_specs=(P("dp"), P("dp")), check_vma=False))

    def f_ne(v):
        return collectives.reduce_scatter(
            v[0], "dp", algorithm="ring", codec="int8", block_size=32)[None]

    step_ne = jax.jit(shard_map(f_ne, mesh=mesh8, in_specs=P("dp"),
                                out_specs=P("dp"), check_vma=False))

    exact = np.asarray(x).sum(0).reshape(n, L)
    err = jnp.zeros((n, n, L), jnp.float32)
    T = 16
    run_ef = np.zeros_like(exact)
    first_err = None
    for t in range(1, T + 1):
        out, err = step(x, err)
        run_ef += np.asarray(out).reshape(n, L)
        if t == 1:
            first_err = np.abs(run_ef - exact).max()
    avg_err = np.abs(run_ef / T - exact).max()
    ne_err = np.abs(np.asarray(step_ne(x)).reshape(n, L) - exact).max()
    assert avg_err < first_err / 4, (avg_err, first_err)
    assert avg_err < ne_err / 4, (avg_err, ne_err)


# ---------------------------------------------------------------- overlap


def test_double_buffered_matches_plain():
    xs = [jnp.arange(4, dtype=jnp.float32) + k for k in range(5)]
    got = overlap.double_buffered(xs, comm_fn=lambda v: v * 2, compute_fn=lambda v: v + 1)
    for g, x in zip(got, xs):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x) * 2 + 1)
    assert overlap.double_buffered([], lambda v: v, lambda v: v) == []


def test_double_buffered_scan_matches_plain():
    chunks = jnp.arange(24, dtype=jnp.float32).reshape(6, 4)
    got = jax.jit(lambda c: overlap.double_buffered_scan(
        c, comm_fn=lambda v: v * 3, compute_fn=lambda v: v - 1))(chunks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(chunks) * 3 - 1)
    one = overlap.double_buffered_scan(chunks[:1], lambda v: v * 3, lambda v: v - 1)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(chunks[:1]) * 3 - 1)


def test_zeropp_gather_overlap_chunks_equivalent(mesh8):
    """The chunked double-buffered qwZ gather is numerically identical to
    the monolithic one (same codec, same blocks — only the schedule moves)."""
    from deepspeed_tpu.parallel.zeropp import _int8_all_gather_dim

    x = jax.random.normal(jax.random.PRNGKey(7), (8, 16, 6)).astype(jnp.float32)

    def f(chunks):
        def body(v):
            return _int8_all_gather_dim(v[0], 0, "dp", 32, chunks)[None]
        return body

    base = np.asarray(_run(mesh8, f(1), x))
    for chunks in (2, 4):
        got = np.asarray(_run(mesh8, f(chunks), x))
        np.testing.assert_array_equal(got, base)


# ----------------------------------------------- end-to-end + telemetry


def test_ring2d_int8_train_step_with_hop_spans(mesh8, tmp_path):
    """Acceptance: comm.all_reduce(algorithm='ring2d', codec='int8') inside
    a jitted train step, hop spans + the routing decision in the trace."""
    tracer = telemetry.configure(enabled=True, trace_path=str(tmp_path / "trace.json"))
    tracer.reset()
    try:
        w0 = jnp.zeros((64,), jnp.float32)
        x = _int_payload((8, 64), seed=8)

        def local_step(w, batch):
            # grad of a toy quadratic loss; the grad all-reduce is the
            # algorithmic quantized collective under test
            g = jax.grad(lambda wv: jnp.sum((batch[0] - wv) ** 2))(w)
            g = dist.all_reduce(g, "dp", op="mean", algorithm="ring2d",
                                codec="int8", block_size=32)
            return w - 0.1 * g

        step = jax.jit(shard_map(
            local_step, mesh=mesh8, in_specs=(P(), P("dp")), out_specs=P(),
            check_vma=False))
        w1 = step(w0, x)
        assert np.isfinite(np.asarray(w1)).all()
        # one traced program: facade span tagged with the routing, per-hop
        # coll: spans, and the underlying ppermute transfers
        names = [e.get("name") for e in tracer.events()]
        assert any(n == "comm:all_reduce_mean" for n in names)
        facade = next(e for e in tracer.events() if e.get("name") == "comm:all_reduce_mean")
        assert facade["args"]["algorithm"] == "ring2d"
        assert facade["args"]["codec"] == "int8"
        hop_names = {n for n in names if n and n.startswith("coll:all_reduce:ring2d")}
        assert {"coll:all_reduce:ring2d/intra-rs", "coll:all_reduce:ring2d/inter-rs",
                "coll:all_reduce:ring2d/inter-ag", "coll:all_reduce:ring2d/intra-ag"
                } <= hop_names, hop_names
        assert any(n == "comm:ppermute" for n in names)
        # the exported chrome trace holds the same hop spans
        telemetry.export_chrome_trace(str(tmp_path / "trace.json"))
        trace = json.loads((tmp_path / "trace.json").read_text())
        tnames = {ev.get("name") for ev in trace.get("traceEvents", [])}
        assert "coll:all_reduce:ring2d/inter-rs" in tnames
    finally:
        telemetry.configure(enabled=False)


from jax.extend.core import ClosedJaxpr, Jaxpr  # noqa: E402


def _count_primitives(jaxpr, counts=None):
    """Recursive primitive census of a (closed) jaxpr — the structural
    evidence for 'one fused program per hop'."""
    counts = counts if counts is not None else {}
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            for j in jax.tree_util.tree_leaves(
                    v, is_leaf=lambda x: isinstance(x, (Jaxpr, ClosedJaxpr))):
                if isinstance(j, ClosedJaxpr):
                    _count_primitives(j.jaxpr, counts)
                elif isinstance(j, Jaxpr):
                    _count_primitives(j, counts)
    return counts


# ------------------------------------------------- pallas remote-DMA backend


@pytest.mark.parametrize("alg", ("pallas_ring", "pallas_ring2d"))
def test_pallas_all_reduce_bit_identical_vs_ring(mesh8, alg):
    """Interpret-mode equivalence: exact-wire pallas all-reduce over remote
    DMA hops is BIT-identical to the ppermute ring (and to the true sum —
    integer payloads make every summation order exact). 103 columns is the
    non-divisible chunk-padding path."""
    x = _int_payload((8, 103), seed=21)

    def f(alg):
        return lambda v: collectives.all_reduce(v[0], "dp", algorithm=alg)[None]

    got = np.asarray(_run(mesh8, f(alg), x)).reshape(8, -1)
    ref = np.asarray(_run(mesh8, f("ring"), x)).reshape(8, -1)
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(
        got, np.tile(np.asarray(x).sum(0, keepdims=True), (8, 1)))


def test_pallas_all_gather_and_reduce_scatter_match_ring(mesh8):
    """pallas_ring selectable through the comm FACADE for gather/scatter
    too (acceptance), bit-identical to the ppermute ring."""
    x = _int_payload((8, 37), seed=22)
    g = np.asarray(_run(
        mesh8, lambda v: dist.all_gather(v[0], "dp", algorithm="pallas_ring")[None], x))
    gr = np.asarray(_run(
        mesh8, lambda v: collectives.all_gather(v[0], "dp", algorithm="ring")[None], x))
    np.testing.assert_array_equal(g, gr)
    xs = _int_payload((8, 96), seed=23)
    rs = np.asarray(_run(
        mesh8, lambda v: dist.reduce_scatter(v[0], "dp", algorithm="pallas_ring")[None],
        xs)).reshape(8, 12)
    np.testing.assert_array_equal(rs, np.asarray(xs).sum(0).reshape(8, 12))


@pytest.mark.parametrize("alg", ("pallas_ring", "pallas_ring2d"))
@pytest.mark.parametrize("codec", ("int8", "fp8"))
def test_pallas_fused_quant_all_reduce_bounded_error(mesh8, alg, codec):
    """The fused dequant-accumulate-requant hop must track the UNFUSED wire
    codec path (same block math via ops.quant, same fp32 accumulation) and
    stay within the quantization tolerance of the exact sum. 103 columns
    exercises both the chunk padding and the codec block padding."""
    x = (jax.random.normal(jax.random.PRNGKey(24), (8, 103)) * 3).astype(jnp.float32)

    def f(a, c):
        return lambda v: collectives.all_reduce(v[0], "dp", algorithm=a,
                                                codec=c, block_size=32)[None]

    fused = np.asarray(_run(mesh8, f(alg, codec), x)).reshape(8, -1)
    base_alg = "ring" if alg == "pallas_ring" else "ring2d"
    unfused = np.asarray(_run(mesh8, f(base_alg, codec), x)).reshape(8, -1)
    exact = np.tile(np.asarray(x).sum(0, keepdims=True), (8, 1))
    scale = np.abs(exact).max() + 1e-9
    assert np.abs(fused - exact).max() / scale < 0.15, (alg, codec)
    assert np.abs(fused - unfused).max() / scale < 0.05, (alg, codec)
    # every rank ends with identical bytes (replica-drift guard)
    for r in range(1, 8):
        np.testing.assert_array_equal(fused[r], fused[0], err_msg=alg)


def test_pallas_fused_hop_is_single_program(mesh8):
    """Structural acceptance: the fused quantized reduce-scatter runs ONE
    pallas program per hop — no collective-permutes, no separate quant
    programs between hops — where the ppermute+int8 path runs 2 ppermutes
    per hop (wire values + scales) around XLA-side codec math."""
    from deepspeed_tpu.utils.compat import shard_map as smap

    x = jnp.ones((8, 96), jnp.float32)

    def traced(alg):
        def body(v):
            return collectives.reduce_scatter(v[0], "dp", algorithm=alg,
                                              codec="int8", block_size=32)[None]
        return jax.make_jaxpr(smap(body, mesh=mesh8, in_specs=P("dp"),
                                   out_specs=P("dp"), check_vma=False))(x)

    fused = _count_primitives(traced("pallas_ring").jaxpr)
    assert fused.get("pallas_call", 0) == 7  # n-1 hops, one program each
    assert fused.get("ppermute", 0) == 0
    unfused = _count_primitives(traced("ring").jaxpr)
    assert unfused.get("pallas_call", 0) == 0  # CPU dispatch: xla codec math
    assert unfused.get("ppermute", 0) == 2 * 7  # q + scales per hop


def test_pallas_exact_wire_hops_are_remote_dma(mesh8):
    """Exact codecs don't fuse, but their hops still ride remote DMA: one
    pallas program per hop (the wire's q leaf; zero-size scale placeholders
    skip), zero ppermutes."""
    from deepspeed_tpu.utils.compat import shard_map as smap

    x = jnp.ones((8, 96), jnp.float32)

    def body(v):
        return collectives.all_gather(v[0], "dp", algorithm="pallas_ring")[None]

    jaxpr = jax.make_jaxpr(smap(body, mesh=mesh8, in_specs=P("dp"),
                                out_specs=P("dp"), check_vma=False))(x)
    counts = _count_primitives(jaxpr.jaxpr)
    assert counts.get("pallas_call", 0) == 7
    assert counts.get("ppermute", 0) == 0


def test_pallas_error_feedback_still_requires_ring():
    with pytest.raises(ValueError, match="ring"):
        collectives.reduce_scatter(jnp.ones((8, 8)), "dp",
                                   algorithm="pallas_ring",
                                   err=jnp.zeros((8, 8)))


def test_pallas_train_step_smoke_with_hop_spans(mesh8, tmp_path):
    """Acceptance: comm.all_reduce(algorithm='pallas_ring', codec='int8')
    inside a jitted train step — fused hop spans (tagged backend=pallas,
    fused) in the exported trace, comm:remote_dma transfers instead of
    comm:ppermute."""
    tracer = telemetry.configure(enabled=True, trace_path=str(tmp_path / "t.json"))
    tracer.reset()
    try:
        w0 = jnp.zeros((64,), jnp.float32)
        x = _int_payload((8, 64), seed=25)

        def local_step(w, batch):
            g = jax.grad(lambda wv: jnp.sum((batch[0] - wv) ** 2))(w)
            g = dist.all_reduce(g, "dp", op="mean", algorithm="pallas_ring",
                                codec="int8", block_size=32)
            return w - 0.1 * g

        step = jax.jit(shard_map(
            local_step, mesh=mesh8, in_specs=(P(), P("dp")), out_specs=P(),
            check_vma=False))
        assert np.isfinite(np.asarray(step(w0, x))).all()
        events = tracer.events()
        names = [e.get("name") for e in events]
        facade = next(e for e in events if e.get("name") == "comm:all_reduce_mean")
        assert facade["args"]["algorithm"] == "pallas_ring"
        assert facade["args"]["codec"] == "int8"
        # fused RS hops: coll: spans tagged with the backend and the fusion
        rs_hops = [e for e in events
                   if e.get("name") == "coll:reduce_scatter:pallas_ring"]
        assert len(rs_hops) == 7 and all(
            e["args"]["backend"] == "pallas" and e["args"]["fused"] for e in rs_hops)
        # AG relay hops keep their schedule label, backend-tagged
        ag_hops = [e for e in events if e.get("name") == "coll:all_gather:ring"]
        assert len(ag_hops) == 7 and all(
            e["args"]["backend"] == "pallas" for e in ag_hops)
        assert any(n == "comm:remote_dma" for n in names)
        assert not any(n == "comm:ppermute" for n in names)
        telemetry.export_chrome_trace(str(tmp_path / "t.json"))
        trace = json.loads((tmp_path / "t.json").read_text())
        tnames = {ev.get("name") for ev in trace.get("traceEvents", [])}
        assert "coll:reduce_scatter:pallas_ring" in tnames
        assert "comm:remote_dma" in tnames
    finally:
        telemetry.configure(enabled=False)


def test_pallas_multi_axis_tuple_rides_hierarchy():
    """pallas_ring over an axis tuple runs the mesh-axis-factored hierarchy.
    The 0.4.x Pallas INTERPRETER cannot discharge remote DMA on multi-axis
    shardings, so on this CPU mesh the hops fall back to ppermute with a
    logged note (compiled TPU runs keep the kernels) — the schedule and
    numerics are what this test pins."""
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("dp", "fsdp"))
    x = _int_payload((4, 2, 24), seed=26)

    def f(v):
        return collectives.all_reduce(v[0, 0], ("fsdp", "dp"),
                                      algorithm="pallas_ring")[None, None]

    out = np.asarray(_run(mesh, f, x, in_specs=(P("dp", "fsdp"),),
                          out_specs=P("dp", "fsdp")))
    expected = np.asarray(x).sum((0, 1))
    for u in range(4):
        for v in range(2):
            np.testing.assert_array_equal(out[u, v], expected)


def test_selector_never_picks_pallas_off_tpu():
    """Model mode must not route remote-DMA algorithms where the backend
    cannot run them compiled (interpret mode is a test vehicle, not a
    transport); monkeypatched availability admits them — and the cache key
    carries the backend so the two regimes never share decisions."""
    from deepspeed_tpu.collectives import pallas_backend

    selector.configure(codecs=("none", "int8"))
    d = selector.select("all_reduce", 1 << 24, 8, codec="int8")
    assert not d.algorithm.startswith("pallas_")


def test_selector_pallas_available_changes_model_and_cache(monkeypatch):
    from deepspeed_tpu.collectives import pallas_backend

    selector.configure(codecs=("none", "int8"), alpha_us=50.0,
                       beta_us_per_mb=10.0)
    before = selector.select("all_reduce", 1 << 24, 8, codec="int8")
    monkeypatch.setattr(pallas_backend, "available", lambda: True)
    after = selector.select("all_reduce", 1 << 24, 8, codec="int8")
    # same query, different backend token => a FRESH cache entry, and with
    # the alpha discount the pallas carrier wins at this hop-heavy regime
    assert selector.cache_info()["entries"] == 2
    assert after.algorithm.startswith("pallas_"), after
    assert not before.algorithm.startswith("pallas_")


def test_measured_table_backend_stamps(monkeypatch, tmp_path):
    """A ppermute-era table (no backend stamp) must never route a pallas
    algorithm even when the backend is available; correctly stamped pallas
    rows route only when it is."""
    from deepspeed_tpu.collectives import pallas_backend

    table = [
        {"op": "all_reduce", "world": 8, "size_mb": 1.0, "algorithm": "pallas_ring",
         "codec": "none", "latency_ms": 0.1},  # mis-stamped: no backend field
        {"op": "all_reduce", "world": 8, "size_mb": 1.0, "algorithm": "ring",
         "codec": "none", "latency_ms": 2.0, "backend": "ppermute"},
    ]
    path = tmp_path / "t.json"
    path.write_text(json.dumps(table))
    monkeypatch.setattr(pallas_backend, "available", lambda: True)
    selector.configure(decision_table=str(path))
    d = selector.select("all_reduce", 1_000_000, 8)
    assert d.source == "measured" and d.algorithm == "ring"
    # properly stamped pallas rows win when available...
    table[0]["backend"] = "pallas"
    path.write_text(json.dumps(table))
    selector.configure(decision_table=str(path))
    assert selector.select("all_reduce", 1_000_000, 8).algorithm == "pallas_ring"
    # ...and are invisible when the backend is not usable in this process
    monkeypatch.setattr(pallas_backend, "available", lambda: False)
    selector.configure(decision_table=str(path))
    assert selector.select("all_reduce", 1_000_000, 8).algorithm == "ring"


def test_sweep_skips_pallas_off_tpu(caplog):
    """--sweep with pallas algorithms on a CPU box: logged skip, no crash,
    no interpret-mode rows in the table; surviving rows carry backend
    stamps."""
    import logging

    from deepspeed_tpu.comm.benchmark import run_sweep

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    lg = logging.getLogger("deepspeed_tpu")
    prev = lg.propagate
    lg.propagate = True  # the repo logger defaults propagate=False; caplog
    try:
        with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
            rows = run_sweep(ops=("all_reduce",), sizes_mb=[0.01], mesh=mesh,
                             algorithms=["lax", "ring", "pallas_ring"],
                             codecs=["none"], iters=1, warmup=1)
    finally:
        lg.propagate = prev
    assert any("skipping" in r.message and "pallas_ring" in r.message
               for r in caplog.records)
    algs = {r["algorithm"] for r in rows}
    assert algs == {"lax", "ring"}
    assert {r["backend"] for r in rows} == {"xla", "ppermute"}


def test_selector_decision_emits_telemetry_instant():
    tracer = telemetry.configure(enabled=True)
    tracer.reset()
    try:
        selector.configure(codecs=("none",))
        selector.select("all_gather", 123456, 8)
        evs = [e for e in tracer.events() if e.get("name") == "coll:select"]
        assert evs and evs[0]["args"]["op"] == "all_gather"
        assert evs[0]["args"]["algorithm"] in ALGS + ("lax",)
    finally:
        telemetry.configure(enabled=False)


# --------------------------------------------------------------- all-to-all
#
# ISSUE 15: the algorithmic library's all_to_all (ring / bidir / ring2d
# schedules, encode-once wire codecs, pallas remote-DMA hops) against the
# ``jax.lax.all_to_all(tiled=True)`` baseline.

A2A_ALGS = ("ring", "bidir", "ring2d")


def _lax_a2a(mesh, x, split=0, concat=0):
    return np.asarray(_run(
        mesh, lambda v: jax.lax.all_to_all(
            v[0], "dp", split_axis=split, concat_axis=concat, tiled=True)[None],
        x))


@pytest.mark.parametrize("alg", A2A_ALGS)
@pytest.mark.parametrize("codec", CODECS)
def test_all_to_all_matrix_vs_lax(mesh8, alg, codec):
    """Pure data movement: passthrough codecs are BIT-identical to the lax
    baseline; lossy wires quantize each destination row exactly once
    (encode-once at the source, the ring2d middle hop relays WIRE bytes),
    so the error bound is one codec roundtrip. 37 columns: the per-row
    length is not a multiple of the codec block (padding path)."""
    x = _int_payload((8, 64, 37), seed=31)

    def f(v):
        return collectives.all_to_all(v[0], "dp", split_axis=0, concat_axis=0,
                                      algorithm=alg, codec=codec,
                                      block_size=BLOCK)[None]

    out = np.asarray(_run(mesh8, f, x))
    expected = _lax_a2a(mesh8, x)
    if codec in ("none", "fp32"):
        np.testing.assert_array_equal(out, expected, err_msg=f"{alg}/{codec}")
    elif codec == "bf16":
        np.testing.assert_allclose(out, expected, rtol=0.01, atol=0.05)
    else:  # int8 / fp8: ONE quantization regardless of relay hops
        scale = np.abs(expected).max() + 1e-9
        tol = 0.01 if codec == "int8" else 0.05
        assert np.abs(out - expected).max() / scale < tol, (alg, codec)
        # own block never crosses a link: stays bit-exact on every rank
        own = np.asarray(x).reshape(8, 8, 8, 37)
        got = out.reshape(8, 8, 8, 37)
        for r in range(8):
            np.testing.assert_array_equal(got[r, r], own[r, r])


@pytest.mark.parametrize("alg", A2A_ALGS)
def test_all_to_all_split_concat_axes(mesh8, alg):
    """lax tiled semantics on distinct split/concat axes (the MoE dispatch
    shape: split experts, concat capacity — and back)."""
    x = _int_payload((8, 16, 8), seed=32)

    def f(split, concat):
        def body(v):
            return collectives.all_to_all(v[0], "dp", split_axis=split,
                                          concat_axis=concat, algorithm=alg)[None]
        return body

    out = np.asarray(_run(mesh8, f(0, 1), x))
    np.testing.assert_array_equal(out, _lax_a2a(mesh8, x, split=0, concat=1))
    out = np.asarray(_run(mesh8, f(1, 0), x))
    np.testing.assert_array_equal(out, _lax_a2a(mesh8, x, split=1, concat=0))


def test_all_to_all_non_divisible_split_raises(mesh8):
    x = jnp.ones((8, 12), jnp.float32)  # 12 % 8 != 0
    with pytest.raises(ValueError, match="not divisible"):
        _run(mesh8, lambda v: collectives.all_to_all(
            v[0], "dp", split_axis=0, concat_axis=0, algorithm="ring")[None], x)


def test_all_to_all_rejects_rhd_and_multi_axis(mesh8):
    with pytest.raises(ValueError, match="recursive-halving"):
        collectives.all_to_all(jnp.ones((8, 8)), "dp", split_axis=0,
                               concat_axis=0, algorithm="rhd")
    with pytest.raises(ValueError, match="one axis"):
        collectives.all_to_all(jnp.ones((8, 8)), ("dp", "tp"), split_axis=0,
                               concat_axis=0, algorithm="ring")
    with pytest.raises(ValueError, match="tiled"):
        dist.all_to_all(jnp.ones((8, 8)), "dp", split_axis=0, concat_axis=0,
                        tiled=False, algorithm="ring")


def test_all_to_all_ring2d_factorization(mesh8):
    """The Big-Send-off sub-ring factored schedule: 8 = 4x2, so the traced
    program carries (a-1)+(b-1) = 4 hop phases instead of ring's 7 — the
    structural evidence the 2D variant actually factors the exchange."""
    from deepspeed_tpu.utils.compat import shard_map as smap

    x = jnp.ones((8, 64), jnp.float32)

    def traced(alg):
        def body(v):
            return collectives.all_to_all(v[0], "dp", split_axis=0,
                                          concat_axis=0, algorithm=alg)[None]
        return jax.make_jaxpr(smap(body, mesh=mesh8, in_specs=P("dp"),
                                   out_specs=P("dp"), check_vma=False))(x)

    ring = _count_primitives(traced("ring").jaxpr)
    two_d = _count_primitives(traced("ring2d").jaxpr)
    assert ring.get("ppermute", 0) == 7
    assert two_d.get("ppermute", 0) == 4  # (4-1) + (2-1)
    # bidir pairs mirror distances: ceil(7/2) = 4 phases, two sends in all
    # but the middle phase -> still 7 row moves
    bidir = _count_primitives(traced("bidir").jaxpr)
    assert bidir.get("ppermute", 0) == 7


def test_all_to_all_pallas_census(mesh8):
    """Acceptance (ISSUE 15): the fused pallas dispatch wire runs ONE
    pallas program per hop — n-1 pallas_calls, ZERO ppermutes — where the
    unfused int8 ring permutes wire values + scales around XLA codec math."""
    from deepspeed_tpu.utils.compat import shard_map as smap

    x = jnp.ones((8, 96), jnp.float32)

    def traced(alg, codec):
        def body(v):
            return collectives.all_to_all(v[0], "dp", split_axis=0,
                                          concat_axis=0, algorithm=alg,
                                          codec=codec, block_size=32)[None]
        return jax.make_jaxpr(smap(body, mesh=mesh8, in_specs=P("dp"),
                                   out_specs=P("dp"), check_vma=False))(x)

    fused = _count_primitives(traced("pallas_ring", "int8").jaxpr)
    assert fused.get("pallas_call", 0) == 7  # n-1 hops, one program each
    assert fused.get("ppermute", 0) == 0
    unfused = _count_primitives(traced("ring", "int8").jaxpr)
    assert unfused.get("pallas_call", 0) == 0
    assert unfused.get("ppermute", 0) == 2 * 7  # q + scales per hop
    exact = _count_primitives(traced("pallas_ring", "none").jaxpr)
    assert exact.get("pallas_call", 0) == 7  # exact wire still remote-DMA
    assert exact.get("ppermute", 0) == 0


@pytest.mark.parametrize("alg,codec", [("pallas_ring", "int8"),
                                       ("pallas_ring", "fp8"),
                                       ("pallas_ring2d", "int8")])
def test_all_to_all_pallas_matches_unfused(mesh8, alg, codec):
    """Interpret-mode equivalence: the fused requantize->DMA->dequant hop
    must track the unfused encode-once wire (same ops.quant block math) —
    and the exact pallas wire must be BIT-identical to lax."""
    x = (jax.random.normal(jax.random.PRNGKey(33), (8, 96)) * 3).astype(jnp.float32)

    def f(a, c):
        return lambda v: collectives.all_to_all(
            v[0], "dp", split_axis=0, concat_axis=0, algorithm=a, codec=c,
            block_size=32)[None]

    fused = np.asarray(_run(mesh8, f(alg, codec), x))
    base = "ring" if alg == "pallas_ring" else "ring2d"
    unfused = np.asarray(_run(mesh8, f(base, codec), x))
    exact = _lax_a2a(mesh8, x)
    scale = np.abs(exact).max() + 1e-9
    tol = 0.02 if codec == "int8" else 0.06  # fp8 E4M3: 3 mantissa bits
    assert np.abs(fused - exact).max() / scale < tol, (alg, codec)
    assert np.abs(fused - unfused).max() / scale < tol / 2, (alg, codec)
    got = np.asarray(_run(mesh8, f("pallas_ring", "none"), x))
    np.testing.assert_array_equal(got, exact)


def test_all_to_all_facade_routing_with_hop_spans(mesh8, tmp_path):
    """Acceptance (ISSUE 15): comm.all_to_all(algorithm='ring',
    codec='int8') routes through the collectives layer with the facade span
    tagged, per-hop coll: spans, and an observatory route signature."""
    from deepspeed_tpu.collectives import observatory as coll_obs

    tracer = telemetry.configure(enabled=True)
    tracer.reset()
    obs = coll_obs.configure(enabled=True, persist=False, refit_every=0,
                             async_compile=False)
    try:
        x = _int_payload((8, 8, 64), seed=34)
        out = _run(mesh8, lambda v: dist.all_to_all(
            v[0], "dp", split_axis=0, concat_axis=0, algorithm="ring",
            codec="int8", block_size=32)[None], x)
        expected = _lax_a2a(mesh8, x)
        scale = np.abs(expected).max() + 1e-9
        assert np.abs(np.asarray(out) - expected).max() / scale < 0.02
        names = [e.get("name") for e in tracer.events()]
        facade = next(e for e in tracer.events()
                      if e.get("name") == "comm:all_to_all")
        assert facade["args"]["algorithm"] == "ring"
        assert facade["args"]["codec"] == "int8"
        assert any(n == "coll:all_to_all:ring" for n in names), names
        routes = obs.routes()
        sig = next(r for r in routes if r.op == "all_to_all")
        assert (sig.algorithm, sig.codec, sig.backend) == ("ring", "int8",
                                                           "ppermute")
        assert sig.hops == 7 and sig.wire_bytes > 0  # n-1 hop census
    finally:
        coll_obs.configure(enabled=False)
        telemetry.configure(enabled=False)


def test_all_to_all_selector_and_measured_routing(tmp_path):
    """Selector coverage for the new op: the model never proposes rhd (no
    recursive-halving form), repeated queries hit the decision cache, and a
    measured decision-table row routes an auto call onto its algorithm."""
    selector.configure(codecs=("none", "int8"))
    d1 = selector.select("all_to_all", 1 << 20, 8)
    assert d1.algorithm != "rhd"
    d2 = selector.select("all_to_all", 1 << 20, 8)
    assert d1 is d2 and selector.cache_info()["hits"] >= 1
    # measured mode: a table row for all_to_all wins over the model
    table = [{"op": "all_to_all", "world": 8, "size_mb": 1.0,
              "algorithm": "ring2d", "codec": "int8", "latency_ms": 0.4},
             {"op": "all_to_all", "world": 8, "size_mb": 1.0,
              "algorithm": "ring", "codec": "none", "latency_ms": 2.0}]
    path = tmp_path / "a2a.json"
    path.write_text(json.dumps(table))
    selector.configure(decision_table=str(path), codecs=("none", "int8"))
    d = selector.select("all_to_all", 1_000_000, 8)
    assert d.source == "measured" and d.algorithm == "ring2d" and d.codec == "int8"


def test_all_to_all_candidate_pairs_exclude_rhd():
    """The sweep/probe enumeration (ONE function, shared) never proposes
    rhd for all_to_all, on any world size."""
    from deepspeed_tpu.comm.benchmark import candidate_pairs

    pairs = candidate_pairs(8, ("none", "int8"), op="all_to_all")
    assert pairs and all(alg != "rhd" for alg, _ in pairs)
    assert ("ring", "int8") in pairs and ("lax", "none") in pairs
    # other ops keep rhd on pow2 worlds (no behavior change)
    assert any(alg == "rhd" for alg, _ in candidate_pairs(8, ("none",)))


def test_all_to_all_sweep_feeds_selector(tmp_path):
    """--sweep covers all_to_all end-to-end: backend-stamped rows the
    measured mode consumes."""
    from deepspeed_tpu.comm.benchmark import run_sweep

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("dp",))
    rows = run_sweep(ops=("all_to_all",), sizes_mb=[0.01], mesh=mesh,
                     algorithms=["lax", "ring"], codecs=["none"],
                     iters=2, warmup=1)
    assert {r["algorithm"] for r in rows} == {"lax", "ring"}
    assert all(r["op"] == "all_to_all" and r["latency_ms"] > 0 for r in rows)
    assert {r["backend"] for r in rows} == {"xla", "ppermute"}
    path = tmp_path / "sweep.json"
    path.write_text(json.dumps(rows))
    selector.configure(decision_table=str(path))
    d = selector.select("all_to_all", 10_000, 4)
    assert d.source == "measured"


def test_qgz_exchange_wire_stays_on_lax(mesh8):
    """The zeropp qgZ destination-shard exchange moves an ALREADY-ENCODED
    wire — a facade default must never route it back through the
    algorithmic/codec path (double quantization)."""
    from deepspeed_tpu.parallel.quant_collectives import exchange_wire

    selector.configure(facade_algorithm="ring", facade_codec="int8")
    tracer = telemetry.configure(enabled=True)
    tracer.reset()
    try:
        x = _int_payload((8, 64), seed=35)
        out = _run(mesh8, lambda v: exchange_wire(v[0], "dp")[None], x)
        np.testing.assert_array_equal(np.asarray(out), _lax_a2a(mesh8, x))
        facade = next(e for e in tracer.events()
                      if e.get("name") == "comm:all_to_all")
        assert "algorithm" not in facade.get("args", {})
    finally:
        telemetry.configure(enabled=False)


def test_all_to_all_facade_default_rhd_falls_back_to_lax(mesh8):
    """A configured facade default the op has NO form of (rhd) must keep
    default-routed all_to_all on the lax lowering — only an explicit rhd
    request surfaces the library's error."""
    selector.configure(facade_algorithm="rhd", facade_codec="int8")
    x = _int_payload((8, 64), seed=36)
    out = np.asarray(_run(
        mesh8, lambda v: dist.all_to_all(v[0], "dp", split_axis=0,
                                         concat_axis=0)[None], x))
    np.testing.assert_array_equal(out, _lax_a2a(mesh8, x))
