"""Comm facade + telemetry + quantized collectives on the CPU mesh.

Mirrors the reference's ``tests/unit/comm`` (collective correctness +
comms-logging) and ``tests/unit/runtime/zero/test_zeropp.py`` (qgZ/qwZ).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from deepspeed_tpu.utils.compat import shard_map

import deepspeed_tpu.comm as dist
from deepspeed_tpu.parallel.quant_collectives import (
    quantized_all_gather,
    quantized_reduce_scatter,
)


@pytest.fixture
def mesh():
    devs = jax.devices()[:4]
    return Mesh(np.array(devs), ("dp",))


@pytest.fixture(params=[2, 4, 8], ids=lambda n: f"world{n}")
def ranks(request):
    """The quantized wires over two, four and eight ranks: a shard's blocks end with the shard at every one."""
    return Mesh(np.array(jax.devices()[:request.param]), ("dp",))


def test_all_reduce_and_logging(mesh):
    dist.comms_logger.configure(enabled=True)
    dist.comms_logger.reset()

    x = jnp.arange(16, dtype=jnp.float32).reshape(4, 4)

    def f(x):
        return dist.all_reduce(x, "dp")

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(x)
    expected = np.tile(np.asarray(x).reshape(4, 4).sum(axis=0, keepdims=True), (4, 1))
    np.testing.assert_allclose(np.asarray(out), expected)

    rows = dist.comms_logger.summary()
    assert any(r["op"] == "all_reduce_sum" and r["axis"] == "dp" for r in rows)
    r = next(r for r in rows if r["op"] == "all_reduce_sum")
    assert r["count"] >= 1 and r["total_bytes"] > 0 and r["bus_bytes"] > 0
    dist.log_summary()
    dist.comms_logger.configure(enabled=False)


def test_reduce_scatter_all_gather_roundtrip(mesh):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8))

    def f(x):
        s = dist.reduce_scatter(x[0], "dp", scatter_axis=0)  # local shard [2]
        return dist.all_gather(s, "dp", concat_axis=0)[None]  # full [1, 8]

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(x)
    # reduce_scatter+all_gather == all_reduce
    expected = np.tile(np.asarray(x).sum(axis=0, keepdims=True), (4, 1)).reshape(4, 8)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-6)


def test_broadcast(mesh):
    x = jnp.arange(4, dtype=jnp.float32).reshape(4, 1)  # rank r holds value r

    def f(x):
        return dist.broadcast(x, "dp", root=2)

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(x)
    np.testing.assert_allclose(np.asarray(out), np.full((4, 1), 2.0))


def test_quantized_reduce_scatter_approximates_mean(ranks):
    mesh, n = ranks, ranks.shape["dp"]
    N = n * 256
    g = jax.random.normal(jax.random.PRNGKey(1), (n, N))  # per-rank full grads

    def f(g):
        return quantized_reduce_scatter(g[0], "dp", block_size=128)[None]

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(g)
    full = np.asarray(g).mean(axis=0)  # exact mean of the ranks' grads
    got = np.asarray(out).reshape(-1)
    # int8 block quant: error bounded by ~absmax/127 per block
    tol = np.abs(np.asarray(g)).max() / 127 + 1e-5
    np.testing.assert_allclose(got, full, atol=tol)


def test_quantized_all_gather_approximates_exact(ranks):
    mesh, n = ranks, ranks.shape["dp"]
    x = jax.random.normal(jax.random.PRNGKey(2), (n, 64)).astype(jnp.float32)

    def f(xs):
        return quantized_all_gather(xs[0], "dp", block_size=64)[None]

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(x)
    # every rank returns the same gathered buffer; check rank 0's copy
    got = np.asarray(out).reshape(n, n * 64)[0]
    exact = np.asarray(x).reshape(-1)
    tol = np.abs(exact).max() / 127 + 1e-5
    np.testing.assert_allclose(got, exact, atol=tol)


def test_quantized_reduce_scatter_nondivisible_shard(ranks):
    # shard (750) not a multiple of block (256): blocks must not straddle ranks
    mesh, n = ranks, ranks.shape["dp"]
    N = n * 750
    g = jax.random.normal(jax.random.PRNGKey(3), (n, N))

    def f(g):
        return quantized_reduce_scatter(g[0], "dp", block_size=256)[None]

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(g)
    full = np.asarray(g).mean(axis=0)
    tol = np.abs(np.asarray(g)).max() / 127 + 1e-5
    np.testing.assert_allclose(np.asarray(out).reshape(-1), full, atol=tol)


def test_quantized_all_gather_nondivisible_shard(ranks):
    # local shard 100 with block 64: per-rank padding must survive the gather
    mesh, n = ranks, ranks.shape["dp"]
    x = jax.random.normal(jax.random.PRNGKey(4), (n, 100)).astype(jnp.float32)

    def f(xs):
        return quantized_all_gather(xs[0], "dp", block_size=64)[None]

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(x)
    got = np.asarray(out).reshape(n, n * 100)[0]
    exact = np.asarray(x).reshape(-1)
    tol = np.abs(exact).max() / 127 + 1e-5
    np.testing.assert_allclose(got, exact, atol=tol)


def test_host_api_single_process():
    assert dist.get_world_size() >= 1
    assert dist.get_rank() == 0
    dist.barrier()  # no-op single process
    assert dist.init_distributed() is False  # single-process => not multi


@pytest.mark.parametrize("hostnames,count", [
    (None, 0), ("", 0), ("localhost", 1), ("10.0.0.1,10.0.0.2", 2)])
def test_single_tpu_host_is_not_a_pod(monkeypatch, hostnames, count):
    """The chip tool's one-host v5e sets TPU_WORKER_HOSTNAMES=localhost; only
    more than one host may trigger the argument-less rendezvous."""
    from deepspeed_tpu.comm.comm import _tpu_worker_count

    if hostnames is None:
        monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    else:
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", hostnames)
    assert _tpu_worker_count() == count


@pytest.mark.parametrize("op", ["all_reduce", "all_gather", "reduce_scatter", "all_to_all"])
def test_collective_bench_rows(devices, op):
    """ds_bench analog: a sweep of sizes runs on the CPU mesh and the busbw factor of the op holds."""
    from deepspeed_tpu.comm.benchmark import run_collective_bench

    (row,) = run_collective_bench(op, sizes_mb=[0.05], axis="dp", iters=2, warmup=1)
    assert row["op"] == op and row["world"] == 8 and row["latency_ms"] > 0
    want = 2 * 7 / 8 if op == "all_reduce" else 7 / 8
    # both gbps fields are rounded to 3dp, so compare within that grain
    # (a loaded CI box can produce sub-0.01 gbps rows)
    assert abs(row["busbw_gbps"] - row["algbw_gbps"] * want) <= 1.5e-3, (op, row)


# --------------------------------------------------------------------------
# every facade op against NumPy on the 8 devices: values, and the lowered
# program holds ONE collective of the op's kind and no hop beside it (what XLA
# lowers is the one way an op runs)
# --------------------------------------------------------------------------

DP_FSDP = ("dp", "fsdp")
_KINDS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all", "collective_permute")


@pytest.fixture
def mesh8():
    return Mesh(np.array(jax.devices()[:8]), ("dp",))


@pytest.fixture
def mesh42():
    return Mesh(np.array(jax.devices()[:8]).reshape(4, 2), DP_FSDP)


def _ints(shape, dtype, seed=0):
    """Small whole numbers: every sum and every mean over 8 ranks is exact in bf16 and in int8."""
    return jnp.asarray(np.random.default_rng(seed).integers(-8, 9, shape), dtype)


def _np(x):
    return np.asarray(jnp.asarray(x, jnp.float32)) if x.dtype == jnp.bfloat16 else np.asarray(x)


def _run_and_count(mesh, body, x, spec, out_spec=None):
    """``body`` on every device's shard of ``x``; (the result, {kind: count} in the lowered text)."""
    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec if out_spec is None else out_spec,
                           check_vma=False))
    text = fn.lower(x).as_text()
    return fn(x), {k: text.count(f"stablehlo.{k}") for k in _KINDS if f"stablehlo.{k}" in text}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("axis", ["dp", DP_FSDP, ()], ids=["dp", "dp+fsdp", "no-axis"])
@pytest.mark.parametrize("op", ["sum", "mean", "max", "min"])
def test_all_reduce_is_numpy_s_reduction_over_the_named_axes(mesh42, op, axis, dtype):
    x = _ints((4, 2, 6), dtype, seed=1)
    out, kinds = _run_and_count(mesh42, lambda v: dist.all_reduce(v, axis, op=op), x, P(*DP_FSDP))
    over = {"dp": (0,), DP_FSDP: (0, 1), (): ()}[axis]
    want = getattr(np, op)(_np(x), axis=over, keepdims=True) if over else _np(x)
    np.testing.assert_array_equal(_np(out), np.broadcast_to(want, x.shape))
    assert out.dtype == x.dtype
    assert kinds == ({"all_reduce": 1} if over else {})  # over no axis nothing crosses a wire


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8], ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("tiled", [True, False], ids=["tiled", "stacked"])
@pytest.mark.parametrize("concat_axis", [0, 1])
def test_all_gather_is_numpy_s_concatenate_or_stack(mesh8, concat_axis, tiled, dtype):
    x = _ints((8, 2, 3), dtype, seed=2)  # a rank's shard: [2, 3]
    out, kinds = _run_and_count(
        mesh8, lambda v: dist.all_gather(v[0], "dp", concat_axis=concat_axis, tiled=tiled)[None], x, P("dp"))
    shards = list(_np(x))
    want = np.concatenate(shards, concat_axis) if tiled else np.stack(shards, concat_axis)
    for rank in range(8):
        np.testing.assert_array_equal(_np(out)[rank], want)
    assert out.dtype == x.dtype and kinds == {"all_gather": 1}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32], ids=["f32", "bf16", "int32"])
@pytest.mark.parametrize("tiled", [True, False], ids=["tiled", "one-row"])
@pytest.mark.parametrize("scatter_axis", [0, 1])
def test_reduce_scatter_is_a_rank_s_slice_of_numpy_s_sum(mesh8, scatter_axis, tiled, dtype):
    x = _ints((8, 16, 8) if scatter_axis == 0 else (8, 8, 16), dtype, seed=3)
    if not tiled:  # untiled: the scattered dimension is the ranks' and leaves the result
        x = x[:, :8, :8]
    out, kinds = _run_and_count(
        mesh8, lambda v: dist.reduce_scatter(v[0], "dp", scatter_axis=scatter_axis, tiled=tiled)[None], x, P("dp"))
    total = _np(x).sum(0)
    for rank in range(8):
        want = (np.split(total, 8, scatter_axis)[rank] if tiled else np.take(total, rank, scatter_axis))
        np.testing.assert_array_equal(_np(out)[rank], want)
    assert out.dtype == x.dtype and kinds == {"reduce_scatter": 1}


def _all_to_all_by_hand(shards, split_axis, concat_axis, tiled):
    """Rank r receives block r of every rank's ``split_axis``, laid along ``concat_axis`` in the senders' order."""
    n = len(shards)
    if tiled:
        return [np.concatenate([np.split(s, n, split_axis)[r] for s in shards], concat_axis) for r in range(n)]
    return [np.stack([np.take(s, r, split_axis) for s in shards], concat_axis) for r in range(n)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32], ids=["f32", "bf16", "int32"])
@pytest.mark.parametrize("split_axis,concat_axis", [(0, 0), (0, 1), (1, 0), (1, 2), (2, 1)])
def test_all_to_all_is_the_block_transpose_by_hand(mesh8, split_axis, concat_axis, dtype):
    x = _ints((8, 8, 16, 24), dtype, seed=4)  # a rank's shard: [8, 16, 24], every dimension 8 blocks
    out, kinds = _run_and_count(
        mesh8, lambda v: dist.all_to_all(v[0], "dp", split_axis=split_axis, concat_axis=concat_axis)[None],
        x, P("dp"))
    for rank, want in enumerate(_all_to_all_by_hand(list(_np(x)), split_axis, concat_axis, tiled=True)):
        np.testing.assert_array_equal(_np(out)[rank], want)
    assert out.dtype == x.dtype and kinds == {"all_to_all": 1}


def test_all_to_all_untiled_trades_the_ranks_dimension_for_a_new_one(mesh8):
    x = _ints((8, 8, 3), jnp.float32, seed=5)  # a rank's shard: [8 = the ranks, 3]
    out, kinds = _run_and_count(
        mesh8, lambda v: dist.all_to_all(v[0], "dp", split_axis=0, concat_axis=1, tiled=False)[None], x, P("dp"))
    for rank, want in enumerate(_all_to_all_by_hand(list(_np(x)), 0, 1, tiled=False)):
        np.testing.assert_array_equal(_np(out)[rank], want)
    assert kinds == {"all_to_all": 1}


_PERMS = {
    "shift": [(i, (i + 1) % 8) for i in range(8)],
    "reverse": [(i, 7 - i) for i in range(8)],
    "one-pair": [(2, 5), (5, 2)],  # a rank nobody sends to receives zeros
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8], ids=["f32", "int8"])
@pytest.mark.parametrize("perm", list(_PERMS))
def test_ppermute_moves_each_shard_to_its_destination(mesh8, perm, dtype):
    x = _ints((8, 5), dtype, seed=6)
    out, kinds = _run_and_count(mesh8, lambda v: dist.ppermute(v, "dp", _PERMS[perm]), x, P("dp"))
    want = np.zeros_like(_np(x))
    for src, dst in _PERMS[perm]:
        want[dst] = _np(x)[src]
    np.testing.assert_array_equal(_np(out), want)
    assert kinds == {"collective_permute": 1}


@pytest.mark.parametrize("axis", ["dp", DP_FSDP], ids=["dp", "dp+fsdp"])
@pytest.mark.parametrize("root", [0, 3, 7])
def test_broadcast_hands_every_rank_the_root_s_shard(mesh8, mesh42, root, axis):
    x = _ints((8, 4), jnp.float32, seed=7)
    mesh, spec = (mesh8, P("dp")) if axis == "dp" else (mesh42, P(DP_FSDP))
    out, kinds = _run_and_count(mesh, lambda v: dist.broadcast(v, axis, root=root), x, spec)
    np.testing.assert_array_equal(_np(out), np.tile(_np(x)[root], (8, 1)))
    assert kinds == {"all_gather": 1}


_SHARD = (4, 6)  # what a rank holds in the record's cases
_RECORDED = {
    "all_reduce_sum": (lambda v, ax: dist.all_reduce(v, ax), "dp"),
    "all_reduce_max": (lambda v, ax: dist.all_reduce(v, ax, op="max"), DP_FSDP),
    "all_gather": (lambda v, ax: dist.all_gather(v, ax), DP_FSDP),
    "reduce_scatter": (lambda v, ax: dist.reduce_scatter(v, ax), "dp"),
    "all_to_all": (lambda v, ax: dist.all_to_all(v, ax, split_axis=0, concat_axis=1), "dp"),
    "ppermute": (lambda v, ax: dist.ppermute(v, ax, [(0, 1), (1, 0)]), "fsdp"),
    "broadcast": (lambda v, ax: dist.broadcast(v, ax, root=1), "fsdp"),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8], ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("op", list(_RECORDED))
def test_an_op_leaves_one_record_of_what_it_moves_at_trace_time(mesh42, op, dtype):
    """bytes = the shard's elements x its itemsize, world = the product of the named axes: one ``comm:<op>`` span
    with those tags, the same bytes under ``comm/bytes/<op>``, and one row of the comms logger."""
    from deepspeed_tpu import telemetry

    body, axis = _RECORDED[op]
    nbytes = int(np.prod(_SHARD)) * jnp.dtype(dtype).itemsize
    world = int(np.prod([mesh42.shape[a] for a in ((axis,) if isinstance(axis, str) else axis)]))
    tracer = telemetry.configure(enabled=True)
    tracer.reset()
    dist.comms_logger.configure(enabled=True)
    dist.comms_logger.reset()
    try:
        jax.make_jaxpr(shard_map(lambda v: body(v[0, 0], axis)[None, None], mesh=mesh42, in_specs=P(*DP_FSDP),
                                 out_specs=P(*DP_FSDP), check_vma=False))(jnp.zeros((4, 2, *_SHARD), dtype))
        (span,) = [e for e in tracer.events() if e.get("cat") == "comm"]
        assert span["name"] == f"comm:{op}"
        assert span["args"] == {"op": op, "axis": "+".join(axis) if isinstance(axis, tuple) else axis,
                                "bytes": nbytes, "dtype": jnp.dtype(dtype).name, "world": world}
        counters = tracer.registry.counters()
        assert counters["comm/count"] == 1 and counters["comm/bytes"] == counters[f"comm/bytes/{op}"] == nbytes
        (row,) = dist.comms_logger.summary()
        factor = 2 * (world - 1) / world if op.startswith("all_reduce") else (world - 1) / world
        assert (row["op"], row["count"], row["total_bytes"], row["bus_bytes"]) == (op, 1, nbytes, int(factor * nbytes))
    finally:
        dist.comms_logger.configure(enabled=False)
        telemetry.configure(enabled=False)


@pytest.mark.parametrize("call", [
    lambda v: dist.all_reduce(v, "dp", algorithm="ring"),
    lambda v: dist.all_gather(v, "dp", codec="int8"),
    lambda v: dist.reduce_scatter(v, "dp", block_size=64),
    lambda v: dist.all_to_all(v, "dp", split_axis=0, concat_axis=0, algorithm="lax"),
], ids=["all_reduce", "all_gather", "reduce_scatter", "all_to_all"])
def test_an_op_takes_no_routing_argument(call):
    with pytest.raises(TypeError, match="unexpected keyword"):
        call(jnp.zeros((8, 8)))
