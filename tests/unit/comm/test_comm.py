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


def test_quantized_reduce_scatter_approximates_mean(mesh):
    N = 4 * 256
    g = jax.random.normal(jax.random.PRNGKey(1), (4, N))  # per-rank full grads

    def f(g):
        return quantized_reduce_scatter(g[0], "dp", block_size=128)[None]

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(g)
    full = np.asarray(g).mean(axis=0)  # exact mean of the 4 ranks' grads
    got = np.asarray(out).reshape(-1)
    # int8 block quant: error bounded by ~absmax/127 per block
    tol = np.abs(np.asarray(g)).max() / 127 + 1e-5
    np.testing.assert_allclose(got, full, atol=tol)


def test_quantized_all_gather_approximates_exact(mesh):
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 64)).astype(jnp.float32)

    def f(xs):
        return quantized_all_gather(xs[0], "dp", block_size=64)[None]

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(x)
    # every rank returns the same gathered buffer; check rank 0's copy
    got = np.asarray(out).reshape(4, 256)[0]
    exact = np.asarray(x).reshape(-1)
    tol = np.abs(exact).max() / 127 + 1e-5
    np.testing.assert_allclose(got, exact, atol=tol)


def test_quantized_reduce_scatter_nondivisible_shard(mesh):
    # shard (750) not a multiple of block (256): blocks must not straddle ranks
    N = 4 * 750
    g = jax.random.normal(jax.random.PRNGKey(3), (4, N))

    def f(g):
        return quantized_reduce_scatter(g[0], "dp", block_size=256)[None]

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(g)
    full = np.asarray(g).mean(axis=0)
    tol = np.abs(np.asarray(g)).max() / 127 + 1e-5
    np.testing.assert_allclose(np.asarray(out).reshape(-1), full, atol=tol)


def test_quantized_all_gather_nondivisible_shard(mesh):
    # local shard 100 with block 64: per-rank padding must survive the gather
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 100)).astype(jnp.float32)

    def f(xs):
        return quantized_all_gather(xs[0], "dp", block_size=64)[None]

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(x)
    got = np.asarray(out).reshape(4, 400)[0]
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


def test_collective_bench_rows(devices):
    """ds_bench analog: sweeps run on the CPU mesh and busbw factors hold."""
    from deepspeed_tpu.comm.benchmark import run_collective_bench

    for op in ("all_reduce", "all_gather", "reduce_scatter", "all_to_all"):
        rows = run_collective_bench(op, sizes_mb=[0.05], axis="dp", iters=2, warmup=1)
        (row,) = rows
        assert row["world"] == 8 and row["latency_ms"] > 0
        want = 2 * 7 / 8 if op == "all_reduce" else 7 / 8
        # both gbps fields are rounded to 3dp, so compare within that grain
        # (a loaded CI box can produce sub-0.01 gbps rows)
        assert abs(row["busbw_gbps"] - row["algbw_gbps"] * want) <= 1.5e-3, (op, row)
