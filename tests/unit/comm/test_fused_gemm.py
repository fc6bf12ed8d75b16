"""Fused matmul<->collective Pallas kernels (ISSUE 19, T3-style).

Acceptance pins, all under the Pallas interpreter on the forced CPU mesh
(the same no-hardware equivalence story as the PR-8 hop kernels):

- ``all_gather_matmul`` (accumulate and ``out_block`` modes) and
  ``matmul_reduce_scatter`` are BIT-identical to the plain
  gather-then-dot / dot-then-scatter composition on exact wires
  (integer-valued payloads), and bounded on int8 wires;
- the jaxpr census shows the fusion is real: n-1 ``pallas_call`` hops and
  ZERO standalone collective primitives between the matmuls;
- config-off is jaxpr-clean (zero ``pallas_call``) and numerically
  identical — the knob cannot change results, only the schedule;
- ``zeropp.sharded_matmul``'s custom_vjp produces fused gradients that
  match the unfused composition bit-exactly, and a multi-step ZeRO-3
  SGD loop keeps its loss trajectory within tolerance of unfused.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.collectives import fused_gemm
from deepspeed_tpu.parallel import zeropp
from deepspeed_tpu.utils.compat import shard_map

N_DEV = 4
M, KS, N = 6, 8, 16
K = N_DEV * KS


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:N_DEV]), ("tp",))


@pytest.fixture(autouse=True)
def _fused_off():
    fused_gemm.configure(enabled=False)
    yield
    fused_gemm.configure(enabled=False)


def _run(mesh, f, *args, in_specs, out_specs):
    return jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))(*args)


def _ints(rng, shape):
    return jnp.asarray(rng.integers(-4, 4, size=shape).astype(np.float32))


def test_all_gather_matmul_exact_bit_identity(mesh):
    rng = np.random.default_rng(0)
    x, w = _ints(rng, (M, K)), _ints(rng, (K, N))
    got = _run(mesh, lambda xv, wv: fused_gemm.all_gather_matmul(
        xv, wv, "tp", fused=True), x, w,
        in_specs=(P(), P("tp")), out_specs=P())
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(x) @ np.asarray(w))


def test_all_gather_matmul_out_block_bit_identity(mesh):
    # the backward-dx shape: g [M,N] @ W^T -> [M,K]
    rng = np.random.default_rng(1)
    g, w = _ints(rng, (M, N)), _ints(rng, (K, N))
    got = _run(mesh, lambda gv, wv: fused_gemm.all_gather_matmul(
        gv, wv, "tp", out_block=True, fused=True), g, w,
        in_specs=(P(), P("tp")), out_specs=P())
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(g) @ np.asarray(w).T)


def test_matmul_reduce_scatter_exact_bit_identity(mesh):
    rng = np.random.default_rng(2)
    a, w = _ints(rng, (8, K)), _ints(rng, (K, N))
    got = _run(mesh, lambda av, wv: fused_gemm.matmul_reduce_scatter(
        av, wv, "tp", fused=True), a, w,
        in_specs=(P(None, "tp"), P("tp")), out_specs=P("tp"))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(a) @ np.asarray(w))


def test_int8_wire_bounded(mesh):
    rng = np.random.default_rng(3)
    xf = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    wf = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
    af = jnp.asarray(rng.normal(size=(8, K)).astype(np.float32))
    got = _run(mesh, lambda xv, wv: fused_gemm.all_gather_matmul(
        xv, wv, "tp", codec="int8", block_size=64, fused=True), xf, wf,
        in_specs=(P(), P("tp")), out_specs=P())
    want = np.asarray(xf) @ np.asarray(wf)
    rel = np.abs(np.asarray(got) - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 2e-2, rel
    got = _run(mesh, lambda av, wv: fused_gemm.matmul_reduce_scatter(
        av, wv, "tp", codec="int8", block_size=64, fused=True), af, wf,
        in_specs=(P(None, "tp"), P("tp")), out_specs=P("tp"))
    want = np.asarray(af) @ np.asarray(wf)
    rel = np.abs(np.asarray(got) - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 2e-2, rel


def test_jaxpr_census_fused_and_config_off(mesh):
    rng = np.random.default_rng(4)
    x, w = _ints(rng, (M, K)), _ints(rng, (K, N))
    fn = shard_map(lambda xv, wv: fused_gemm.all_gather_matmul(
        xv, wv, "tp", fused=True), mesh=mesh,
        in_specs=(P(), P("tp")), out_specs=P(), check_vma=False)
    jx = str(jax.make_jaxpr(fn)(x, w))
    # the fusion is real: one pallas hop per ring step, no standalone
    # collective primitive anywhere between the matmuls
    assert jx.count("pallas_call") == N_DEV - 1
    for prim in ("all_gather", "psum", "ppermute", "all_reduce"):
        assert f" {prim}" not in jx and f"{prim}[" not in jx, prim
    # config-off: plain lax composition, zero pallas, identical numbers
    fn_off = shard_map(lambda xv, wv: fused_gemm.all_gather_matmul(
        xv, wv, "tp", fused=False), mesh=mesh,
        in_specs=(P(), P("tp")), out_specs=P(), check_vma=False)
    assert "pallas_call" not in str(jax.make_jaxpr(fn_off)(x, w))
    np.testing.assert_array_equal(np.asarray(jax.jit(fn_off)(x, w)),
                                  np.asarray(x) @ np.asarray(w))
    np.testing.assert_array_equal(np.asarray(jax.jit(fn)(x, w)),
                                  np.asarray(jax.jit(fn_off)(x, w)))


def test_knob_routes_default_path(mesh):
    # fused=None consults configure(); enabled -> pallas hops appear.
    # NOTE: build the shard_map wrapper AFTER flipping the knob — jax
    # caches the traced body by callable identity + avals.
    rng = np.random.default_rng(5)
    x, w = _ints(rng, (M, K)), _ints(rng, (K, N))

    def make():
        return shard_map(lambda xv, wv: fused_gemm.all_gather_matmul(
            xv, wv, "tp"), mesh=mesh,
            in_specs=(P(), P("tp")), out_specs=P(), check_vma=False)

    assert "pallas_call" not in str(jax.make_jaxpr(make())(x, w))
    fused_gemm.configure(enabled=True)
    try:
        assert "pallas_call" in str(jax.make_jaxpr(make())(x, w))
    finally:
        fused_gemm.configure(enabled=False)


def test_sharded_matmul_grads_fused_matches_unfused(mesh):
    rng = np.random.default_rng(6)
    x, w = _ints(rng, (M, K)), _ints(rng, (K, N))
    t = _ints(rng, (M, N))

    def loss(xv, wv):
        y = zeropp.sharded_matmul(xv, wv, "tp", False, 64)
        return jnp.sum((y - t) * (y - t))

    grads = {}
    for fused in (False, True):
        fused_gemm.configure(enabled=fused)
        f = shard_map(jax.grad(loss, argnums=(0, 1)), mesh=mesh,
                      in_specs=(P(), P("tp")), out_specs=(P(), P("tp")),
                      check_vma=False)
        grads[fused] = jax.jit(f)(x, w)
    np.testing.assert_array_equal(np.asarray(grads[True][0]),
                                  np.asarray(grads[False][0]))
    np.testing.assert_array_equal(np.asarray(grads[True][1]),
                                  np.asarray(grads[False][1]))


def test_zero3_sgd_trajectory_fused_tracks_unfused(mesh):
    # batch-sharded x, parameter-sharded w: the fused forward gathers w on
    # the fly, the fused backward reduce-scatters dw to each rank's shard
    steps, lr, rtol = 6, 1e-3, 1e-4
    mb = 4
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(N_DEV * mb, K)).astype(np.float32))
    w0 = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32) * 0.1)
    t = jnp.asarray(rng.normal(size=(N_DEV * mb, N)).astype(np.float32))

    def sgd_step(xv, wv, tv):
        def loss(a, b):
            y = zeropp.sharded_matmul(a, b, "tp", False, 64)
            return jnp.sum((y - tv) * (y - tv))

        lval, dw = jax.value_and_grad(loss, argnums=1)(xv, wv)
        return wv - lr * dw, jnp.reshape(lval, (1,))

    def trajectory(fused):
        fused_gemm.configure(enabled=fused)
        f = jax.jit(shard_map(
            sgd_step, mesh=mesh, in_specs=(P("tp"), P("tp"), P("tp")),
            out_specs=(P("tp"), P("tp")), check_vma=False))
        w, losses = w0, []
        for _ in range(steps):
            w, lv = f(x, w, t)
            losses.append(float(np.asarray(lv).sum()))
        return np.asarray(losses), np.asarray(w)

    l_off, w_off = trajectory(False)
    l_on, w_on = trajectory(True)
    assert l_off[-1] < l_off[0]  # it actually trains
    rel = np.abs(l_on - l_off) / (np.abs(l_off) + 1e-12)
    assert rel.max() < rtol, rel
    w_rel = np.abs(w_on - w_off).max() / (np.abs(w_off).max() + 1e-12)
    assert w_rel < rtol, w_rel
