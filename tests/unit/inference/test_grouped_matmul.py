"""The routed prefill's grouped matmul: the tiles ``_gmm_tiles`` and
``_tgmm_tiles`` pick from a call's static shapes, and ``_gmm_padded`` (megablox
``gmm`` at those tiles, interpret mode here) and its gradient against
``lax.ragged_dot``. What Mosaic makes of the tiles is
``tests/unit/ops/test_chip_compile.py``'s; what they cost is a chip run's
(``tools/gmm_kernel_bench.py``), never this file's."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import model
from deepspeed_tpu.inference.model import (
    _GMM_VMEM_BUDGET, _TGMM_VMEM_BUDGET, _gmm_padded, _gmm_tiles, _gmm_vmem_bytes, _tgmm_tiles, _tgmm_vmem_bytes)

# (m, K, N, E, itemsize): the glm cell's two calls, its set-up check's prefill,
# the smallest grouped call at 64 experts, mixtral-shaped layers (K past one
# block), fp32 operands, a toy's few rows, widths with few divisors
SHAPES = [
    (65536, 2048, 1536, 64, 2), (65536, 1536, 2048, 64, 2), (3072, 2048, 1536, 64, 2),
    (512, 2048, 1536, 64, 2), (8192, 4096, 14336, 8, 2), (8192, 14336, 4096, 8, 2),
    (65536, 2048, 1536, 64, 4), (32768, 8192, 8192, 16, 2), (20, 128, 128, 3, 4),
    (40, 128, 256, 4, 2), (1000, 640, 896, 8, 2), (131072, 1536, 2048, 64, 2),
]
IDS = ["x".join(map(str, s)) for s in SHAPES]


def _lawful_row_tile(tm, m, itemsize):
    sublanes = 32 // itemsize
    assert tm % sublanes == 0 and tm <= 512
    assert tm - sublanes < m or tm == sublanes  # the rows padded to tm are under one packed tile more
    assert (tm >= 128 and tm & (tm - 1) == 0) or tm == -(-m // sublanes) * sublanes


@pytest.mark.parametrize("m,K,N,E,itemsize", SHAPES, ids=IDS)
def test_tiles_are_lawful_and_fit_the_budget(m, K, N, E, itemsize):
    tm, tk, tn = tiles = _gmm_tiles(m, K, N, E, itemsize)
    _lawful_row_tile(tm, m, itemsize)
    assert K % tk == 0 and (tk % 128 == 0 or tk == K)  # no remainder mask in the k loop
    assert N % tn == 0 and (tn % 128 == 0 or tn == N) and tn <= 1024
    assert _gmm_vmem_bytes(tm, tk, tn, itemsize) <= _GMM_VMEM_BUDGET < 16 * 2 ** 20
    assert _gmm_tiles(m, K, N, E, itemsize) == tiles and all(type(t) is int for t in tiles)


@pytest.mark.parametrize("m,K,N,E,itemsize", SHAPES, ids=IDS)
def test_the_weight_gradient_takes_tiles_of_its_own(m, K, N, E, itemsize):
    """``tgmm`` keeps a ``[tk, tn]`` accumulator and out block where the
    forward keeps ``[tm, tn]`` (the forward's (128, 4096, 512) of a mixtral
    layer is 18.25 MiB there, and Mosaic refused it). Its tiles come from the
    same shapes by its own arithmetic: the most FLOPs a byte that fit."""
    tm, tk, tn = tiles = _tgmm_tiles(m, K, N, E, itemsize)
    _lawful_row_tile(tm, m, itemsize)
    assert K % tk == 0 and N % tn == 0 and tk % 128 == 0 and tn % 128 == 0
    assert _tgmm_vmem_bytes(tm, tk, tn, itemsize) <= _TGMM_VMEM_BUDGET < 16 * 2 ** 20
    for wider in ((tm, 2 * tk, tn), (tm, tk, 2 * tn)):  # nothing wider in one dim alone would have fitted
        assert K % wider[1] or N % wider[2] or _tgmm_vmem_bytes(*wider, itemsize) > _TGMM_VMEM_BUDGET
    assert _tgmm_tiles(m, K, N, E, itemsize) == tiles and all(type(t) is int for t in tiles)


def test_the_tgmm_arithmetic_is_mosaics_own_at_the_steps_it_refused():
    """PERF.md, PR 34: the three ``tgmm`` steps Mosaic refused for the v5e, at
    the figure its message gave, and the largest it compiled."""
    MiB = 2 ** 20
    assert _tgmm_vmem_bytes(512, 2048, 768, 2) == 17.5 * MiB
    assert _tgmm_vmem_bytes(512, 1536, 1024, 2) == 17.0 * MiB
    assert _tgmm_vmem_bytes(128, 4096, 512, 2) == 18.25 * MiB
    assert _tgmm_vmem_bytes(256, 2048, 768, 2) == 14.75 * MiB < _TGMM_VMEM_BUDGET


@pytest.mark.parametrize("m,K,N", [(65536, 2048, 1536), (65536, 1536, 2048)], ids=["gate-up", "down"])
def test_the_cell_prefill_takes_the_tiles_the_chip_sweep_found_best(m, K, N):
    """glm-4.7-flash.serve.batch's (64, 256) prefill, 64 groups of 1,024 rows
    on average (PERF.md section 6, PR 34's sweep)."""
    tm, tk, tn = _gmm_tiles(m, K, N, 64, 2)
    assert tm >= 256 and tk == K and tn >= 256
    assert (tm, tk, tn) == (256, K, {1536: 768, 2048: 1024}[N])
    assert _tgmm_tiles(m, K, N, 64, 2) == (256, *{1536: (1024, 1536), 2048: (1536, 1024)}[N])  # 614 FLOPs a byte


@pytest.mark.parametrize("rows_a_group", [2, 8, 64, 100, 128, 255])
@pytest.mark.parametrize("E", [8, 64])
def test_small_groups_take_the_small_row_tile(rows_a_group, E):
    tm, _, _ = _gmm_tiles(rows_a_group * E, 2048, 1536, E, 2)
    assert tm <= 128


def test_row_tile_grows_with_the_mean_group_and_stops_at_512():
    tms = [_gmm_tiles(r * 64, 1024, 512, 64, 2)[0] for r in (16, 64, 256, 512, 1024, 2048, 4096, 65536)]
    assert tms == [128, 128, 128, 128, 256, 512, 512, 512]


def _operands(seed, sizes, K, N, dtype):
    rng = np.random.default_rng(seed)
    m = int(sum(sizes))
    lhs = jnp.asarray(rng.standard_normal((m, K)), dtype)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), K, N)) * 0.1, dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


# (sizes, K, N, dtype, forced tiles or None for the chooser's)
CASES = {
    # review r5: a non-128-multiple prefill crashed at trace time on the TPU; the
    # wrapper pads the rows into the last group and slices them off
    "rows-not-a-multiple-of-the-sublanes": ([7, 9, 4], 128, 128, jnp.float32, None),
    "an-empty-group": ([100, 0, 60, 0, 0, 96], 128, 256, jnp.float32, None),
    "empty-first-and-last-groups": ([0, 130, 126, 0], 128, 128, jnp.float32, None),
    "groups-smaller-than-the-tile": ([3, 40, 1, 17, 60, 7, 100, 28], 256, 128, jnp.float32, None),
    "a-group-spanning-three-tiles": ([50, 300, 34], 128, 128, jnp.float32, None),
    "m-not-a-multiple-of-tm": ([200, 150, 83], 128, 384, jnp.float32, None),
    "tm-256-from-groups-of-a-thousand": ([1300, 1100], 128, 128, jnp.float32, None),
    "tm-512-and-a-group-across-three-tiles": ([2300, 1800], 128, 128, jnp.float32, None),
    "bf16-operands": ([90, 0, 166, 64], 256, 256, jnp.bfloat16, None),
    "forced-k-loop-and-n-tiles": ([200, 150, 83], 384, 256, jnp.float32, (128, 128, 128)),
    "forced-whole-k-one-n-tile": ([200, 0, 233], 384, 256, jnp.float32, (256, 384, 256)),
    "forced-tile-wider-than-every-group": ([30, 20, 70, 8], 128, 128, jnp.float32, (128, 128, 128)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_gmm_padded_equals_ragged_dot(case, monkeypatch):
    """Interpret mode exercises the real kernel's path on the CPU."""
    sizes, K, N, dtype, forced = CASES[case]
    lhs, rhs, gs = _operands(4, sizes, K, N, dtype)
    if forced:
        monkeypatch.setattr(model, "_gmm_tiles", lambda *a: forced)
    tm = model._gmm_tiles(lhs.shape[0], K, N, len(sizes), lhs.dtype.itemsize)[0]
    if case.startswith("tm-"):
        assert tm == int(case.split("-")[1])
    if "three-tiles" in case:
        assert max(sizes) > 2 * tm
    got = _gmm_padded(lhs, rhs, gs, interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, gs, preferred_element_type=jnp.float32)
    assert got.shape == (lhs.shape[0], N) and got.dtype == lhs.dtype
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), **tol)


GRADIENT_CASES = ["rows-not-a-multiple-of-the-sublanes", "an-empty-group", "groups-smaller-than-the-tile",
                  "a-group-spanning-three-tiles", "m-not-a-multiple-of-tm", "tm-256-from-groups-of-a-thousand",
                  "bf16-operands", "forced-tile-wider-than-every-group"]


@pytest.mark.parametrize("case", GRADIENT_CASES)
def test_gmm_padded_gradient_equals_ragged_dot(case, monkeypatch):
    """``jax.grad`` through ``_gmm_padded`` (``DropFreeMoE`` trains through it
    on a TPU): d lhs by the forward's kernel on the transposed weights, d rhs
    by ``tgmm``, each at tiles chosen from its own shapes; an empty group's
    weights get a zero gradient and the padding rows none."""
    sizes, K, N, dtype, forced = CASES[case]
    lhs, rhs, gs = _operands(5, sizes, K, N, dtype)
    ct = jnp.asarray(np.random.default_rng(6).standard_normal((lhs.shape[0], N)), jnp.float32)
    if forced:
        monkeypatch.setattr(model, "_gmm_tiles", lambda *a: forced)
        monkeypatch.setattr(model, "_tgmm_tiles", lambda *a: forced)

    def grads(product):
        return jax.grad(lambda a, b: jnp.sum(product(a, b).astype(jnp.float32) * ct), argnums=(0, 1))(lhs, rhs)

    got = grads(lambda a, b: _gmm_padded(a, b, gs, True))
    want = grads(lambda a, b: jax.lax.ragged_dot(a, b, gs, preferred_element_type=jnp.float32))
    for g, w, like in zip(got, want, (lhs, rhs)):
        assert g.shape == like.shape and g.dtype == like.dtype
        scale = float(jnp.max(jnp.abs(w.astype(jnp.float32))))
        tol = 2 ** -7 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w, np.float32), rtol=tol, atol=tol * scale)
    if 0 in sizes:
        assert not np.asarray(got[1][sizes.index(0)], np.float32).any()


def test_each_backward_kernel_asks_for_tiles_at_its_own_shapes(monkeypatch):
    """The library's ``custom_vjp`` hands the forward's tiles to both backward
    kernels; ``_gmm_padded``'s asks the choosers again: the transposed product
    is ``[m, N] x [E, N, K]``, the weights' gradient ``tgmm`` at (m, K, N)."""
    asked = []
    gmm_tiles, tgmm_tiles = model._gmm_tiles, model._tgmm_tiles
    monkeypatch.setattr(model, "_gmm_tiles", lambda *a: asked.append(("gmm", *a)) or gmm_tiles(*a))
    monkeypatch.setattr(model, "_tgmm_tiles", lambda *a: asked.append(("tgmm", *a)) or tgmm_tiles(*a))
    lhs, rhs, gs = _operands(1, [100, 156], 128, 384, jnp.float32)
    jax.grad(lambda a, b: _gmm_padded(a, b, gs, True).sum(), argnums=(0, 1))(lhs, rhs)
    assert asked == [("gmm", 256, 128, 384, 2, 4), ("gmm", 256, 384, 128, 2, 4), ("tgmm", 256, 128, 384, 2, 4)]


def _kernel_bench():
    path = os.path.join(os.path.dirname(__file__), "..", "..", "..", "tools", "gmm_kernel_bench.py")
    spec = importlib.util.spec_from_file_location("gmm_kernel_bench", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


@pytest.mark.parametrize("tiles", ["chosen", (128, 128, 128), (256, 256, 128)], ids=str)
def test_gmm_kernel_bench_runs_a_reading(tiles):
    """``tools/gmm_kernel_bench.py`` (PERF.md reads its chip runs) at a toy shape
    in interpret mode: a reading goes through ``_gmm_padded`` or the swept
    tiles, agrees with ``lax.ragged_dot`` and counts the grid's steps as the
    kernel walks them. The times mean nothing here; ``main`` refuses to run off
    a chip."""
    bench = _kernel_bench()
    shape = (1024, 256, 384, 8, 2)
    ops = bench.operands(shape, 2 ** 31 + 5, 0.3)
    sizes = np.asarray(ops[3])
    assert sizes.sum() == 1024 and sizes.max() >= 0.3 * 512  # the padding's share lands in one token's picks
    got = bench.measure("toy", tiles, ops, repeats=1, device_kind="TPU v5 lite", interpret=True)
    assert "refused" not in got and got["max_abs_err"] <= 2 ** -6 * got["max_abs_ref"]
    used = (got["tm"], got["tk"], got["tn"])
    assert used == (_gmm_tiles(1024, 256, 384, 8, 2) if tiles == "chosen" else tiles)
    tm, tk, tn = used
    ends = np.cumsum(sizes)
    visits = sum(len(range(int(s) // tm, -(-int(e) // tm))) for s, e in zip(ends - sizes, ends) if e > s)
    assert got["grid_steps"] == (384 // tn) * visits * (256 // tk)
    assert got["peak_pct"] == pytest.approx(100.0 * got["tflops"] / 197.0)
    assert bench.SHAPES["glm-up"][:4] == (65536, 2048, 1536, 64) and bench.SHAPES["glm-down"][:4] == (65536, 1536, 2048, 64)


@pytest.mark.parametrize("tiles", ["chosen", "library-128", (128, 256, 128)], ids=str)
def test_gmm_kernel_bench_reads_the_backward(tiles):
    """``--backward``: the gradient's two kernels through ``_gmm_padded``, through
    the library's own vjp at the 128s (a ``jax.grad`` before PR 34), or ``tgmm``
    alone at a swept tiling, against plain products a group."""
    bench = _kernel_bench()
    ops = bench.operands((1024, 256, 384, 8, 2), 2 ** 31 + 5, 0.0)
    got = bench.measure_backward("toy", tiles, ops, bench.backward_references(ops), repeats=1,
                                 device_kind="TPU v5 lite", interpret=True)
    assert "refused" not in got and got["pass"] == "backward"
    assert got["d_rhs_max_abs_err"] <= 2 ** -6 * got["d_rhs_max_abs_ref"]
    if isinstance(tiles, str):
        assert got["flops"] == 4.0 * 1024 * 256 * 384
        assert got["d_lhs_max_abs_err"] <= 2 ** -6 * got["d_lhs_max_abs_ref"]
    else:
        assert got["flops"] == 2.0 * 1024 * 256 * 384 and got["d_rhs_tiles"] == list(tiles)
        assert "d_lhs_max_abs_err" not in got
    if tiles == "chosen":
        assert got["d_lhs_tiles"] == list(_gmm_tiles(1024, 384, 256, 8, 2))
        assert got["d_rhs_tiles"] == list(_tgmm_tiles(1024, 256, 384, 8, 2))
