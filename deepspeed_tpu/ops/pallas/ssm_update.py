"""One token of the Mamba-2 recurrence, in place in the state pool (Pallas TPU).

A decode step of a state-space layer moves nothing but state: per live row
``H x P x N`` float32 (2 MiB at 64 x 64 x 128) read and written, against a few
KB of inputs. XLA's form of ``ops/ssm.py::ssm_step`` on a row of the pool
(``inference/cache.StatePool``) is two fusions, one that reads the row, updates
it and reduces it to ``y``, and one that reads it AGAIN, updates it again and
writes it: three passes over the state where the mathematics needs two (449
GB/s on its own bytes, 55% of the v5e's 819: PERF.md, section 6, PR 42). This
kernel makes one read and one write: a grid step holds one row's tiles in VMEM,
``S <- a S + (dt x) (outer) B`` and ``y = S C`` a tile, and writes each tile
back to where it came from: the pool is aliased in and out, and nothing else of
it is touched.

Layout (``ops/ssm.py::to_pool``). The pool is ``[layers, slots, tiles, N,
128]``: a tile is 128 of a layer's ``H P`` channels ON THE LANES and the
state's ``N`` on the sublanes. The decay ``a``, ``dt x`` and ``y`` are vectors
over the channels, so each is a lane-dense ``[tiles, 128]`` block whose row
broadcasts DOWN a tile for free; ``B`` and ``C`` are vectors over ``N``, the
same for every channel of a group, so each is made ONE ``[N, 128]`` tile a row
of the call (a 128 x 128 transpose of its broadcast) and reused by all its
tiles; and ``y = S C`` is a sum over SUBLANES, plain adds. A first form of this
kernel kept ``[.., H, P, N]`` (``N`` on the lanes): every head then paid two
masked lane reductions to take its ``dt x`` column out and put its ``y`` column
back, about 300 vector operations a head, and ran at 353 GB/s, slower than
XLA's three passes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.registry import register
from deepspeed_tpu.utils.compat import tpu_compiler_params

_LANES = 128
# a row's block of tiles, in and out, each double-buffered by the pipeline
_VMEM_BUDGET = 9 << 20


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def takes(H: int, P: int, G: int, N: int) -> bool:
    """Whether the chip's compiler takes the kernel at these sizes: whole
    128-lane tiles of channels, a group's channels whole tiles, and an ``[N,
    128]`` tile of ``B`` made by an aligned transpose."""
    return (H * P) % _LANES == 0 and (H // G * P) % _LANES == 0 and N % _LANES == 0


def _kernel(layer_ref, s_ref, xdt_ref, a_ref, b_ref, c_ref, o_ref, y_ref, *, tb, per_group):
    j = pl.program_id(1)
    N, W = s_ref.shape[3:]
    G = b_ref.shape[1]

    def down(ref, g):  # a group's B or C [1, N] as a tile [N, W]: the same column under every channel
        return jnp.broadcast_to(ref[0, pl.ds(g, 1), :], (W, N)).T

    shared = (down(b_ref, 0), down(c_ref, 0)) if G == 1 else None

    def tile(t, _):
        a, x = a_ref[0, pl.ds(t, 1), :], xdt_ref[0, pl.ds(t, 1), :]  # [1, W]
        b, c = shared or (down(b_ref, (j * tb + t) // per_group), down(c_ref, (j * tb + t) // per_group))
        # a decay of 0 is a row that starts a sequence: whatever its slot holds is another's
        s = jnp.where(a > 0.0, s_ref[0, 0, t], 0.0) * a + b * x
        o_ref[0, 0, t] = s
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(s * c, axis=0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, tb, tile, 0)


@register("ssm_pool_step", "pallas")
def ssm_pool_step(pool, layer, x, dt, A_log, B, C, D, live=None, fresh=None):
    """``ops/ssm.py::ssm_pool_step``: one token of the recurrence for the
    program's rows, on row ``layer`` of ``pool`` [layers, slots, tiles, N, W]
    float32, slots 0..rows-1, in place. ``x`` [rows, H, P], ``dt`` [rows, H]
    (after the softplus), ``B``/``C`` [rows, G, N], ``live``/``fresh`` [rows]
    bool. Returns ``(y [rows, H, P] in x's dtype, pool)``."""
    R, H, P = x.shape
    G, N = B.shape[-2:]
    T, W = pool.shape[2], pool.shape[4]
    if T * W != H * P or (T % G and G > 1):
        raise ValueError(f"ssm_update: a pool of {T} tiles of {W} channels for {H} heads of {P} in {G} groups")
    f32 = jnp.float32
    dt = dt.astype(f32)
    if live is not None:
        dt = jnp.where(live[:, None], dt, 0.0)
    a = jnp.exp(dt * -jnp.exp(A_log.astype(f32)))  # [R, H]
    if fresh is not None:
        a = jnp.where(fresh[:, None], 0.0, a)
    xf = x.astype(f32)
    xdt = (xf * dt[..., None]).reshape(R, T, W)  # a channel's, lane-dense
    a = jnp.broadcast_to(a[..., None], (R, H, P)).reshape(R, T, W)
    tb = next(d for d in range(T, 0, -1) if T % d == 0 and 4 * d * N * W * 4 <= _VMEM_BUDGET)
    tiles = pl.BlockSpec((1, 1, tb, N, W), lambda r, j, layer: (layer[0], r, j, 0, 0))
    rows = pl.BlockSpec((1, tb, W), lambda r, j, layer: (r, j, 0))
    whole = pl.BlockSpec((1, G, N), lambda r, j, layer: (r, 0, 0))
    pool, y = pl.pallas_call(
        functools.partial(_kernel, tb=tb, per_group=max(T // G, 1)),
        name="ssm_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the layer's row of the pool
            grid=(R, T // tb),
            in_specs=[tiles, rows, rows, whole, whole],
            out_specs=[tiles, rows],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype), jax.ShapeDtypeStruct((R, T, W), f32)],
        input_output_aliases={1: 0},  # the pool, in place (operand 0 is the prefetched scalar)
        compiler_params=tpu_compiler_params(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), pool, xdt, a, B.astype(f32), C.astype(f32))
    y = y.reshape(R, H, P) + D.astype(f32)[:, None] * xf
    return y.astype(x.dtype), pool


# ---- a prompt's states into and out of the pool ------------------------------
#
# The chunked scan (``ops/ssm.py::ssd_chunked``) takes and leaves states ``[rows,
# H, P, N]``, the channels on the sublanes. Written in ``jax.numpy``, the swap
# into the pool's layout made the chip's compiler re-lay the POOL instead, the
# whole of it copied in and out of the prefill (4.5 GB: "the compiler decides
# whether the pool is copied"). A kernel's operands have the layout they are
# given: these two move the call's rows, a 128 x 128 transpose a tile, and
# alias the pool through.

def _swap_kernel(layer_ref, *refs, tb, out_of_pool):
    src_ref, dst_ref = refs[-2:]  # (``rows_in`` is handed the pool it aliases besides, and does not read it)

    def tile(t, _):
        if out_of_pool:  # [1, 1, tb, N, W] -> [1, tb, W, N]
            dst_ref[0, t] = src_ref[0, 0, t].T
        else:
            dst_ref[0, 0, t] = src_ref[0, t].T
        return 0

    jax.lax.fori_loop(0, tb, tile, 0)


def _swap_specs(pool, rows):
    T, N, W = pool.shape[2:]
    tb = next(d for d in range(T, 0, -1) if T % d == 0 and 4 * d * N * W * 4 <= _VMEM_BUDGET)
    tiles = pl.BlockSpec((1, 1, tb, N, W), lambda r, j, layer: (layer[0], r, j, 0, 0))
    states = pl.BlockSpec((1, tb, W, N), lambda r, j, layer: (r, j, 0, 0))
    return tb, (rows, T // tb), tiles, states


def rows_out(pool, layer, rows: int):
    """The call's rows of row ``layer`` of the pool, ``[rows, tiles, W, N]``:
    the channels back on the sublanes (``[rows, H, P, N]`` by a reshape)."""
    T, N, W = pool.shape[2:]
    tb, grid, tiles, states = _swap_specs(pool, rows)
    return pl.pallas_call(
        functools.partial(_swap_kernel, tb=tb, out_of_pool=True), name="ssm_rows_out",
        grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1, grid=grid, in_specs=[tiles],
                                               out_specs=states),
        out_shape=jax.ShapeDtypeStruct((rows, T, W, N), pool.dtype),
        compiler_params=tpu_compiler_params(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), pool)


def rows_in(pool, layer, states):
    """``states`` [rows, tiles, W, N] into slots 0..rows-1 of row ``layer`` of
    the pool, in place."""
    tb, grid, tiles, block = _swap_specs(pool, states.shape[0])
    return pl.pallas_call(
        functools.partial(_swap_kernel, tb=tb, out_of_pool=False), name="ssm_rows_in",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), block], out_specs=tiles),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={1: 0},  # the pool, in place: the rows' tiles alone are written
        compiler_params=tpu_compiler_params(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), pool, states)
