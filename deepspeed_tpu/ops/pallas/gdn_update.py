"""One token of the gated delta rule, in place in the state pool (Pallas TPU).

A decode step of a Gated DeltaNet layer moves nothing but state: per live row
``Hv x Dk x Dv`` float32 (2 MiB at 32 x 128 x 128) read and written, against a
few KB of inputs. XLA's form of ``ops/gdn.py::gdn_step`` on a row of the pool
(``inference/cache.StatePool``) needs the decayed state twice (for ``S^T k``,
then for the rank-one update) and the updated state twice (to store it, and for
``S^T q``), in fusions of its own choosing. This kernel makes one read and one
write: a grid step holds one row's heads in VMEM, a head ``S <- e^g S``, ``d =
beta (v - S^T k)``, ``S <- S + k d^T``, ``o = S^T q`` on the tile, and writes
each head back to where it came from: the pool is aliased in and out, and
nothing else of it is touched.

Layout. The pool is ``[layers, slots, Hv, Dk, Dv]``: a head's state is one
``[Dk, Dv]`` tile, the VALUES on the lanes and the keys on the sublanes, which
is also how ``ops/gdn.py``'s chunked form takes and leaves it (no swap on the
way in or out of a prompt). What is a vector over the values (``v``, ``d``,
``o``, and the head's scalars ``e^g`` and ``beta`` spread over a row) is a
lane-dense ``[1, Dv]`` row that broadcasts DOWN the tile; ``k`` and ``q`` are
vectors over the keys, the same for every value, so each is made ONE ``[Dk,
Dv]`` tile a key head (a transpose of its broadcast, ``ssm_update.py``'s
device) and reused by the value heads that read it; ``S^T k`` and ``S^T q``
are sums over SUBLANES, plain adds.
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
# a row's block of heads, in and out, each double-buffered by the pipeline
_VMEM_BUDGET = 9 << 20


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def takes(Hk: int, Hv: int, Dk: int, Dv: int) -> bool:
    """Whether the chip's compiler takes the kernel at these sizes: a head's
    state whole 128-lane tiles, and a ``[Dk, Dv]`` tile of ``k`` made by an
    aligned transpose."""
    return Dk % _LANES == 0 and Dv % _LANES == 0 and Dk == Dv and Hv % Hk == 0


def _kernel(layer_ref, s_ref, a_ref, beta_ref, v_ref, k_ref, q_ref, o_ref, y_ref, *, hb, rep):
    j = pl.program_id(1)
    Dk, Dv = s_ref.shape[3:]

    def down(ref, h):  # a key head's k or q [1, Dk] as a tile [Dk, Dv]: the same column under every value
        return jnp.broadcast_to(ref[0, pl.ds(h, 1), :], (Dv, Dk)).T

    def key_head(c, _):
        kh = j * (hb // rep) + c
        k, q = down(k_ref, kh), down(q_ref, kh)
        for r in range(rep):  # the value heads that read this key head
            t = c * rep + r
            a, beta, v = a_ref[0, pl.ds(t, 1), :], beta_ref[0, pl.ds(t, 1), :], v_ref[0, pl.ds(t, 1), :]  # [1, Dv]
            # a decay of 0 is a row that starts a sequence: whatever its slot holds is another's
            s = jnp.where(a > 0.0, s_ref[0, 0, t], 0.0) * a
            d = beta * (v - jnp.sum(s * k, axis=0, keepdims=True))
            s = s + k * d
            o_ref[0, 0, t] = s
            y_ref[0, pl.ds(t, 1), :] = jnp.sum(s * q, axis=0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, hb // rep, key_head, 0)


@register("gdn_pool_step", "pallas")
def gdn_pool_step(pool, layer, q, k, v, g, beta, live=None, fresh=None):
    """``ops/gdn.py::gdn_pool_step``: one token of the recurrence for the
    program's rows, on row ``layer`` of ``pool`` [layers, slots, Hv, Dk, Dv]
    float32, slots 0..rows-1, in place. ``q``/``k`` [rows, Hk, Dk], ``v`` [rows,
    Hv, Dv], ``g``/``beta`` [rows, Hv] float32, ``live``/``fresh`` [rows] bool.
    Returns ``(o [rows, Hv, Dv] in v's dtype, pool)``."""
    R, Hv, Dv = v.shape
    Hk, Dk = q.shape[1:]
    if pool.shape[2:] != (Hv, Dk, Dv):
        raise ValueError(f"gdn_update: a pool of {pool.shape[2:]} states for {Hv} heads of [{Dk}, {Dv}]")
    f32 = jnp.float32
    if live is not None:
        g, beta = jnp.where(live[:, None], g, 0.0), jnp.where(live[:, None], beta, 0.0)
    a = jnp.exp(g)
    if fresh is not None:
        a = jnp.where(fresh[:, None], 0.0, a)
    rep = Hv // Hk
    spread = lambda x: jnp.broadcast_to(x.astype(f32)[..., None], (R, Hv, Dv))  # noqa: E731  a head's scalar, lane-dense
    hb = next(d for d in range(Hv, 0, -rep) if Hv % d == 0 and 4 * d * Dk * Dv * 4 <= _VMEM_BUDGET)
    tiles = pl.BlockSpec((1, 1, hb, Dk, Dv), lambda r, j, layer: (layer[0], r, j, 0, 0))
    rows = pl.BlockSpec((1, hb, Dv), lambda r, j, layer: (r, j, 0))
    keys = pl.BlockSpec((1, Hk, Dk), lambda r, j, layer: (r, 0, 0))
    pool, y = pl.pallas_call(
        functools.partial(_kernel, hb=hb, rep=rep),
        name="gdn_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the layer's row of the pool
            grid=(R, Hv // hb),
            in_specs=[tiles, rows, rows, rows, keys, keys],
            out_specs=[tiles, rows],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype), jax.ShapeDtypeStruct((R, Hv, Dv), f32)],
        input_output_aliases={1: 0},  # the pool, in place (operand 0 is the prefetched scalar)
        compiler_params=tpu_compiler_params(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), pool, spread(a), spread(beta), v.astype(f32),
      k.astype(f32), q.astype(f32))
    return y.astype(v.dtype), pool
