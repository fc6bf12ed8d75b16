"""The sparse-attention indexer's scores as one TPU kernel (``ops/dsa.py``
has the mathematics and the plain XLA form):

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        float32, -inf at s > t

A grid step takes a tile of ``_TQ`` queries, ALL the indexer's heads, against a
tile of ``_TK`` keys: the heads are the rows of one product, head-major, ``[heads
* _TQ, D] x [D, _TK]``, so the keys are pushed to the MXU once for all heads;
the ``relu``, the heads' weights and the sum over heads are then adds and
multiplies of ``[_TQ, _TK]`` slabs in fast memory, and only the summed scores
leave. XLA's form writes every head's scores first: 32 times the result.

A key tile wholly past a query tile's last position computes nothing and
writes ``-inf`` (a prompt from position 0: half the tiles). The result comes
in whole key tiles, ``[N, C, ceil(S / _TK) * _TK]``, the columns past ``S``
``-inf``: cutting them off would copy it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.registry import register
from deepspeed_tpu.utils.compat import tpu_compiler_params

_TQ = 64   # queries a tile: with 32 heads, 2,048 rows of the product
_TK = 512  # keys a tile: a step's float32 scores are [2048, 512], 4 MiB


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _index_kernel(last_ref, q_ref, w_ref, pos_ref, k_ref, o_ref, *, heads):
    n, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tq, tk = o_ref.shape[1], o_ref.shape[2]

    @pl.when(j * tk <= last_ref[n, i])
    def _scores():
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [heads * tq, tk]
        s = jnp.maximum(s, 0.0) * w_ref[0, 0]
        total = s.reshape(heads, tq, tk).sum(axis=0)
        col = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        o_ref[0] = jnp.where(col <= pos_ref[0, 0], total, -jnp.inf)

    @pl.when(j * tk > last_ref[n, i])
    def _past():
        o_ref[0] = jnp.full((tq, tk), -jnp.inf, jnp.float32)


@register("dsa_index", "pallas")
def index_scores(q: jax.Array, k: jax.Array, w: jax.Array, q_positions: jax.Array) -> jax.Array:
    """``ops/dsa.py::index_scores``: q [N, C, H, D], k [N, S, D], w [N, C, H] float32, q_positions [N, C]
    -> float32 [N, C, S rounded up to whole key tiles]."""
    N, C, H, D = q.shape
    S = k.shape[1]
    tq, tk = _TQ, _TK
    Cp, Sp = -(-C // tq) * tq, -(-S // tk) * tk
    if Cp != C:
        q = jnp.pad(q, ((0, 0), (0, Cp - C), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, Cp - C), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, Cp - C)), constant_values=-1)
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0)))
    nt = Cp // tq
    # a tile's rows head-major: [N, tiles, H * tq, D], the weights a column beside them
    q2 = q.reshape(N, nt, tq, H, D).transpose(0, 1, 3, 2, 4).reshape(N, nt, H * tq, D)
    w2 = w.astype(jnp.float32).reshape(N, nt, tq, H).transpose(0, 1, 3, 2).reshape(N, nt, H * tq, 1)
    pos = q_positions.astype(jnp.int32).reshape(N, nt, tq, 1)
    last = pos[..., 0].max(axis=-1)  # [N, nt]: the last position a tile's queries may see

    out = pl.pallas_call(
        functools.partial(_index_kernel, heads=H),
        name="dsa_index",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N, nt, Sp // tk),
            in_specs=[
                pl.BlockSpec((1, 1, H * tq, D), lambda n, i, j, last: (n, i, 0, 0)),
                pl.BlockSpec((1, 1, H * tq, 1), lambda n, i, j, last: (n, i, 0, 0)),
                pl.BlockSpec((1, 1, tq, 1), lambda n, i, j, last: (n, i, 0, 0)),
                # past a tile's last position the block stays where it was: no fetch for a step that computes nothing
                pl.BlockSpec((1, tk, D), lambda n, i, j, last: (n, jnp.minimum(j, jnp.maximum(last[n, i], 0) // tk), 0)),
            ],
            out_specs=pl.BlockSpec((1, tq, tk), lambda n, i, j, last: (n, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((N, Cp, Sp), jnp.float32),
        compiler_params=tpu_compiler_params(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(last, q2, w2, pos, k)
    return out if Cp == C else out[:, :C]
