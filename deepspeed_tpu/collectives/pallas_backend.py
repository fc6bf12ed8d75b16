"""Pallas TPU remote-DMA collective backend.

The hop primitive behind ``algorithm="pallas_ring"`` / ``"pallas_ring2d"``:
instead of routing each neighbor exchange through ``lax.ppermute`` (one XLA
collective-permute per hop, with the wire codec's encode/decode as separate
programs around it), every hop is ONE Pallas kernel built on
``pltpu.make_async_remote_copy`` + DMA-semaphore signaling — the SNIPPETS
right-permute shape, with the neighbor resolved to a LOGICAL device id so
it works on any full-manual mesh.

Two kernel shapes:

- :func:`permute_wire` — the plain hop: remote-copy every wire leaf
  (quantized values + scales) HBM→HBM in one program. Used by the
  encode-once gather/relay paths, and by reduce paths whose codec cannot
  fuse (exact wires, integer payloads).
- :func:`fused_ring_reduce_scatter_rows` — the EQuARX fusion
  (arxiv 2506.17615): for int8/fp8 wires the whole
  quantize → remote-DMA → dequantize-accumulate hop runs inside ONE kernel,
  with the wire blocks staged in VMEM. The kernel grid double-buffers
  chunks (the ``overlap.py`` T3 pattern moved inside the kernel): the
  remote DMA of chunk ``j`` is in flight while chunk ``j-1`` is
  dequant-accumulated, on a 2-slot VMEM wire buffer. One program per hop
  where the ppermute path ran three (encode / permute / decode).

Quantization block math is shared with the ``ops.quant`` registry
(``int8_block_math`` / ``fp8_block_math``) so the fused wire is the same
format every other collective and the zeropp gathers speak.

Execution modes: compiled Mosaic on a real TPU backend; Pallas
``interpret=True`` everywhere else (the tier-1 equivalence tests run the
same kernels on the forced-CPU mesh). Interpret mode cannot express remote
``semaphore_signal`` — the credit-based sender flow control and the
kernel-entry barrier are therefore emitted only in compiled mode (the
interpreter's DMAs are synchronous, so the slot-reuse hazard they guard
against cannot occur there).
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.collectives.codecs import Codec

PALLAS_ALGORITHMS = ("pallas_ring", "pallas_ring2d")

# double-buffered chunk target (elements) for the fused hop kernel grid;
# rounded to a whole number of quantization blocks per chunk
_CHUNK_TARGET = 16384


def is_pallas(algorithm) -> bool:
    return isinstance(algorithm, str) and algorithm in PALLAS_ALGORITHMS


def base_algorithm(algorithm: str) -> str:
    """The schedule a pallas algorithm runs (``pallas_ring`` -> ``ring``):
    hop counts and link volumes are identical — only the hop primitive and
    the codec fusion move."""
    return algorithm[len("pallas_"):] if is_pallas(algorithm) else algorithm


def available() -> bool:
    """True when compiled remote-DMA hops can actually run (a real TPU
    backend). Off-TPU the kernels still execute under ``interpret=True``
    when explicitly requested (tests, smoke stages), but the selector and
    the benchmark sweep must never route production traffic there."""
    return jax.default_backend() == "tpu"


def backend_token() -> str:
    """The hop-backend family usable in this process — stamped into
    selector cache keys and matched against measured decision-table rows so
    a table swept with one backend never routes the other's algorithms."""
    return "pallas" if available() else "ppermute"


def hop_backend(algorithm: str) -> str:
    """The hop-backend family an algorithm name implies — THE one
    classification behind decision-table ``backend`` stamps, the selector's
    calibrated alpha/beta lookup, and the observatory's sample labels
    (``"xla"`` for the native lowering, ``"pallas"`` for the remote-DMA
    kernels, ``"ppermute"`` for everything else)."""
    if algorithm == "lax":
        return "xla"
    return "pallas" if is_pallas(algorithm) else "ppermute"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# The fused quantized hop does not compile on the installed Mosaic (libtpu
# 0.0.34): its wire rides (1, B) row blocks, (2, B) 1-byte and (2, nb) fp32
# VMEM wire slots sliced one row at a time, and every one of those breaks
# the compiler's rule that a block or DMA slice of the two minor dims be
# (8, 128)-aligned (rehearsed on a described v5e:2x2; interpret mode cannot
# see it). Until the wire format is rebuilt around aligned tiles these
# codecs are NOT supported on a pallas algorithm in compiled mode: the
# selector never picks the pair on its own (``compiled_ok``) and asking
# for it by name raises (``_fused_hop``) — it never falls back in silence.
FUSED_CODECS = ("int8", "fp8")


def compiled_ok(algorithm, codec) -> bool:
    """False for an (algorithm, codec) pair the chip's compiler refuses:
    a pallas algorithm with a fused-hop codec, outside interpret mode."""
    return _interpret() or not (is_pallas(algorithm) and codec in FUSED_CODECS)


def fusable(codec: Codec, dtype) -> bool:
    """The in-kernel dequant-accumulate-requant fusion speaks the 1-byte
    block-quant wires (int8/fp8) over float payloads; everything else runs
    the unfused wire with plain remote-copy hops."""
    return codec.name in ("int8", "fp8") and jnp.issubdtype(dtype, jnp.floating)


# ------------------------------------------------------------- hop routing

_hop_state = threading.local()


def hops_active() -> bool:
    return getattr(_hop_state, "active", False)


@contextlib.contextmanager
def hop_scope():
    """Trace-time scope marking that the current algorithm's hops run on
    the Pallas backend (``algorithms._permute_wire`` and the reduce-scatter
    row helper consult it, so the schedule layer stays hop-agnostic)."""
    prev = getattr(_hop_state, "active", False)
    _hop_state.active = True
    try:
        yield
    finally:
        _hop_state.active = prev


_warned_multiaxis = False


def remote_dma_supported() -> bool:
    """Whether the remote-DMA hop can actually express this trace context.

    Compiled Mosaic handles LOGICAL device ids on any mesh; the Pallas
    INTERPRETER only discharges them for single-named-axis shardings (jax
    0.4.x ``dma_start_discharge_rule``). Inside interpret mode on a
    multi-axis mesh the hops fall back to ppermute — the schedule, codec,
    and numerics are identical, only the transport differs, so tests on 2D
    CPU meshes still validate the algorithm while 1D meshes validate the
    kernels themselves."""
    global _warned_multiaxis
    if not _interpret():
        return True
    names, _ = _mesh_axes()
    if len(names) == 1:
        return True
    if not _warned_multiaxis:
        _warned_multiaxis = True
        from deepspeed_tpu.utils.logging import logger

        logger.warning(
            f"pallas collectives: interpret mode cannot express remote DMA "
            f"on a multi-axis mesh ({names}) — hops fall back to ppermute "
            "for this trace (compiled TPU runs use the kernels)")
    return False


# ------------------------------------------------------- device id resolution


def _mesh_axes() -> Tuple[List[str], List[int]]:
    """(names, sizes) of every bound mesh axis, in mesh order, from the
    trace-time axis env (full-manual shard_map binds them all)."""
    from deepspeed_tpu.utils.compat import axis_env_sizes

    sizes = axis_env_sizes()
    if not sizes:
        raise RuntimeError(
            "pallas collective hops need bound mesh axis names — call inside "
            "a full-manual shard_map (see utils/compat.shard_map)")
    return list(sizes.keys()), [int(v) for v in sizes.values()]


def _neighbor_logicals(axis, perm: Sequence[Tuple[int, int]]):
    """(dst, src) LOGICAL device ids (traced int32 scalars) of the ranks this
    device sends to / receives from under ``perm`` (a permutation of the
    ``axis`` indices). Logical ids are row-major over the mesh shape, so the
    neighbor differs from this device only along the hop axis' stride."""
    names, sizes = _mesh_axes()
    if axis not in names:
        raise ValueError(f"hop axis {axis!r} not bound in mesh axes {names}")
    ax = names.index(axis)
    n = sizes[ax]
    stride = int(np.prod(sizes[ax + 1:], dtype=np.int64)) if ax + 1 < len(sizes) else 1
    dst_t = np.full((n,), -1, np.int32)
    src_t = np.full((n,), -1, np.int32)
    for s, d in perm:
        dst_t[s] = d
        src_t[d] = s
    if (dst_t < 0).any() or (src_t < 0).any():
        raise ValueError(f"perm is not a full permutation of {n} ranks: {perm}")
    i = lax.axis_index(axis)
    my_logical = jnp.int32(0)
    for k, nm in enumerate(names):
        st = int(np.prod(sizes[k + 1:], dtype=np.int64)) if k + 1 < len(sizes) else 1
        my_logical = my_logical + lax.axis_index(nm).astype(jnp.int32) * np.int32(st)
    dst = my_logical + (jnp.asarray(dst_t)[i] - i).astype(jnp.int32) * np.int32(stride)
    src = my_logical + (jnp.asarray(src_t)[i] - i).astype(jnp.int32) * np.int32(stride)
    return dst, src


def _compiler_params():
    """Mosaic params for compiled mode (interpret mode takes none):
    collective kernels sharing the barrier semaphore need a
    ``collective_id`` (one id — every hop kernel of a step participates in
    the same gang)."""
    if _interpret():
        return None
    from deepspeed_tpu.utils.compat import tpu_compiler_params

    return tpu_compiler_params(collective_id=0)


def _entry_barrier(dst, src, interpret: bool):
    """Compiled-mode rendezvous with both hop partners before touching
    comm buffers: a remote DMA may not land in a peer's buffer before that
    peer's kernel owns it. Interpret mode is synchronous — skip."""
    if interpret:
        return
    bar = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(bar, 1, device_id=dst,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_signal(bar, 1, device_id=src,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(bar, 2)


# ------------------------------------------------------------ plain hop kernel


def _permute_leaves_kernel(idx_ref, *refs, k: int, interpret: bool):
    """Remote-copy ``k`` HBM buffers to the ``dst`` rank in one program.
    refs = inputs[k] + outputs[k] + DMA sems[2k] (send/recv per leaf)."""
    ins, outs, sems = refs[:k], refs[k:2 * k], refs[2 * k:]
    dst, src = idx_ref[0], idx_ref[1]
    _entry_barrier(dst, src, interpret)
    ops = []
    for t in range(k):
        op = pltpu.make_async_remote_copy(
            src_ref=ins[t], dst_ref=outs[t],
            send_sem=sems[2 * t], recv_sem=sems[2 * t + 1],
            device_id=dst, device_id_type=pltpu.DeviceIdType.LOGICAL)
        op.start()
        ops.append(op)
    for op in ops:
        op.wait()


def remote_permute_leaves(leaves: Sequence[jax.Array], axis,
                          perm: Sequence[Tuple[int, int]]) -> List[jax.Array]:
    """One Pallas program moving every leaf one hop along ``perm`` (the
    ``ppermute`` replacement: same permutation semantics, remote DMA
    transport)."""
    leaves = list(leaves)
    if not leaves:
        return []
    interpret = _interpret()
    dst, src = _neighbor_logicals(axis, perm)
    idx = jnp.stack([dst, src])
    k = len(leaves)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY) for _ in range(k)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY) for _ in range(k)],
        scratch_shapes=[pltpu.SemaphoreType.DMA] * (2 * k),
    )
    out = pl.pallas_call(
        functools.partial(_permute_leaves_kernel, k=k, interpret=interpret),
        out_shape=[jax.ShapeDtypeStruct(l.shape, l.dtype) for l in leaves],
        grid_spec=grid_spec,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(idx, *leaves)
    return list(out) if isinstance(out, (list, tuple)) else [out]


def permute_wire(wire, axis, perm):
    """Permute a wire pytree one hop over remote DMA (the pallas analog of
    ``algorithms._permute_wire``); zero-size leaves (passthrough codec
    scale placeholders) pass through untouched. The transfer is recorded as
    a ``comm:remote_dma`` span so trace consumers see the hop's wire bytes
    exactly like a ``comm:ppermute``."""
    from deepspeed_tpu.comm import comm as dist

    leaves, treedef = jax.tree_util.tree_flatten(wire)
    live = [(i, l) for i, l in enumerate(leaves) if l.size > 0]
    if not live:
        return wire
    nbytes = sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize for _, l in live)
    proxy = jax.ShapeDtypeStruct((nbytes,), jnp.int8)
    with dist._record("remote_dma", axis, proxy, backend="pallas"):
        moved = remote_permute_leaves([l for _, l in live], axis, perm)
    out = list(leaves)
    for (i, _), m in zip(live, moved):
        out[i] = m
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------------ fused hop kernel


def _block_math(codec: Codec):
    """(encode, decode, wire_dtype) — the shared ``ops.quant`` block math
    the fused kernel runs in VMEM, identical to the unfused wire codecs."""
    from deepspeed_tpu.ops.quant import (fp8_block_dequant, fp8_block_math,
                                         int8_block_dequant, int8_block_math)

    if codec.name == "int8":
        return int8_block_math, int8_block_dequant, jnp.int8
    if codec.name == "fp8":
        return fp8_block_math, fp8_block_dequant, jnp.float8_e4m3fn
    raise ValueError(f"no fused kernel for codec {codec.name!r}")


def _fused_hop_kernel(idx_ref, send_blk, recv_blk, out_blk,
                      send_q, send_s, recv_q, recv_s,
                      sq_sem, ss_sem, rq_sem, rs_sem, cap_sem,
                      *, C: int, B: int, qb: int, encode, decode,
                      interpret: bool, accumulate: bool = True):
    """One ring hop, fused: grid step ``j`` requantizes chunk ``j`` of the
    accumulated send row into a VMEM wire slot and launches its remote DMA,
    then dequant-accumulates chunk ``j-1`` (whose DMA was launched last
    step) into the output row — chunk ``j``'s interconnect time hides
    behind chunk ``j-1``'s VMEM compute. 2-slot wire buffers; the last grid
    step (``j == C``) only drains."""
    j = pl.program_id(0)
    slot = lax.rem(j, 2)
    prev = lax.rem(j + 1, 2)  # == (j - 1) % 2
    dst, src = idx_ref[2], idx_ref[3]
    nb = B // qb

    def q_copy(s):
        return pltpu.make_async_remote_copy(
            src_ref=send_q.at[s], dst_ref=recv_q.at[s],
            send_sem=sq_sem.at[s], recv_sem=rq_sem.at[s],
            device_id=dst, device_id_type=pltpu.DeviceIdType.LOGICAL)

    def s_copy(s):
        return pltpu.make_async_remote_copy(
            src_ref=send_s.at[s], dst_ref=recv_s.at[s],
            send_sem=ss_sem.at[s], recv_sem=rs_sem.at[s],
            device_id=dst, device_id_type=pltpu.DeviceIdType.LOGICAL)

    @pl.when(j == 0)
    def _():
        _entry_barrier(dst, src, interpret)

    @pl.when(j < C)
    def _send():
        @pl.when(j >= 2)
        def _():
            # slot reuse: our previous DMAs out of this slot must have left
            # the buffer, and (compiled mode) the receiver must have drained
            # the chunk we sent into ITS slot two steps ago — the credit it
            # signals back when consuming
            q_copy(slot).wait_send()
            s_copy(slot).wait_send()
            if not interpret:
                pltpu.semaphore_wait(cap_sem, 1)
        x = send_blk[0].astype(jnp.float32).reshape(nb, qb)
        q, s = encode(x)
        send_q[slot] = q.reshape(B)
        send_s[slot] = s.reshape(nb)
        q_copy(slot).start()
        s_copy(slot).start()

    @pl.when(j > 0)
    def _recv():
        q_copy(prev).wait_recv()
        s_copy(prev).wait_recv()
        deq = decode(recv_q[prev].reshape(nb, qb), recv_s[prev].reshape(nb, 1))
        if accumulate:
            out_blk[0] = recv_blk[0] + deq.reshape(B).astype(jnp.float32)
        else:
            # the all-to-all hop: the PR-8 fused reduce hop MINUS the
            # accumulate — the dequantized wire IS the received row
            out_blk[0] = deq.reshape(B).astype(jnp.float32)
        if not interpret:
            # grant the sender upstream one wire-slot credit
            pltpu.semaphore_signal(cap_sem, 1, device_id=src,
                                   device_id_type=pltpu.DeviceIdType.LOGICAL)

    # semaphore balance: every DMA/credit semaphore must read zero at kernel
    # exit — consecutive hop kernels reuse the same physical scratch
    # semaphores, so a leftover send credit would let the NEXT hop's
    # wait_send pass before its own DMA drained the VMEM slot, corrupting
    # wire data. The send loop waits slot s only when a LATER send reuses it
    # (j in [2, C-1]), which leaves the final min(C, 2) sends outstanding;
    # wait them here. cap_sem (compiled mode only): the downstream receiver
    # signals C credits but the send loop consumes only C-2 (the first two
    # sends ride the free slots) — draining the rest doubles as
    # back-pressure: this hop cannot retire until the downstream rank
    # consumed every chunk.
    @pl.when(j == C)
    def _drain():
        for s in ([0] if C == 1 else [(C - 2) % 2, (C - 1) % 2]):
            q_copy(s).wait_send()
            s_copy(s).wait_send()
        if not interpret:
            pltpu.semaphore_wait(cap_sem, min(C, 2))


def _fused_hop(acc: jax.Array, send_idx, recv_idx, dst, src, *,
               C: int, B: int, qb: int, codec: Codec,
               accumulate: bool = True) -> jax.Array:
    """acc ``[n, Lp]`` fp32 (``Lp == C*B``) -> the updated receive row
    ``[Lp]``: ``acc[recv_idx] + dequant(wire(acc[send_idx]))`` where the
    wire crossed the interconnect quantized. ONE program.
    ``accumulate=False`` drops the add (the all-to-all dispatch hop): the
    returned row is ``dequant(wire(acc[send_idx]))`` from the upstream
    neighbor."""
    encode, decode, wdtype = _block_math(codec)
    interpret = _interpret()
    if not interpret:
        raise NotImplementedError(
            f"pallas collectives with the fused {codec.name} hop are not "
            "supported on this compiler (Mosaic refuses the kernel's (1, B) "
            "wire blocks — see pallas_backend.FUSED_CODECS); use "
            f"algorithm='ring' with codec={codec.name!r}, or a pallas "
            "algorithm with codec='none'")
    nb = B // qb
    idx = jnp.stack([send_idx.astype(jnp.int32), recv_idx.astype(jnp.int32),
                     dst, src])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C + 1,),
        in_specs=[
            # chunk j of the row being sent (pipelined HBM->VMEM by pallas)
            pl.BlockSpec((1, B), lambda j, idx: (idx[0], jnp.minimum(j, C - 1))),
            # chunk j-1 of the row being accumulated into
            pl.BlockSpec((1, B), lambda j, idx: (idx[1], jnp.maximum(j - 1, 0))),
        ],
        out_specs=pl.BlockSpec((1, B), lambda j, idx: (0, jnp.maximum(j - 1, 0))),
        scratch_shapes=[
            pltpu.VMEM((2, B), wdtype),        # send wire values
            pltpu.VMEM((2, nb), jnp.float32),  # send wire scales
            pltpu.VMEM((2, B), wdtype),        # recv wire values
            pltpu.VMEM((2, nb), jnp.float32),  # recv wire scales
            pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,       # sender flow-control credits
        ],
    )
    out = pl.pallas_call(
        functools.partial(_fused_hop_kernel, C=C, B=B, qb=qb,
                          encode=encode, decode=decode, interpret=interpret,
                          accumulate=accumulate),
        out_shape=jax.ShapeDtypeStruct((1, C * B), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(idx, acc, acc)
    return out[0]


def _chunk_geometry(L: int, block_size: int) -> Tuple[int, int, int]:
    """(C, B, qb): kernel chunks of B elements, each a whole number of
    quantization blocks of qb, covering L once padded to C*B."""
    qb = max(min(int(block_size), L), 1)
    per_chunk = max(_CHUNK_TARGET // qb, 1)
    B = qb * min(per_chunk, -(-L // qb))
    C = -(-L // B)
    return C, B, qb


def fused_ring_reduce_scatter_rows(rows: jax.Array, axis, codec: Codec, *,
                                   reverse: bool = False,
                                   sub: Optional[tuple] = None) -> jax.Array:
    """Ring reduce-scatter of ``[n, L]`` chunk rows with every hop a single
    fused dequant-accumulate-requant kernel — the same schedule as
    ``algorithms._ring_reduce_scatter_rows`` (including ring2d's
    ``sub``-ring form), EQuARX transport. Returns this rank's fully reduced
    chunk ``[L]`` in fp32 (the caller casts at the RS->AG boundary, like
    the unfused path)."""
    from deepspeed_tpu.collectives.algorithms import _hop_span, _ring_perm
    from deepspeed_tpu.comm import comm as dist
    from deepspeed_tpu.utils.compat import axis_size

    if sub is not None:
        n, i, perm, label = sub
        step = 1
    else:
        n = axis_size(axis)
        i = lax.axis_index(axis) if n > 1 else 0
        step = -1 if reverse else 1
        perm = _ring_perm(n, reverse)
        label = f"reduce_scatter:pallas_ring{'-' if reverse else ''}"
    L = rows.shape[1]
    if n == 1:
        return rows[0].astype(jnp.float32)
    C, B, qb = _chunk_geometry(L, codec.block_size)
    Lp = C * B
    acc = rows.astype(jnp.float32)
    if Lp != L:
        acc = jnp.pad(acc, ((0, 0), (0, Lp - L)))
    dst, src = _neighbor_logicals(axis, perm)
    wire_bytes = (Lp + 4 * (Lp // qb)) * 1  # 1B values + fp32 scales, per hop
    proxy = jax.ShapeDtypeStruct((wire_bytes,), jnp.int8)
    for k in range(n - 1):
        send_idx = jnp.asarray((i - step * (1 + k)) % n)
        recv_idx = jnp.asarray((i - step * (2 + k)) % n)
        with _hop_span(label, axis, k, codec, fused=True):
            with dist._record("remote_dma", axis, proxy, backend="pallas",
                              fused=codec.name):
                new_row = _fused_hop(acc, send_idx, recv_idx, dst, src,
                                     C=C, B=B, qb=qb, codec=codec)
        acc = lax.dynamic_update_index_in_dim(acc, new_row[None], recv_idx, axis=0)
    out = lax.dynamic_index_in_dim(acc, jnp.asarray(i), axis=0)[0]
    return out[:L]


def fused_ring_all_to_all_rows(rows: jax.Array, axis, codec: Codec, *,
                               n: int, i, perm_k, label: str) -> jax.Array:
    """All-to-all of ``[n, L]`` destination rows with every phase a single
    fused requantize -> remote-DMA -> dequantize kernel (the EQuARX fused
    reduce hop of :func:`fused_ring_reduce_scatter_rows` minus the
    accumulate) — the shift schedule of
    ``algorithms._ring_all_to_all_rows``: phase k moves the row destined
    ``k`` ranks ahead directly via the distance-k permutation ``perm_k(k)``.
    Each row crosses exactly one hop, so the wire quantizes exactly once,
    same as the unfused encode-once path. The own row never leaves HBM and
    stays raw. Returns ``[n, L]`` rows ordered by source rank, in the
    payload dtype."""
    from deepspeed_tpu.collectives.algorithms import _hop_span
    from deepspeed_tpu.comm import comm as dist

    L = rows.shape[1]
    if n == 1:
        return rows
    C, B, qb = _chunk_geometry(L, codec.block_size)
    Lp = C * B
    acc = rows.astype(jnp.float32)
    if Lp != L:
        acc = jnp.pad(acc, ((0, 0), (0, Lp - L)))
    out = jnp.zeros((n, Lp), jnp.float32)
    out = lax.dynamic_update_index_in_dim(
        out, lax.dynamic_index_in_dim(acc, jnp.asarray(i), axis=0),
        jnp.asarray(i), axis=0)  # own row: raw, no wire crossed
    wire_bytes = (Lp + 4 * (Lp // qb)) * 1  # 1B values + fp32 scales, per hop
    proxy = jax.ShapeDtypeStruct((wire_bytes,), jnp.int8)
    for k in range(1, n):
        dst, src = _neighbor_logicals(axis, perm_k(k))
        send_idx = jnp.asarray((i + k) % n)
        with _hop_span(label, axis, k - 1, codec, fused=True):
            with dist._record("remote_dma", axis, proxy, backend="pallas",
                              fused=codec.name):
                new_row = _fused_hop(acc, send_idx, send_idx, dst, src,
                                     C=C, B=B, qb=qb, codec=codec,
                                     accumulate=False)
        out = lax.dynamic_update_index_in_dim(
            out, new_row[None], jnp.asarray((i - k) % n), axis=0)
    return out[:, :L].astype(rows.dtype)
