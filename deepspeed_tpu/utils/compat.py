"""The jax API surface this package leans on, spelled once.

Written for the ONE installation there is (jax/jaxlib 0.9.0, libtpu 0.0.34):
``jax.shard_map``, ``jax.lax.axis_size``, ``jax.typeof``, ``jax.memory`` and
``pltpu.CompilerParams`` all exist there, so nothing here chooses between
versions. What remains is (a) one import point for the symbols that have
moved before — ``shard_map`` and ``axis_size``; a tier-1 lint
(tests/unit/test_no_bare_shard_map.py) keeps call sites from reaching past
it — and (b) helpers that are about BACK-ENDS, not versions
(``with_memory_kind``, ``device_put_unaliased``, ``host_copy_unaliased``).
"""

from __future__ import annotations

from typing import Optional

import jax

shard_map = jax.shard_map


def axis_size(axis, default: Optional[int] = None) -> int:
    """``jax.lax.axis_size`` over an axis name or a tuple of them. This is
    THE axis-size helper — the comm facade, zeropp and the quantized
    collectives all route here.

    Outside a bound-axis context the size is unknowable; pass ``default`` to
    get it back instead of the NameError (the comm facade's record path uses
    ``default=1`` so telemetry works outside shard_map too)."""
    if isinstance(axis, (tuple, list)):
        out = 1
        for a in axis:
            out *= axis_size(a, default=default)
        return out
    try:
        return int(jax.lax.axis_size(axis))
    except Exception:
        if default is not None:
            return int(default)
        raise


def shape_dtype_struct(shape, dtype, *like):
    """``jax.ShapeDtypeStruct`` for a Pallas ``out_shape``, stamped with the
    union of the varying-manual-axes of ``like`` (``jax.typeof(x).vma``) so
    the kernel composes with ``check_vma`` shard_maps."""
    vma = frozenset()
    for a in like:
        vma = vma | getattr(jax.typeof(a), "vma", frozenset())
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams`` (imported lazily: Pallas TPU is heavy)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def memory_space(space: str):
    """A ``jax.device_put`` target selecting host vs device memory
    (sharding-preserving memory-kind transfer, works inside jit)."""
    return jax.memory.Space.Host if space == "host" else jax.memory.Space.Device


def with_memory_kind(sharding, kind: str):
    """``sharding.with_memory_kind(kind)`` with a device-capability fallback.

    CPU devices on this jax address exactly ONE memory space
    (``unpinned_host``) — there is no pinned-host/device split to place
    into, and constructing a sharding with either kind raises ``ValueError:
    Could not find memory addressable by device cpu``. Offload placement
    (ZeRO-Inference's pinned-host weights, the stream-on-read device
    reads) degrades to the device-set's default kind there: every
    ``device_put`` through the returned sharding is a same-space no-op, so
    the code path stays exercised end-to-end on CPU instead of crashing,
    and real TPU/GPU backends get the requested kind unchanged."""
    try:
        return sharding.with_memory_kind(kind)
    except ValueError:
        # requested kind unaddressable on this backend: keep the sharding's
        # current (default) memory kind — placement becomes the identity
        return sharding


def device_put_unaliased(arr, sharding):
    """``jax.device_put`` of host numpy into buffers XLA owns EXCLUSIVELY.

    On the CPU backend, ``device_put`` of a 64-byte-aligned numpy array is
    ZERO-COPY: the resulting ``jax.Array`` (or its device-0 shard under a
    replicated sharding) aliases numpy-owned memory. A checkpoint-restored
    leaf flows straight into the engine's compiled steps, which DONATE
    their state buffers — XLA then reuses memory it does not exclusively
    own, and the glibc heap corrupts ("corrupted double-linked list" aborts
    / segfaults a few steps after restore, nondeterministic because it
    hinges on malloc returning a 64-byte-aligned block for that particular
    array). This is the PR-1 checkpoint landmine, root-caused by the PR-6
    fault-injection work. Copying through a deliberately misaligned staging
    buffer breaks the zero-copy precondition, so PJRT always copies into
    its own allocation. Every restore path places leaves through here.
    """
    import numpy as np

    if isinstance(arr, jax.Array):  # already XLA-owned: plain transfer is safe
        return jax.device_put(arr, sharding)
    arr = np.asarray(arr)
    if arr.nbytes:
        staging = np.empty(arr.nbytes + 64 + arr.itemsize, dtype=np.uint8)
        base = (-staging.ctypes.data) % 64
        off = base + arr.itemsize  # itemsize-aligned for the view, never 64-aligned
        view = staging[off:off + arr.nbytes].view(arr.dtype).reshape(arr.shape)
        np.copyto(view, arr)
        arr = view
    return jax.device_put(arr, sharding)


def host_copy_unaliased(tree):
    """``jax.device_get`` into host memory the CALLER owns exclusively.

    The D2H mirror of :func:`device_put_unaliased`. On the CPU backend
    ``device_get`` of a committed array is ZERO-COPY — the numpy result is a
    VIEW of the PJRT buffer. A donated step is supposed to copy rather than
    alias when the input buffer has live external references, but executables
    deserialized from the persistent compilation cache skip that protection
    on this jax/XLA build (observed under
    ``--xla_backend_optimization_level=1``, the test-harness setting): the
    step writes THROUGH the view, so any ``device_get`` result that outlives
    the next donated step — an async checkpoint writer's queued payload, the
    snapshot boundary copy, a caller-held "state before" reference — silently
    reads the LATER state. A torn/mutated host reference, not heap
    corruption: the memory is PJRT-owned either way. ``np.array(copy=True)``
    breaks the aliasing; every D2H that must stay frozen goes through here.
    """
    import numpy as np

    return jax.tree_util.tree_map(
        lambda x: np.array(jax.device_get(x), copy=True) if x is not None else x,
        tree)
