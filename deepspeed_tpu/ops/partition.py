"""Run a Mosaic kernel on each device's shard of a global-SPMD program.

GSPMD cannot partition a Mosaic kernel: inside a jit over more than one
device the lowering raises "Mosaic kernels cannot be automatically
partitioned. Please wrap the call in a shard_map" — the first thing the
four-chip bring-up hit. Interpret mode lowers to plain HLO, so the CPU test
lane never sees it. Every op whose Pallas implementation can be reached from
a model therefore runs it through :func:`per_shard`: a ``shard_map`` over
every mesh axis that is not already manual, with the op saying which of its
dims may be split over which axes.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

from jax.sharding import PartitionSpec as P


def kernel_mesh():
    """``(mesh, free_axes)`` when a Mosaic kernel called from this trace
    context needs wrapping — an active multi-device mesh with axes that are
    still auto — else ``None`` (single device, no mesh, already inside a
    full-manual shard_map, or inside somebody else's mesh)."""
    from jax._src import mesh as mesh_lib

    from deepspeed_tpu.topology.mesh import get_mesh, has_mesh

    if not has_mesh():
        return None
    mesh, ctx = get_mesh(), mesh_lib.get_abstract_mesh()
    if mesh.size == 1 or (
            ctx.axis_names and tuple(ctx.axis_names) != tuple(mesh.axis_names)):
        return None
    free = tuple(a for a in mesh.axis_names if a not in set(ctx.manual_axes))
    return (mesh, free) if free else None


def live_axes(mesh, free: Sequence[str], axes: Sequence[str], *dims: int
              ) -> Optional[Tuple[str, ...]]:
    """The axes of ``axes`` a dim may be split over: still auto, wider than
    one, and together dividing every size in ``dims`` (all the dims that
    must split alike). ``None`` — compute that dim replicated — otherwise:
    never wrong, only redundant."""
    keep = tuple(a for a in axes if a in free and mesh.shape[a] > 1)
    n = 1
    for a in keep:
        n *= mesh.shape[a]
    return keep if keep and all(d % n == 0 for d in dims) else None


def per_shard(fn: Callable, mesh, free: Sequence[str], in_specs, out_specs) -> Callable:
    from deepspeed_tpu.utils.compat import shard_map

    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     axis_names=set(free), check_vma=False)


def rowwise_spec(mesh, free: Sequence[str], shape: Sequence[int]) -> P:
    """Spec of a ``[batch, (seq,) ..., features]`` activation for an op that
    is independent per row: batch over the data axes, seq over ``sp``."""
    from deepspeed_tpu.topology.mesh import BATCH_AXES

    lead = [live_axes(mesh, free, BATCH_AXES, shape[0])] if len(shape) > 1 else []
    if len(shape) > 2:
        lead.append(live_axes(mesh, free, ("sp",), shape[1]))
    return P(*lead)
