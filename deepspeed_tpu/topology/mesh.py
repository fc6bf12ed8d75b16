"""Device mesh / parallelism topology.

TPU-native replacement for the reference's process-group plumbing
(``deepspeed/utils/groups.py``, ``deepspeed/runtime/pipe/topology.py``): one
``jax.sharding.Mesh`` with named axes carries every parallel dimension, and
"process groups" become axis names referenced by shardings and collectives.

Canonical axis order (outermost/slowest first)::

    ("pp", "dp", "fsdp", "ep", "sp", "tp")

``pp`` (pipeline) is outermost so multi-slice deployments can run it over DCN;
``tp`` is innermost so tensor-parallel collectives ride the fastest ICI links.
The data-parallel world (for batch sharding + the batch-size triad) is the
product ``dp * fsdp``: ZeRO-3/FSDP shards both parameters and batch over
``fsdp``. Ulysses sequence parallelism shards sequence over ``sp``; its ranks
also act as data-parallel for parameter purposes (reference
``seq_data_parallel_group``, ``runtime/engine.py:1296``).
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

MESH_AXES: Tuple[str, ...] = ("pp", "dp", "fsdp", "ep", "sp", "tp")

# Axes over which a batch is sharded (each rank of these sees distinct samples).
BATCH_AXES: Tuple[str, ...] = ("dp", "fsdp")
# Axes over which gradients must be summed (all data-like axes incl. sequence).
GRAD_REDUCE_AXES: Tuple[str, ...] = ("dp", "fsdp", "sp")


def resolve_axis_sizes(axis_sizes: Dict[str, int], n_devices: int) -> Dict[str, int]:
    """Resolve -1 axis sizes: the single -1 axis absorbs remaining devices.

    Mirrors the reference's implicit ``dp = world // (pp*mp*ep)`` arithmetic
    (``runtime/pipe/topology.py`` / ``utils/groups.py:236``).
    """
    sizes = {ax: int(axis_sizes.get(ax, 1)) for ax in MESH_AXES}
    wildcard = [ax for ax, s in sizes.items() if s == -1]
    if len(wildcard) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {wildcard}")
    fixed = 1
    for ax, s in sizes.items():
        if s != -1:
            if s < 1:
                raise ValueError(f"Mesh axis {ax} must be >=1 or -1, got {s}")
            fixed *= s
    if wildcard:
        if n_devices % fixed != 0:
            raise ValueError(
                f"Device count {n_devices} not divisible by fixed axis product {fixed}"
            )
        sizes[wildcard[0]] = n_devices // fixed
    elif fixed != n_devices:
        raise ValueError(
            f"Mesh axis product {fixed} != device count {n_devices}; "
            f"set one axis to -1 to absorb remaining devices"
        )
    return sizes


def build_mesh(
    mesh_config=None,
    devices: Optional[Sequence] = None,
    axis_sizes: Optional[Dict[str, int]] = None,
) -> Mesh:
    """Construct the named device mesh.

    ``mesh_config`` is a ``MeshConfig`` (config section); ``axis_sizes`` may be
    passed directly for tests. Multi-slice (num_slices > 1) uses a hybrid
    ICI/DCN mesh with the configured ``dcn_axis`` spanning slices.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)

    if axis_sizes is None:
        if mesh_config is None:
            axis_sizes = {"dp": -1}
        else:
            axis_sizes = {ax: getattr(mesh_config, ax) for ax in MESH_AXES}
    sizes = resolve_axis_sizes(axis_sizes, n)
    shape = tuple(sizes[ax] for ax in MESH_AXES)

    num_slices = getattr(mesh_config, "num_slices", 1) if mesh_config is not None else 1
    if num_slices > 1:
        dcn_axis = getattr(mesh_config, "dcn_axis", "dp")
        ici_shape = list(shape)
        dcn_shape = [1] * len(MESH_AXES)
        idx = MESH_AXES.index(dcn_axis)
        if sizes[dcn_axis] % num_slices != 0:
            raise ValueError(f"dcn axis {dcn_axis}={sizes[dcn_axis]} not divisible by num_slices={num_slices}")
        ici_shape[idx] = sizes[dcn_axis] // num_slices
        dcn_shape[idx] = num_slices
        device_array = mesh_utils.create_hybrid_device_mesh(
            tuple(ici_shape), tuple(dcn_shape), devices=devices, allow_split_physical_axes=True
        )
    else:
        try:
            device_array = mesh_utils.create_device_mesh(shape, devices=devices, allow_split_physical_axes=True)
        except Exception:
            device_array = np.asarray(devices).reshape(shape)
    return Mesh(device_array, MESH_AXES)


# ---------------------------------------------------------------------------
# Active-mesh registry (the analog of groups.initialize() global state)
# ---------------------------------------------------------------------------

_ACTIVE_MESH: Optional[Mesh] = None


def set_mesh(mesh: Mesh) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def get_mesh() -> Mesh:
    if _ACTIVE_MESH is None:
        raise RuntimeError("No active mesh; call deepspeed_tpu.initialize() or set_mesh() first")
    return _ACTIVE_MESH


def has_mesh() -> bool:
    return _ACTIVE_MESH is not None


@contextlib.contextmanager
def mesh_context(mesh: Mesh):
    global _ACTIVE_MESH
    prev = _ACTIVE_MESH
    _ACTIVE_MESH = mesh
    try:
        yield mesh
    finally:
        _ACTIVE_MESH = prev


# What a model's products ask while the engine traces a step: ``path`` of the
# weight -> a ``dot_general`` of the engine's own, or None for flax's. Nothing
# is published outside such a trace (ZeRO-3 publishes ``runtime/zero.py``'s).
_DOT_GENERALS: Optional[Callable[[Tuple[str, ...]], Optional[Callable]]] = None


@contextlib.contextmanager
def dot_general_context(provider: Optional[Callable[[Tuple[str, ...]], Optional[Callable]]]):
    global _DOT_GENERALS
    prev, _DOT_GENERALS = _DOT_GENERALS, provider
    try:
        yield provider
    finally:
        _DOT_GENERALS = prev


def dot_general_for(path: Sequence[str]) -> Optional[Callable]:
    """``dot_general=`` for the flax product that reads the parameter at
    ``path``: None (flax's own) unless the trace under way published another."""
    return None if _DOT_GENERALS is None else _DOT_GENERALS(tuple(path))


# ---------------------------------------------------------------------------
# World-size helpers (the groups.py accessor API surface)
# ---------------------------------------------------------------------------

def axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def get_data_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    """Ranks that see distinct batches (reference ``groups._get_data_parallel_world_size``)."""
    mesh = mesh or get_mesh()
    return int(np.prod([mesh.shape[a] for a in BATCH_AXES]))


def get_model_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return mesh.shape["tp"]


def get_pipeline_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return mesh.shape["pp"]


def get_expert_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return mesh.shape["ep"]


def get_sequence_parallel_world_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return mesh.shape["sp"]


def get_world_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return mesh.size


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------

def batch_pspec(mesh: Optional[Mesh] = None, seq_axis: bool = True) -> PartitionSpec:
    """PartitionSpec for a [batch, seq, ...] array: batch over (dp, fsdp), seq over sp."""
    mesh = mesh or get_mesh()
    batch_axes = tuple(a for a in BATCH_AXES if mesh.shape[a] > 1) or None
    if seq_axis and mesh.shape["sp"] > 1:
        return PartitionSpec(batch_axes, "sp")
    return PartitionSpec(batch_axes)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


class ProcessTopology:
    """Axis-coordinate bookkeeping (reference ``runtime/pipe/topology.py:12``).

    Maps a flat rank to named-axis coordinates and back, for launcher/debug
    tooling. The mesh itself is authoritative for placement; this exists for
    API parity and host-side logic (checkpoint naming, logging).
    """

    def __init__(self, axes: Sequence[str], dims: Sequence[int]):
        if len(axes) != len(dims):
            raise ValueError("axes and dims must have equal length")
        self.axes = list(axes)
        self.dims = list(int(d) for d in dims)

    def get_rank(self, **coords) -> int:
        missing = set(self.axes) - set(coords)
        if missing:
            raise ValueError(f"Missing coordinates: {missing}")
        rank = 0
        for ax, dim in zip(self.axes, self.dims):
            c = coords[ax]
            if not 0 <= c < dim:
                raise ValueError(f"Coordinate {ax}={c} out of range [0,{dim})")
            rank = rank * dim + c
        return rank

    def get_coord(self, rank: int) -> Dict[str, int]:
        coords = {}
        for ax, dim in zip(reversed(self.axes), reversed(self.dims)):
            coords[ax] = rank % dim
            rank //= dim
        return {ax: coords[ax] for ax in self.axes}

    def get_dim(self, axis: str) -> int:
        return self.dims[self.axes.index(axis)]

    @property
    def world_size(self) -> int:
        return int(np.prod(self.dims))

    def filter_match(self, **coords) -> List[int]:
        """All ranks whose coordinates match the given values."""
        return [r for r in range(self.world_size) if all(self.get_coord(r)[a] == v for a, v in coords.items())]


def topology_from_mesh(mesh: Mesh) -> ProcessTopology:
    return ProcessTopology(list(mesh.axis_names), [mesh.shape[a] for a in mesh.axis_names])
