"""ZeRO stages as sharding placements.

TPU-native re-design of the reference ZeRO stack (``zero/stage_1_and_2.py``,
``zero/stage3.py``, ``zero/partition_parameters.py`` — ~11k LoC of hook/bucket
machinery). On TPU the same memory states are obtained by *placing* the train
state on the mesh and letting XLA schedule the collectives:

  stage 0: params/grads/opt replicated; grads all-reduced (psum) over data axes
  stage 1: optimizer state + fp32 master params sharded over the data axes
           (update computed on the shard, updated weights all-gathered —
           exactly the reference's partitioned fp32 update + bucketed
           allgather, ``stage_1_and_2.py:1835``)
  stage 2: + gradient accumulation buffers sharded (each micro-batch's grads
           are reduce-scattered into the shard instead of all-reduced,
           ``stage_1_and_2.py:1057 average_tensor``)
  stage 3: + parameters themselves sharded over the ``fsdp`` mesh axis
           per-tensor. The collectives of the scanned layers are the
           program's own (``ScanGathers``, below): every product of a
           scanned layer gathers its weight's shard whole (an asynchronous
           all-gather that hangs on the scan body's inputs alone, the
           reference's prefetch of ``partitioned_param_coordinator.py``),
           gathers it again in the backward, and reduce-scatters the gradient
           into the accumulator's placement by a ring of its own, both ways
           round, whose hops wait for no product. Left to the partitioner the
           same products become one-way rings of collective-permutes, each
           hop tied to a quarter of one product. Everything else stays the
           partitioner's: the embedding, the head, layers outside the scan,
           the optimizer's gathers, any leaf whose ``fsdp`` shares its
           dimension with another axis or sits on the layers' own, a mesh
           with ``tp``, ``sp``, ``ep`` or ``pp`` longer than 1, the ZeRO++
           micro-step (which gathers the whole tree itself) and the
           host-offload micro-step.

MiCS (``zero/mics.py``) falls out of the mesh shape: ``fsdp < dp_world`` gives
sub-group sharding with replication across groups.

The unit of partitioning is a whole tensor dimension (largest dimension
divisible by the shard count), not a flat byte range: XLA needs dimension
shardings. Tensors too small to matter (< ``param_persistence_threshold``
elements, reference ``zero/config.py``) stay replicated, which mirrors the
reference's persistent-parameter optimization (``parameter_offload.py:261``).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.config.config import ZeroConfig
from deepspeed_tpu.topology.mesh import BATCH_AXES, get_mesh

# Leaves smaller than this stay replicated in stage-1/2 opt-state sharding
# (sharding a 10-element bias buys nothing and costs collective latency).
DEFAULT_SHARD_MIN_NUMEL = 2048


def _fill_largest_free_dim(
    base: list,
    shape: Sequence[int],
    mesh: Mesh,
    axes: Tuple[str, ...],
    min_numel: int,
) -> list:
    """Shared policy: shard the largest dim of ``shape`` not already occupied
    in ``base`` (and divisible by the joint axis size) over ``axes``."""
    live = tuple(a for a in axes if mesh.shape[a] > 1)
    if not live:
        return base
    n = int(np.prod([mesh.shape[a] for a in live]))
    if int(np.prod(shape or (0,))) < max(min_numel, n):
        return base
    free = [i for i, e in enumerate(base) if e is None and shape[i] % n == 0 and shape[i] >= n]
    if free:
        dim = max(free, key=lambda i: shape[i])
        base[dim] = live if len(live) > 1 else live[0]
    return base


def auto_partition_spec(
    shape: Sequence[int],
    mesh: Mesh,
    axes: Tuple[str, ...],
    min_numel: int = DEFAULT_SHARD_MIN_NUMEL,
) -> PartitionSpec:
    """Shard the largest divisible dimension of ``shape`` over ``axes`` (jointly)."""
    spec = _fill_largest_free_dim([None] * len(shape), shape, mesh, axes, min_numel)
    return PartitionSpec(*spec) if any(e is not None for e in spec) else PartitionSpec()


def param_partition_spec(
    shape: Sequence[int],
    mesh: Mesh,
    zero_config: ZeroConfig,
    base_spec: Optional[PartitionSpec] = None,
) -> PartitionSpec:
    """PartitionSpec for a *parameter* under the configured ZeRO stage.

    ``base_spec`` carries model-parallel placements (e.g. a ``tp`` entry from
    AutoTP rules); stage 3 then shards the largest still-unsharded dimension
    over ``fsdp``. Stages 0-2 keep only the base (model-parallel) placement.
    """
    base = list(base_spec) if base_spec is not None else []
    base = base + [None] * (len(shape) - len(base))
    if zero_config.stage >= 3:
        base = _fill_largest_free_dim(
            base, shape, mesh, ("fsdp",), max(zero_config.param_persistence_threshold, 1)
        )
    return PartitionSpec(*base) if any(e is not None for e in base) else PartitionSpec()


def master_partition_spec(
    shape: Sequence[int],
    mesh: Mesh,
    zero_config: ZeroConfig,
    base_spec: Optional[PartitionSpec] = None,
) -> PartitionSpec:
    """PartitionSpec for fp32 master params / optimizer moments / grad accumulators.

    Stage >=1 shards the largest free dimension over the data axes (dp and
    fsdp jointly) — the ZeRO insight that optimizer state need only exist once
    per data-parallel world. Model-parallel placements from ``base_spec``
    (e.g. ``tp`` entries) are preserved.
    """
    base = list(base_spec) if base_spec is not None else []
    base = base + [None] * (len(shape) - len(base))
    if zero_config.stage >= 1:
        base = _fill_largest_free_dim(base, shape, mesh, BATCH_AXES, DEFAULT_SHARD_MIN_NUMEL)
    return PartitionSpec(*base) if any(e is not None for e in base) else PartitionSpec()


def state_sharding(tree: Any, mesh: Mesh, spec_fn, base_specs: Any = None) -> Any:
    """Map ``spec_fn(shape, base_spec) -> PartitionSpec`` over a pytree.

    ``base_specs`` (same structure as ``tree``) carries model-parallel specs.
    """

    def _one(leaf, base):
        shape = getattr(leaf, "shape", ())
        if shape is None or len(shape) == 0:
            return NamedSharding(mesh, PartitionSpec())
        return NamedSharding(mesh, spec_fn(tuple(shape), base))

    if base_specs is None:
        # PartitionSpec is a pytree leaf, so an empty spec is a safe "no base"
        base_specs = jax.tree_util.tree_map(lambda _: PartitionSpec(), tree)
    return jax.tree_util.tree_map(_one, tree, base_specs)


def params_sharding(params: Any, mesh: Mesh, zero_config: ZeroConfig, base_specs: Any = None) -> Any:
    return state_sharding(
        params, mesh, lambda s, b: param_partition_spec(s, mesh, zero_config, b), base_specs
    )


def master_sharding(tree: Any, mesh: Mesh, zero_config: ZeroConfig, base_specs: Any = None) -> Any:
    """Sharding for fp32 master params / grad accumulators (data-axes rule)."""
    return state_sharding(
        tree, mesh, lambda s, b: master_partition_spec(s, mesh, zero_config, b), base_specs
    )


def grads_sharding(params: Any, mesh: Mesh, zero_config: ZeroConfig, base_specs: Any = None) -> Any:
    """Sharding for the gradient-accumulation buffer.

    Stage >=2 shards it like the master state (reduce-scatter per micro-batch);
    stages 0/1 keep full gradients (model-parallel placement only), matching
    the reference's allreduce-then-partition behavior.
    """
    if zero_config.stage < 2:
        return state_sharding(
            params, mesh, lambda s, b: PartitionSpec(*b) if b else PartitionSpec(), base_specs
        )
    return master_sharding(params, mesh, zero_config, base_specs)


# --------------------------------------------- a scanned layer's own gathers
def _axes(entry) -> Tuple[str, ...]:
    """The mesh axes one entry of a ``PartitionSpec`` names."""
    return () if entry is None else entry if isinstance(entry, tuple) else (entry,)


def _held_dim(spec: PartitionSpec) -> Optional[int]:
    """The one dimension of a stacked leaf's ``spec`` that holds ``fsdp``,
    alone; None where there is none, or it is the layers' own (the first)."""
    held = [i for i, entry in enumerate(spec) if "fsdp" in _axes(entry)]
    if len(held) != 1 or held[0] == 0 or _axes(spec[held[0]]) != ("fsdp",):
        return None
    return held[0]


def _gather(w_shard, dim):
    with jax.named_scope("zero_gather"):  # a scope of its own in a device trace
        return lax.all_gather(w_shard, "fsdp", axis=dim, tiled=True)


def _scatter(dw, dim, n):
    """``dw`` summed over the ``n`` chips of ``fsdp``, a chip keeping its shard
    of ``dim``: what ``lax.psum_scatter(..., tiled=True)`` gives, written as a
    ring of ``n - 1`` hops that carries one half of every shard each way round. A
    hop is tied to no product and the chip's compiler runs it asynchronously;
    its reduce-scatter is synchronous and uses one direction (``PERF.md``
    section 6, PR 45)."""
    me, rows = lax.axis_index("fsdp"), dw.shape[dim] // n
    with jax.named_scope("zero_scatter"):
        halves = []
        for way, (start, size) in ((1, (0, rows // 2)), (-1, (rows // 2, rows - rows // 2))):
            if not size:
                continue
            to_next = [(i, (i + way) % n) for i in range(n)]
            # hop h hands on the partial sum of the shard that is h more chips away against the ring's way
            mine = lambda h: lax.dynamic_slice_in_dim(dw, ((me - way * h) % n) * rows + start, size, dim)  # noqa: E731
            part = mine(1)
            for h in range(2, n + 1):
                part = lax.ppermute(part, "fsdp", to_next) + mine(h)
            halves.append(part)
        return halves[0] if len(halves) == 1 else lax.concatenate(halves, dim)


def scan_gathers(shardings: Any, params: Any, mesh: Mesh) -> Optional["ScanGathers"]:
    """What a ZeRO-3 engine publishes while it traces a step, or None where the
    layer scan's collectives stay the partitioner's: ``fsdp`` = 1, and a mesh
    with ``tp``, ``sp``, ``ep`` or ``pp`` longer than 1, which would have to
    stay the partitioner's inside the products' manual region (no test and no
    cell runs that form on the chip, and XLA's CPU compiler aborts on it in
    bf16)."""
    if mesh.shape["fsdp"] == 1 or any(mesh.shape[a] > 1 for a in mesh.axis_names if a not in BATCH_AXES):
        return None
    return ScanGathers(shardings, params)


class ScanGathers:
    """What the engine publishes while it traces a ZeRO-3 step
    (``topology.mesh.dot_general_context``): the placement of every compute
    parameter by its path, for the products of a scanned layer to ask for
    their ``dot_general``. ``received`` is what a chip receives a micro-step
    from each gather traced so far, forward and backward, counted from the
    shapes where the product is traced: which products engage is known there."""

    def __init__(self, shardings: Any, params: Any):
        named = lambda s: isinstance(s, NamedSharding)  # noqa: E731
        flat = jax.tree_util.tree_flatten_with_path(shardings, is_leaf=named)[0]
        shapes = [tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(params)]
        # path -> (the leaf's spec, its shape)
        self.leaves: Dict[Tuple[str, ...], Tuple[PartitionSpec, Tuple[int, ...]]] = {
            tuple(str(getattr(k, "key", k)) for k in path): (sh.spec, shape)
            for (path, sh), shape in zip(flat, shapes)}
        self.received: Dict[Tuple[str, ...], int] = {}

    @property
    def received_bytes(self) -> int:
        return sum(self.received.values())

    def __call__(self, path: Tuple[str, ...]) -> Optional[Callable]:
        """``dot_general=`` for the flax product that reads the leaf at
        ``path``, or None (flax's own: the program it always traced) unless
        the leaf's placement holds ``fsdp``, alone, on a dimension after the
        first."""
        if path not in self.leaves or _held_dim(self.leaves[path][0]) is None:
            return None  # no parameter of the engine's, replicated, or sharded over the layers themselves
        return functools.partial(self._product, path)

    def _product(self, path, x, w, dimension_numbers, precision=None, preferred_element_type=None):
        """``dot_general(x, w)`` where ``w`` is one layer's slice of the
        stacked leaf at ``path``, as a ``shard_map`` over the live batch axes
        of ``dot_general(x, all_gather(w_shard))``: one whole gather a leaf,
        hanging on nothing but the scan body's inputs, so the scheduler may
        start it under any earlier compute. The backward keeps ``x`` and the
        SHARD, gathers again, and hands the weight's gradient over by
        ``_scatter`` into the shard's placement (which is the accumulator's).
        The partitioner would turn the same contraction into a ring of
        ``collective-permute``s, each hop tied to a quarter of one product and
        all drained at the body's end. Any other product stays the
        partitioner's: a leaf that is not stacked (``w`` is not its slice), a
        batched contraction, rows the batch axes do not divide."""
        from deepspeed_tpu.utils.compat import shard_map

        mesh = get_mesh()  # the one the step is traced on, as every model reads it
        batch_axes = tuple(a for a in BATCH_AXES if mesh.shape[a] > 1)
        n_batch = int(np.prod([mesh.shape[a] for a in batch_axes]))
        (x_contract, _), (x_batch, w_batch) = dimension_numbers
        dot = functools.partial(lax.dot_general, dimension_numbers=dimension_numbers, precision=precision,
                                preferred_element_type=preferred_element_type)
        spec, (layers, *slice_shape) = self.leaves[path]
        if tuple(slice_shape) != w.shape or x_batch or w_batch or 0 in x_contract or x.shape[0] % n_batch:
            return dot(x, w)
        dim = _held_dim(spec) - 1
        others = tuple(a for a in batch_axes if a != "fsdp")
        shards = mesh.shape["fsdp"]
        self.received[path] = 2 * layers * (w.size // shards) * (shards - 1) * w.dtype.itemsize

        @jax.custom_vjp
        def product(x, w_shard):
            return dot(x, _gather(w_shard, dim))

        def backward(kept, g):
            x, w_shard = kept
            dx, dw = jax.vjp(dot, x, _gather(w_shard, dim))[1](g)
            return dx, _scatter(dw, dim, shards)

        product.defvjp(lambda x, w_shard: (product(x, w_shard), (x, w_shard)), backward)

        def replicas_product(x, w_shard):
            # the shard is the same on every replica: this cast's transpose sums its gradient over them
            return product(x, lax.pcast(w_shard, others, to="varying") if others else w_shard)

        rows = PartitionSpec(batch_axes)
        return shard_map(replicas_product, mesh=mesh, in_specs=(rows, PartitionSpec(*[None] * dim, "fsdp")),
                         out_specs=rows)(x, w)
