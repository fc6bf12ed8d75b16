"""KV-block migration transport for disaggregated prefill/decode serving.

ISSUE 14 tentpole leg 2: after a prefill completes on a prefill-pool
replica, the request's KV blocks move to a decode-pool replica as a remote
DMA of pool pages — the T3 fused-hop pattern (PAPERS.md) pointed at pool
memory instead of a wire. Two backends, one buffer format
(:class:`~deepspeed_tpu.inference.paged.MigrationBuffer` — quantized values
+ fp32 scale pages, block-table-ordered, bytes verbatim):

- **device copy** (same process): the export gather's output arrays ARE the
  wire — the destination engine's import scatter consumes them directly.
  jax dispatch is asynchronous, so an export dispatched at a prefill
  boundary streams while the host assembles and dispatches the NEXT
  prefill; the router caps in-flight exports per source at
  ``DEFAULT_MIGRATION_DEPTH`` slots (double-buffered: page streaming of
  request N overlaps the prefill of request N+1).
- **across chips**: :func:`remote_copy_pages` moves the buffer leaves
  (values + scales) between two mesh ranks by ``lax.ppermute`` under a
  point-to-point permutation (:func:`transposition_perm`).

Failure contract (the router's side of it): a migration that cannot import
(destination capacity, layout mismatch, any exception) leaves the request
live on its SOURCE replica, which degrades to mixed-mode serving for it —
an admitted request is never dropped.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

# in-flight export cap per source replica: 2 = double-buffered (the export
# of request N streams while the source prefills request N+1; a third
# would just queue behind the first on the device stream)
DEFAULT_MIGRATION_DEPTH = 2


@dataclasses.dataclass
class MigrationTicket:
    """One in-flight post-prefill migration, source replica -> destination
    replica. The export dict is the source engine's
    ``export_request`` result (buffer + geometry); ``tokens`` is the
    request's full context (prompt + generated) at export time — the
    destination re-admits with it and re-indexes its prefix cache from the
    imported (bit-identical) blocks."""

    idx: int                 # request index in the current serve() call
    uid: int                 # uid on the SOURCE replica
    src: int                 # source replica index
    dst: int                 # destination replica index
    export: Dict[str, Any]   # buffer, n_blocks, seen_tokens, pages
    tokens: np.ndarray       # full context at export time
    t_start: float           # export dispatch stamp (migration_ms anchor)
    status: str = "inflight"  # -> "done" | "failed"
    new_uid: Optional[int] = None  # uid on the destination, once imported


def transposition_perm(n: int, src: int, dst: int) -> List[Tuple[int, int]]:
    """Point-to-point migration as a full permutation of ``n`` ranks: the
    src<->dst transposition completed with identity self-edges — the shape
    ``lax.ppermute`` accepts (the hop primitive is a permutation; a
    migration is the degenerate one)."""
    if not (0 <= src < n and 0 <= dst < n):
        raise ValueError(f"src={src}/dst={dst} out of range for {n} ranks")
    if src == dst:
        return [(i, i) for i in range(n)]
    perm = [(i, i) for i in range(n) if i not in (src, dst)]
    perm += [(src, dst), (dst, src)]
    return perm


def remote_copy_pages(leaves: Sequence[jax.Array], mesh, axis_name: str,
                      src: int, dst: int):
    """Move migration-buffer leaves from mesh rank ``src`` to rank ``dst``
    by one ``lax.ppermute`` a leaf.

    ``leaves`` are [n, ...] arrays sharded over ``axis_name`` on their
    leading dim — rank r's shard is ITS local pages (for a migration only
    rank ``src`` carries payload; the others ride the permutation's
    identity edges). Returns leaves of the same shape where rank ``dst``'s
    shard holds rank ``src``'s pages, bytes verbatim (values and scale pages
    alike).
    """
    from deepspeed_tpu.utils.compat import shard_map

    n = mesh.shape[axis_name]
    perm = transposition_perm(n, src, dst)
    leaves = list(leaves)

    def hop(*shards):
        return tuple(lax.ppermute(s, axis_name, perm) for s in shards)

    spec = P(axis_name)
    f = shard_map(hop, mesh=mesh,
                  in_specs=tuple(spec for _ in leaves),
                  out_specs=tuple(spec for _ in leaves),
                  check_vma=False)
    return list(f(*leaves))
