"""Collectives facade over named mesh axes, with telemetry.

Reference analog: ``deepspeed/comm/comm.py`` — a torch.distributed-compatible
module API where every collective runs through the ``timed_op`` decorator and
``CommsLogger`` aggregates counts/bytes/bandwidth (``utils/comms_logging.py:67``,
``calc_bw_log`` :34, ``log_summary`` ``comm/comm.py:428``).

TPU-native redesign: collectives are *in-program* ``jax.lax`` ops over named
mesh axes, scheduled by XLA — there is no host-side call to time. Telemetry is
therefore **trace-time**: every facade call records (op, axis, bytes, dtype)
when the traced program is built, so after one compiled step the logger holds
the exact collective workload of that step (count x size per op). Bus-bandwidth
estimates use the standard algo->bus factors (allreduce 2(n-1)/n, allgather /
reducescatter (n-1)/n, alltoall (n-1)/n) from the reference's ``calc_bw_log``.

Host control-plane (multi-host rendezvous) maps to ``jax.distributed`` —
``init_distributed()`` here is the analog of ``deepspeed.init_distributed``
(``comm/comm.py:636``): idempotent, env-driven, no-op in single-process runs.
"""

from __future__ import annotations

import collections
import os
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu import telemetry
from deepspeed_tpu.utils.logging import logger


# --------------------------------------------------------------------------
# telemetry
# --------------------------------------------------------------------------


@dataclass
class _OpRecord:
    count: int = 0
    total_bytes: int = 0
    sizes: collections.Counter = field(default_factory=collections.Counter)


class CommsLogger:
    """Trace-time collective telemetry (reference ``CommsLogger``
    ``utils/comms_logging.py:67``)."""

    def __init__(self, enabled: bool = False, verbose: bool = False, debug: bool = False):
        self.enabled = enabled
        self.verbose = verbose
        self.debug = debug
        self._lock = threading.Lock()
        self._records: Dict[str, _OpRecord] = collections.defaultdict(_OpRecord)

    def configure(self, enabled: bool = True, verbose: bool = False, debug: bool = False):
        self.enabled, self.verbose, self.debug = enabled, verbose, debug

    def reset(self):
        with self._lock:
            self._records.clear()

    def record(self, op_name: str, axis: str, nbytes: int, world: int):
        if not self.enabled:
            return
        key = f"{op_name}@{axis}"
        with self._lock:
            rec = self._records[key]
            rec.count += 1
            rec.total_bytes += nbytes
            rec.sizes[(nbytes, world)] += 1
        if self.verbose:
            logger.info(f"comm: {key} size={nbytes}B world={world}")

    @staticmethod
    def _bus_factor(op_name: str, n: int) -> float:
        if n <= 1:
            return 0.0
        if op_name.startswith("all_reduce"):
            return 2 * (n - 1) / n
        return (n - 1) / n  # all_gather / reduce_scatter / all_to_all

    def summary(self) -> List[dict]:
        rows = []
        with self._lock:
            for key, rec in sorted(self._records.items()):
                op, _, axis = key.partition("@")
                rows.append(
                    {
                        "op": op,
                        "axis": axis,
                        "count": rec.count,
                        "total_bytes": rec.total_bytes,
                        "bus_bytes": int(
                            sum(self._bus_factor(op, w) * b * c for (b, w), c in rec.sizes.items())
                        ),
                    }
                )
        return rows

    def log_summary(self):
        rows = self.summary()
        if not rows:
            logger.info("comm summary: no collectives recorded")
            return rows
        width = max(len(r["op"] + r["axis"]) for r in rows) + 4
        logger.info(f"{'op@axis':<{width}} {'count':>8} {'total':>12} {'bus-traffic':>12}")
        for r in rows:
            logger.info(
                f"{r['op'] + '@' + r['axis']:<{width}} {r['count']:>8} "
                f"{_fmt_bytes(r['total_bytes']):>12} {_fmt_bytes(r['bus_bytes']):>12}"
            )
        return rows


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n:.1f}TB"


comms_logger = CommsLogger(enabled=os.environ.get("DSTPU_COMMS_LOGGER", "") == "1")


def configure(enabled: bool = True, verbose: bool = False, debug: bool = False):
    comms_logger.configure(enabled=enabled, verbose=verbose, debug=debug)


def log_summary():
    """Reference ``deepspeed.comm.log_summary()`` (``comm/comm.py:428``)."""
    return comms_logger.log_summary()


def _axis_size(axis) -> int:
    from deepspeed_tpu.utils.compat import axis_size

    # compat resolves the axis-size API move (unit-psum fallback on older
    # jax); outside a bound axis context the size is unknowable -> 1
    return axis_size(axis, default=1)


def _axes_sig(axis):
    """((name, size), ...) for the selector's decision-cache key and the
    schedule compiler's search domain — two meshes with equal world size
    but different axis factorizations must take different decisions. None
    when any axis is unbound (size unknowable outside shard_map)."""
    from deepspeed_tpu.utils.compat import axis_size

    axes = tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)
    sig = []
    for a in axes:
        n = axis_size(a, default=0)
        if n <= 0:
            return None
        sig.append((str(a), int(n)))
    return tuple(sig)


def _itemsize(x) -> int:
    try:
        return jnp.dtype(x.dtype).itemsize
    except Exception:
        return 4


def _nbytes(x) -> int:
    try:
        return int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
    except Exception:
        return 0


def _record(op_name: str, axis, x, **tags):
    """Record one collective into the comms logger AND the telemetry
    subsystem; returns a span context wrapping the ``jax.lax`` call.

    Collectives here are in-program ops, so both records happen at TRACE
    time: the span duration is host tracing time (one per compiled program,
    not per execution), while the (op, axis, dtype, bytes, world) tags are
    the exact per-execution collective workload of the traced step.
    ``tags`` carries extra span attributes (algorithm/codec on the
    algorithmic path) so routing decisions are visible in the trace.
    """
    axis_str = "+".join(axis) if isinstance(axis, (tuple, list)) else str(axis)
    nbytes, world = _nbytes(x), _axis_size(axis)
    comms_logger.record(op_name, axis_str, nbytes, world)
    if op_name in ("ppermute", "remote_dma"):
        # hop-wire census for the collective observatory: inside a routed
        # collective's trace scope these ARE the wire bytes the selector's
        # routing put on the interconnect (no-op outside a scope — pipeline
        # ppermutes etc. are not routed wires)
        from deepspeed_tpu.collectives import observatory as _coll_obs

        _coll_obs.on_wire(nbytes)
    tracer = telemetry.get_tracer()
    if not tracer.enabled:
        return telemetry.NOOP_SPAN
    tracer.count("comm/count")
    tracer.count("comm/bytes", nbytes)
    tracer.count(f"comm/bytes/{op_name}", nbytes)
    dtype = str(getattr(x, "dtype", "unknown"))
    return tracer.span(f"comm:{op_name}", cat="comm", op=op_name, axis=axis_str,
                       bytes=nbytes, dtype=dtype, world=world, **tags)


# --------------------------------------------------------------------------
# collectives (usable inside shard_map / jit with bound axis names)
# --------------------------------------------------------------------------
#
# ``algorithm=`` / ``codec=`` route through deepspeed_tpu.collectives (the
# hop-composed algorithmic library): algorithm None keeps the plain jax.lax
# lowering (XLA picks the implementation), "auto" asks collectives.selector
# for the best (algorithm, codec) per (op, bytes, axis size), and a concrete
# name ("ring" / "bidir" / "rhd" / "ring2d", or "pallas_ring" /
# "pallas_ring2d" for remote-DMA hop kernels with in-kernel fused int8/fp8
# reduction — collectives/pallas_backend.py) forces it. The algorithmic
# path must run inside FULL-MANUAL shard_map (see utils/compat.py).


def _algorithmic(op_name: str, x, axis, algorithm, codec, reduce_op: str = "sum"):
    """Resolve (algorithm, codec) — consulting the selector for "auto" —
    and tag the choice on the facade span.

    A call with no explicit algorithm/codec first picks up the process
    defaults the ``collectives`` config block installed
    (``selector.SelectorConfig.facade_algorithm/codec``). Default-routed
    calls stay on the lax lowering when the algorithmic path cannot serve
    them (multi-axis tuples, max/min reductions) and never apply a lossy
    codec to non-float payloads (token ids, the already-int8 zeropp wire);
    an EXPLICIT algorithm/codec argument is honored verbatim and surfaces
    the library's own errors instead."""
    from deepspeed_tpu.collectives import selector

    if isinstance(axis, (tuple, list)) and len(axis) == 0:
        # an empty axis tuple is the native no-op reduction (lax.pmean(x, ())
        # == x — e.g. grad means on a mesh with no >1 data axis): nothing
        # crosses a wire, so there is nothing to route or quantize
        return None, None
    explicit = algorithm is not None or codec is not None
    from_config = False
    if not explicit:
        cfg = selector.get_config()
        if cfg.facade_algorithm is None:
            return None, None
        if isinstance(axis, (tuple, list)) and len(axis) > 1:
            return None, None  # hierarchical tuples only when asked for
        if reduce_op not in ("sum", "mean", "avg"):
            return None, None  # algorithmic all_reduce has no max/min
        if not jnp.issubdtype(getattr(x, "dtype", jnp.float32), jnp.floating):
            # integer payloads (token ids, counters, the zeropp int8 wire)
            # keep the native lowering under default routing
            return None, None
        algorithm, codec = cfg.facade_algorithm, cfg.facade_codec
        from_config = True
        if op_name == "all_to_all" and algorithm == "rhd":
            # the configured default may be an algorithm this op has no
            # form of (rhd: every block has exactly one destination);
            # default routing keeps the lax lowering — only an EXPLICIT
            # rhd request surfaces the library's error
            return None, None
    if algorithm == "lax":
        return None, None
    if algorithm in (None, "auto"):
        if codec is None and not jnp.issubdtype(
                getattr(x, "dtype", jnp.float32), jnp.floating):
            codec = "none"
        d = selector.select(op_name, _nbytes(x), _axis_size(axis), codec,
                            itemsize=_itemsize(x), axes_sig=_axes_sig(axis))
        if d.algorithm == "lax":
            # measured mode's "don't bother" verdict: the baseline won
            return None, None
        return d.algorithm, d.codec
    if codec is None and from_config:
        # concrete configured algorithm + codec "auto": the selector still
        # picks the wire among the configured candidates
        codec = selector.pick_codec(op_name, _nbytes(x), _axis_size(axis),
                                    algorithm, itemsize=_itemsize(x))
    return algorithm, codec or "none"


def _observe_route(op_name: str, x, axis, algorithm: str, codec: str,
                   block_size: Optional[int]):
    """Trace-time observatory registration of one ROUTED collective: the
    returned context collects this trace's hop/wire census
    (``collectives/observatory.py``). A nullcontext when the observatory is
    disabled — the traced program is identical either way (the observatory
    never adds operations; its timings come from standalone probe
    dispatches)."""
    from deepspeed_tpu.collectives import observatory as _coll_obs
    from deepspeed_tpu.telemetry import numerics as _numerics_obs

    # the numerics observatory registers the same signature for its
    # wire-fidelity probes (lossy codecs only; a no-op when disabled)
    _numerics_obs.note_route(
        op_name, algorithm, codec, _nbytes(x), _itemsize(x),
        _axis_size(axis), axis, str(getattr(x, "dtype", "unknown")),
        block_size)
    return _coll_obs.note_route(
        op_name, algorithm, codec, _nbytes(x), _itemsize(x),
        _axis_size(axis), axis, str(getattr(x, "dtype", "unknown")),
        block_size)


def _resolved_block_size(block_size: Optional[int]) -> Optional[int]:
    """The configured quantization block for auto-routed collectives (the
    caller's explicit block_size wins)."""
    if block_size is not None:
        return block_size
    from deepspeed_tpu.collectives import selector

    return selector.get_config().block_size


def all_reduce(x, axis, op: str = "sum", *, algorithm: Optional[str] = None,
               codec: Optional[str] = None, block_size: Optional[int] = None):
    """psum/pmax/pmin over a named axis (reference ``all_reduce`` ``comm/comm.py``)."""
    alg, cd = _algorithmic("all_reduce", x, axis, algorithm, codec, reduce_op=op)
    if alg is not None:
        from deepspeed_tpu import collectives

        bs = _resolved_block_size(block_size)
        with _record(f"all_reduce_{op}", axis, x, algorithm=alg, codec=cd), \
                _observe_route("all_reduce", x, axis, alg, cd, bs):
            return collectives.all_reduce(x, axis, algorithm=alg, codec=cd, op=op,
                                          block_size=bs)
    with _record(f"all_reduce_{op}", axis, x):
        if op == "sum":
            return jax.lax.psum(x, axis)
        if op == "max":
            return jax.lax.pmax(x, axis)
        if op == "min":
            return jax.lax.pmin(x, axis)
        if op in ("mean", "avg"):
            return jax.lax.pmean(x, axis)
        raise ValueError(f"unsupported reduce op {op!r}")


def all_gather(x, axis, *, concat_axis: int = 0, tiled: bool = True,
               algorithm: Optional[str] = None, codec: Optional[str] = None,
               block_size: Optional[int] = None):
    """all_gather over a named axis (reference ``all_gather_into_tensor``)."""
    if not tiled:
        # untiled gathers have no algorithmic form: explicit requests get a
        # clear error, default routing skips the selector entirely (no
        # cached decision / coll:select event for a path never taken)
        if algorithm is not None or codec is not None:
            raise ValueError("algorithmic all_gather supports tiled=True only")
        alg = cd = None
    else:
        alg, cd = _algorithmic("all_gather", x, axis, algorithm, codec)
    if alg is not None:
        from deepspeed_tpu import collectives

        bs = _resolved_block_size(block_size)
        with _record("all_gather", axis, x, algorithm=alg, codec=cd), \
                _observe_route("all_gather", x, axis, alg, cd, bs):
            return collectives.all_gather(x, axis, algorithm=alg, codec=cd,
                                          concat_axis=concat_axis, block_size=bs)
    with _record("all_gather", axis, x):
        return jax.lax.all_gather(x, axis, axis=concat_axis, tiled=tiled)


def reduce_scatter(x, axis, *, scatter_axis: int = 0, tiled: bool = True,
                   algorithm: Optional[str] = None, codec: Optional[str] = None,
                   block_size: Optional[int] = None):
    """psum_scatter (reference ``reduce_scatter_tensor``)."""
    if not tiled:
        # untiled scatters have no algorithmic form (see all_gather above)
        if algorithm is not None or codec is not None:
            raise ValueError("algorithmic reduce_scatter supports tiled=True only")
        alg = cd = None
    else:
        alg, cd = _algorithmic("reduce_scatter", x, axis, algorithm, codec)
    if alg is not None:
        from deepspeed_tpu import collectives

        bs = _resolved_block_size(block_size)
        with _record("reduce_scatter", axis, x, algorithm=alg, codec=cd), \
                _observe_route("reduce_scatter", x, axis, alg, cd, bs):
            return collectives.reduce_scatter(x, axis, algorithm=alg, codec=cd,
                                              scatter_axis=scatter_axis,
                                              block_size=bs)
    with _record("reduce_scatter", axis, x):
        return jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=tiled)


def all_to_all(x, axis, *, split_axis: int, concat_axis: int, tiled: bool = True,
               algorithm: Optional[str] = None, codec: Optional[str] = None,
               block_size: Optional[int] = None):
    """all_to_all (reference ``all_to_all_single``; backbone of Ulysses + MoE).

    ``algorithm=``/``codec=`` route through the algorithmic collectives
    library like every other facade op: ``None`` defers to the process
    facade defaults the ``collectives`` config block installed (falling
    back to the byte-identical ``jax.lax`` lowering when none are set —
    callers moving already-encoded bytes must pin ``algorithm="lax"``,
    see ``quant_collectives.exchange_wire``), "auto" consults the
    selector, a concrete name
    ("ring" / "bidir" / "ring2d", or "pallas_ring"/"pallas_ring2d" for
    remote-DMA hops with the in-kernel fused int8/fp8 dispatch wire) forces
    it. The MoE token dispatch/combine (``parallel/moe.py``) and the
    expert-parallel inference path ride this entry point."""
    if not tiled:
        # untiled all_to_all has no algorithmic form (the block-exchange
        # schedules are tiled by construction); explicit requests get a
        # clear error, default routing skips the selector entirely
        if algorithm is not None or codec is not None:
            raise ValueError("algorithmic all_to_all supports tiled=True only")
        alg = cd = None
    else:
        alg, cd = _algorithmic("all_to_all", x, axis, algorithm, codec)
    if alg is not None:
        from deepspeed_tpu import collectives

        bs = _resolved_block_size(block_size)
        with _record("all_to_all", axis, x, algorithm=alg, codec=cd), \
                _observe_route("all_to_all", x, axis, alg, cd, bs):
            return collectives.all_to_all(x, axis, split_axis=split_axis,
                                          concat_axis=concat_axis,
                                          algorithm=alg, codec=cd,
                                          block_size=bs)
    with _record("all_to_all", axis, x):
        return jax.lax.all_to_all(x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled)


def ppermute(x, axis, perm):
    """collective_permute (reference p2p ``send``/``recv``, ``pipe/p2p.py``)."""
    with _record("ppermute", axis, x):
        return jax.lax.ppermute(x, axis, perm)


def broadcast(x, axis, root: int = 0):
    """Broadcast root's shard to all ranks of the axis.

    In-program equivalent of reference ``broadcast`` (``comm/comm.py``): select
    the root slice post-all_gather; XLA lowers this to a broadcast.
    """
    with _record("broadcast", axis, x):
        gathered = jax.lax.all_gather(x, axis, axis=0)
        return gathered[root]


# --------------------------------------------------------------------------
# host control-plane
# --------------------------------------------------------------------------

_initialized = False


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    timeout_s: int = 300,
) -> bool:
    """Multi-host rendezvous via ``jax.distributed`` (reference
    ``init_distributed`` ``comm/comm.py:636``).

    Env-driven like the reference's MASTER_ADDR/RANK/WORLD_SIZE discovery:
    honors ``COORDINATOR_ADDRESS``/``NUM_PROCESSES``/``PROCESS_ID`` or the
    jax-native auto-detection on TPU pods (more than one host in
    ``TPU_WORKER_HOSTNAMES``; a single host is a no-op). Idempotent; returns True when a
    multi-process runtime is active.
    """
    global _initialized
    if _initialized:
        return jax.process_count() > 1
    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    num_processes = num_processes or _int_env("NUM_PROCESSES")
    process_id = process_id if process_id is not None else _int_env("PROCESS_ID")
    try:
        if coordinator_address or num_processes:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                initialization_timeout=timeout_s,
            )
        elif jax.default_backend() == "tpu" and _tpu_worker_count() > 1:
            jax.distributed.initialize()  # auto-detect on TPU pods
    except RuntimeError as e:
        if "already initialized" in str(e).lower():
            logger.debug(f"init_distributed: runtime already initialized: {e}")
        else:
            # A requested multi-host rendezvous that fails must fail loudly
            # (reference deepspeed.init_distributed raises on bad rendezvous);
            # silently continuing would train on 1/N of the pod.
            raise
    _initialized = True
    return jax.process_count() > 1


def _tpu_worker_count() -> int:
    """Hosts named in ``TPU_WORKER_HOSTNAMES``. A one-host machine still
    sets the variable (the chip tool's v5e host sets ``localhost``): one
    host is NOT a pod, there is nobody to rendezvous with, and an argument-
    less ``jax.distributed.initialize()`` there may look for a metadata
    server and hang or raise."""
    return len([h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
                if h.strip()])


def _int_env(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v else None


def get_world_size() -> int:
    """Host-process world size (reference ``get_world_size``)."""
    return jax.process_count()


def get_rank() -> int:
    """Host-process rank (reference ``get_rank``)."""
    return jax.process_index()


def barrier(name: str = "barrier", timeout_s: float = 120.0):
    """Cross-host barrier (reference ``barrier`` ``comm/comm.py``).

    Uses a tiny device psum when multiple processes exist; no-op otherwise.
    """
    if jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)
