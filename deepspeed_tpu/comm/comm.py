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


def _nbytes(x) -> int:
    try:
        return int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
    except Exception:
        return 0


def _record(op_name: str, axis, x):
    """Record one collective into the comms logger AND the telemetry
    subsystem; returns a span context wrapping the ``jax.lax`` call.

    Collectives here are in-program ops, so both records happen at TRACE
    time: the span duration is host tracing time (one per compiled program,
    not per execution), while the (op, axis, dtype, bytes, world) tags are
    the exact per-execution collective workload of the traced step.
    """
    axis_str = "+".join(axis) if isinstance(axis, (tuple, list)) else str(axis)
    nbytes, world = _nbytes(x), _axis_size(axis)
    comms_logger.record(op_name, axis_str, nbytes, world)
    tracer = telemetry.get_tracer()
    if not tracer.enabled:
        return telemetry.NOOP_SPAN
    tracer.count("comm/count")
    tracer.count("comm/bytes", nbytes)
    tracer.count(f"comm/bytes/{op_name}", nbytes)
    dtype = str(getattr(x, "dtype", "unknown"))
    return tracer.span(f"comm:{op_name}", cat="comm", op=op_name, axis=axis_str,
                       bytes=nbytes, dtype=dtype, world=world)


# --------------------------------------------------------------------------
# collectives (usable inside shard_map / jit with bound axis names)
# --------------------------------------------------------------------------
#
# Each op is a name, the trace-time record above and the ``jax.lax`` call: XLA
# picks the implementation (docs/parallelism.md, "Which collectives run").


def all_reduce(x, axis, op: str = "sum"):
    """psum/pmax/pmin over a named axis (reference ``all_reduce`` ``comm/comm.py``)."""
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


def all_gather(x, axis, *, concat_axis: int = 0, tiled: bool = True):
    """all_gather over a named axis (reference ``all_gather_into_tensor``)."""
    with _record("all_gather", axis, x):
        return jax.lax.all_gather(x, axis, axis=concat_axis, tiled=tiled)


def reduce_scatter(x, axis, *, scatter_axis: int = 0, tiled: bool = True):
    """psum_scatter (reference ``reduce_scatter_tensor``)."""
    with _record("reduce_scatter", axis, x):
        return jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=tiled)


def all_to_all(x, axis, *, split_axis: int, concat_axis: int, tiled: bool = True):
    """all_to_all (reference ``all_to_all_single``; backbone of Ulysses + MoE:
    the MoE token dispatch/combine of ``parallel/moe.py`` and the
    expert-parallel inference path ride this entry point)."""
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
