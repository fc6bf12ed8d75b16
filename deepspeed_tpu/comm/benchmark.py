"""Collective micro-benchmark (the ``ds_bench`` analog).

Reference: ``bin/ds_bench`` -> DeepSpeedExamples' communication benchmarks
(allreduce/allgather/alltoall latency + busbw sweeps). Here each collective
runs inside a jitted ``shard_map`` over the requested mesh axis; algorithmic
bus bandwidth uses the standard ring-collective factors (the same formulas as
``utils/comms_logging.calc_bw_log``).

Timing ends in ``jax.block_until_ready``.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.comm import CommsLogger
from deepspeed_tpu.utils.compat import shard_map
from deepspeed_tpu.topology.mesh import build_mesh

OPS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all")


def _collective_fn(op: str, axis: str):
    if op == "all_reduce":
        return lambda x: jax.lax.psum(x, axis)
    if op == "all_gather":
        return lambda x: jax.lax.all_gather(x, axis)
    if op == "reduce_scatter":
        return lambda x: jax.lax.psum_scatter(x, axis, tiled=True)
    if op == "all_to_all":
        return lambda x: jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)
    raise ValueError(f"unknown op {op!r} (one of {OPS})")


# Algorithmic bus-bandwidth factors are shared with the in-band comm telemetry.
_busbw_factor = CommsLogger._bus_factor


def _time_collective(f, x, iters: int, warmup: int) -> float:
    """Compile + warm up, then mean seconds/call, timed to
    ``jax.block_until_ready``."""
    r = f(x)  # compile + first run (counts as warmup)
    for _ in range(max(warmup - 1, 0)):
        r = f(x)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = f(x)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters


def run_collective_bench(
    op: str,
    sizes_mb: List[float],
    axis: str = "dp",
    mesh: Optional[Mesh] = None,
    iters: int = 10,
    warmup: int = 3,
    dtype=jnp.bfloat16,
) -> List[Dict]:
    """Sweep payload sizes for one collective; returns rows of
    {size_mb, latency_ms, algbw_gbps, busbw_gbps}."""
    mesh = mesh if mesh is not None else build_mesh(axis_sizes={axis: -1})
    n = mesh.shape[axis]
    fn = _collective_fn(op, axis)
    itemsize = jnp.dtype(dtype).itemsize

    rows = []
    for size_mb in sizes_mb:
        elems = max(int(size_mb * 1e6 / itemsize), n)
        elems = (elems // (n * 128)) * (n * 128) or n * 128  # divisible, lane-aligned
        x = jax.device_put(
            jnp.ones((elems,), dtype), NamedSharding(mesh, P(axis))
        )
        f = jax.jit(
            shard_map(fn, mesh=mesh, in_specs=P(axis),
                          out_specs=P() if op == "all_reduce" else P(axis),
                          check_vma=False)
        )
        dt = _time_collective(f, x, iters, warmup)

        payload = elems * itemsize  # global payload bytes
        algbw = payload / dt
        busbw = algbw * _busbw_factor(op, n)
        rows.append({
            "op": op, "world": n, "size_mb": round(payload / 1e6, 3),
            "latency_ms": round(dt * 1e3, 4),
            "algbw_gbps": round(algbw / 1e9, 3),
            "busbw_gbps": round(busbw / 1e9, 3),
        })
    return rows


def main(argv=None) -> int:  # pragma: no cover - CLI body exercised via run_collective_bench
    import argparse
    import json

    p = argparse.ArgumentParser(description="Collective micro-benchmark (ds_bench analog)")
    p.add_argument("--op", default="all_reduce", choices=OPS + ("all",))
    p.add_argument("--axis", default="dp")
    p.add_argument("--sizes-mb", default="1,8,64,256")
    p.add_argument("--iters", type=int, default=10)
    a = p.parse_args(argv)
    sizes = [float(s) for s in a.sizes_mb.split(",")]
    ops = OPS if a.op == "all" else (a.op,)
    for op in ops:
        for row in run_collective_bench(op, sizes, axis=a.axis, iters=a.iters):
            print(json.dumps(row))
    return 0


if __name__ == "__main__":  # pragma: no cover - bin/ds_bench is the usual entry
    import sys

    sys.exit(main())
