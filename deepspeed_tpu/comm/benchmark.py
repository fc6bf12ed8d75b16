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
import numpy as np
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
    ``jax.block_until_ready`` — the ONE timing idiom for bench and sweep
    rows."""
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


_SWEEP_OPS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all")


def candidate_pairs(world: int, codecs, algorithms=None, op: Optional[str] = None,
                    axis: Optional[str] = None):
    """(algorithm, codec) measurement candidates for one axis size — THE
    enumeration shared by ``run_sweep`` and the observatory's probe queue,
    so online rows stay comparable with sweep rows: lax + the ppermute
    schedule families (+ the pallas algorithms when the backend is
    available), ``rhd`` only on power-of-two worlds (and never for
    ``all_to_all``, which has no recursive-halving form), the native
    lowering never paired with a wire codec. With ``axis`` (and an ``op``
    the schedule compiler covers), the compiler's top synthesized
    ``compiled:<sig>`` programs join the queue — measured mode then learns
    real latencies for searched schedules, not just the hand-written
    families; their codec column is the signature's lossiest level. An
    EXPLICIT ``algorithms`` list is honored verbatim (no compiled rows):
    a pinned sweep measures exactly what was asked."""
    from deepspeed_tpu.collectives import pallas_backend
    from deepspeed_tpu.collectives.algorithms import ALGORITHMS
    from deepspeed_tpu.collectives.pallas_backend import PALLAS_ALGORITHMS

    auto = algorithms is None
    if auto:
        algorithms = ["lax"] + list(ALGORITHMS)
        if pallas_backend.available():
            algorithms += list(PALLAS_ALGORITHMS)
    pow2 = world > 0 and not (world & (world - 1))
    out = []
    for alg in algorithms:
        if alg == "rhd" and (not pow2 or op == "all_to_all"):
            continue
        for cd in codecs:
            if alg == "lax" and cd != "none":
                continue  # the lax lowering has no wire codec
            if (alg, cd) not in out:
                out.append((alg, cd))
    if auto and axis is not None:
        from deepspeed_tpu.collectives import schedule as _schedule

        if op in _schedule.SCHEDULED_OPS:
            for sig in _schedule.candidate_signatures(op, axis, world,
                                                      codecs=tuple(codecs)):
                pair = (f"compiled:{sig}", _schedule.signature_codec(sig))
                if pair not in out:
                    out.append(pair)
    return out


def probe_elems(n: int, elems: int) -> int:
    """Round a global element count to the sweep's payload base (a multiple
    of ``n*n*128``): the per-device shard must itself divide by ``n`` for
    reduce_scatter and stay lane-aligned. Shared by ``run_sweep`` and the
    observatory's probe payloads so both measure the same shapes."""
    base = n * n * 128
    return (elems // base) * base or base


def _algorithmic_fn(op: str, axis: str, algorithm: str, codec: str, block_size: int):
    """Per-device body routing through the comm facade's algorithmic path
    (so the sweep measures exactly what ``selector`` will later dispatch)."""
    from deepspeed_tpu.comm import comm as dist

    if op == "all_reduce":
        return lambda x: dist.all_reduce(x, axis, algorithm=algorithm, codec=codec,
                                         block_size=block_size)
    if op == "all_gather":
        return lambda x: dist.all_gather(x, axis, algorithm=algorithm, codec=codec,
                                         block_size=block_size)
    if op == "reduce_scatter":
        return lambda x: dist.reduce_scatter(x, axis, algorithm=algorithm, codec=codec,
                                             block_size=block_size)
    if op == "all_to_all":
        return lambda x: dist.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                         algorithm=algorithm, codec=codec,
                                         block_size=block_size)
    raise ValueError(f"sweep op {op!r} not algorithmic (one of {_SWEEP_OPS})")


def run_sweep(
    ops=_SWEEP_OPS,
    sizes_mb: Optional[List[float]] = None,
    axis: str = "dp",
    mesh: Optional[Mesh] = None,
    algorithms: Optional[List[str]] = None,
    codecs: Optional[List[str]] = None,
    iters: int = 5,
    warmup: int = 2,
    block_size: int = 2048,
    dtype=jnp.bfloat16,
) -> List[Dict]:
    """Measure every (op, size, algorithm, codec) combination and return the
    decision-table rows ``selector.configure(decision_table=...)`` consumes
    (one JSON row per measurement: op/world/size_mb/algorithm/codec/backend/
    latency_ms/busbw_gbps; ``size_mb`` is the PER-DEVICE payload, matching
    the local-shard bytes the selector is queried with; ``backend`` is the
    hop backend the row was measured with — measured mode never applies a
    ppermute row to a pallas algorithm or vice versa). The lax baseline
    rides along as ``algorithm="lax"`` so measured mode can conclude
    "don't bother"."""
    from deepspeed_tpu.collectives import pallas_backend
    from deepspeed_tpu.collectives.algorithms import ALGORITHMS
    from deepspeed_tpu.collectives.pallas_backend import PALLAS_ALGORITHMS
    from deepspeed_tpu.utils.logging import logger

    sizes_mb = sizes_mb if sizes_mb is not None else [0.25, 1.0, 4.0]
    if algorithms is None:
        # the pallas remote-DMA algorithms sweep themselves in on TPU only
        algorithms = ["lax"] + list(ALGORITHMS)
        if pallas_backend.available():
            algorithms += list(PALLAS_ALGORITHMS)
    pallas_req = [a for a in algorithms if pallas_backend.is_pallas(a)]
    if pallas_req and not pallas_backend.available():
        # an off-TPU sweep must not crash (CI boxes) — and must not emit
        # interpret-mode timings either: the interpreter's latencies say
        # nothing about remote-DMA hops, and a table holding them would
        # poison measured-mode routing on a real TPU
        logger.warning(
            f"collectives sweep: skipping {pallas_req} — the pallas "
            f"remote-DMA backend needs a TPU (backend is "
            f"{jax.default_backend()!r}; interpret-mode timings would "
            "poison the decision table)")
        algorithms = [a for a in algorithms if not pallas_backend.is_pallas(a)]
    codecs = codecs if codecs is not None else ["none"]
    mesh = mesh if mesh is not None else build_mesh(axis_sizes={axis: -1})
    n = mesh.shape[axis]
    itemsize = jnp.dtype(dtype).itemsize
    rows: List[Dict] = []
    for op in ops:
        for size_mb in sizes_mb:
            elems = probe_elems(n, max(int(size_mb * 1e6 / itemsize), n))
            x = jax.device_put(jnp.ones((elems,), dtype), NamedSharding(mesh, P(axis)))
            for alg, codec in candidate_pairs(n, codecs, algorithms, op=op,
                                              axis=axis):
                fn = (_collective_fn(op, axis) if alg == "lax"
                      else _algorithmic_fn(op, axis, alg, codec, block_size))
                out_spec = P() if op == "all_reduce" else P(axis)
                f = jax.jit(shard_map(fn, mesh=mesh, in_specs=P(axis),
                                      out_specs=out_spec, check_vma=False))
                dt = _time_collective(f, x, iters, warmup)
                payload = elems * itemsize
                busbw = payload / dt * _busbw_factor(op, n)
                # size_mb is the PER-DEVICE payload: selector.select is
                # queried at trace time with the local shard's bytes
                # (inside shard_map), so table rows must bucket the same
                # quantity or measured mode matches a world-x-off regime
                rows.append({
                    "op": op, "world": n, "size_mb": round(payload / n / 1e6, 4),
                    "algorithm": alg, "codec": codec,
                    # the hop backend these timings were measured with:
                    # selector measured mode only applies a row to
                    # algorithms of the same backend (a ppermute table
                    # must never route pallas hop counts, nor vice versa)
                    "backend": pallas_backend.hop_backend(alg),
                    "latency_ms": round(dt * 1e3, 4),
                    "busbw_gbps": round(busbw / 1e9, 3),
                    # payload element width: the observatory's alpha/beta
                    # refit reconstructs wire bytes from it (table.py v1)
                    "itemsize": itemsize,
                    "samples": 1,
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
    p.add_argument("--sweep", action="store_true",
                   help="sweep algorithms x codecs and emit a selector decision table")
    p.add_argument("--codecs", default="none",
                   help="comma-separated wire codecs for --sweep (none,bf16,int8,fp8)")
    p.add_argument("--algorithms", default=None,
                   help="comma-separated algorithms for --sweep (default: lax + "
                        "the ppermute set, + pallas_ring/pallas_ring2d on TPU; "
                        "pallas algorithms are skipped with a logged reason "
                        "off-TPU rather than measured under the interpreter)")
    p.add_argument("--output", default=None,
                   help="write the --sweep decision table JSON here (default "
                        "stdout; versioned schema envelope — see "
                        "collectives/table.py)")
    p.add_argument("--merge", default=None, metavar="TABLE",
                   help="fold the sweep into an EXISTING decision table "
                        "(e.g. the observatory's online coll_table.json): "
                        "matching rows are replaced by the fresh sweep, rows "
                        "the sweep did not cover are kept; written to "
                        "--output (default: back onto TABLE)")
    a = p.parse_args(argv)
    sizes = [float(s) for s in a.sizes_mb.split(",")]
    if a.sweep:
        from deepspeed_tpu.collectives import table as table_mod
        from deepspeed_tpu.utils.logging import logger

        ops = _SWEEP_OPS if a.op == "all" else (a.op,)
        bad = [op for op in ops if op not in _SWEEP_OPS]
        if bad:
            p.error(f"--sweep supports {_SWEEP_OPS}, not {bad}")
        rows = run_sweep(ops=ops, sizes_mb=sizes, axis=a.axis, iters=a.iters,
                         algorithms=([s for s in a.algorithms.split(",") if s]
                                     if a.algorithms else None),
                         codecs=[c for c in a.codecs.split(",") if c])
        source = "sweep"
        out_path = a.output
        if a.merge:
            out_path = out_path or a.merge
            try:
                base = table_mod.load_table(a.merge, strict=True)
            except FileNotFoundError:
                base = []  # first merge into a table nobody persisted yet
            except (OSError, ValueError) as e:
                # unreadable or version-mismatched base: the (possibly
                # long, on-TPU) sweep that just ran must not be thrown
                # away — but neither may rows we cannot parse be DESTROYED
                # by overwriting the base file with sweep-only content
                base = []
                if out_path == a.merge:
                    out_path = a.merge + ".sweep.json"
                logger.warning(
                    f"--merge: base table {a.merge!r} unreadable or "
                    f"version-mismatched ({e}); leaving it untouched and "
                    f"writing the fresh sweep to {out_path}")
            rows = table_mod.merge_rows(base, rows)
            source = "merged"
        if out_path:
            table_mod.write_table(out_path, rows, source=source)
            print(f"wrote {len(rows)} decision rows to {out_path} "
                  f"(schema {table_mod.SCHEMA_VERSION}, source {source})")
        else:
            print(json.dumps({"schema": table_mod.SCHEMA_VERSION,
                              "source": source, "rows": rows}, indent=1))
        return 0
    ops = OPS if a.op == "all" else (a.op,)
    for op in ops:
        for row in run_collective_bench(op, sizes, axis=a.axis, iters=a.iters):
            print(json.dumps(row))
    return 0


if __name__ == "__main__":  # pragma: no cover - bin/ds_bench is the usual entry
    import sys

    sys.exit(main())
