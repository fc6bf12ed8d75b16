"""The paged decode kernel ALONE, at the shapes `pythia-1.4b.serve.batch` runs
it in program `chain`: q [64, 1, 16, 128] bf16, the whole pool [24 * 1621, 16,
2048] bf16, a block table 2048 / 16 = 128 pages wide, contexts drawn as the
cell draws them (prompt uniform 64-256 plus 0-191 decoded tokens).

    chiprun -- python tools/paged_kernel_bench.py --table-cols 32,128,1024

Several hundred calls under one jit (each call's query depends on the call
before, so nothing is hoisted), timed on the host's clock around
`block_until_ready`; prints one JSON line a table width with the time a call
and its share of the roofline by the benchmark's own count and peaks
(`benchmarks/lib/costs.py::paged_decode_cost`: live tokens only). A time
comes only from a chip: without one this exits 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N, H, KVH, HD, BS = 64, 16, 16, 128, 16
LAYERS, PAGES = 24, 1621  # the cell's 4.75 GiB pool: 38,904 pages in all


def draw(seed: int, cols: int, layer: int = 7):
    """Contexts and a block table as the engine hands them over: a row's live
    pages are distinct pages of `layer`, its dead entries the layer's page 0."""
    rng = np.random.default_rng(seed)
    ctx = rng.integers(64, 257, N) + rng.integers(0, 192, N)
    table = np.zeros((N, cols), np.int32)
    free = rng.permutation(np.arange(1, PAGES))
    for n, c in enumerate(ctx):
        live = -(-int(c) // BS)
        table[n, :live], free = free[:live], free[live:]
    return ctx.astype(np.int32), table + layer * PAGES


def measure(kernel, cols: int, seed: int = 0, calls: int = 300, repeats: int = 5) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import costs, peaks

    ctx, table = draw(seed, cols)
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (N, 1, H, HD), jnp.bfloat16)
    page = jax.random.normal(jax.random.fold_in(key, 1), (PAGES, BS, KVH * HD), jnp.bfloat16)
    pool_k = jnp.tile(page, (LAYERS, 1, 1))
    pool_v = jnp.tile(page[::-1], (LAYERS, 1, 1))
    pos = jnp.asarray(ctx - 1)[:, None]
    lens = jnp.ones((N,), jnp.int32)
    table = jnp.asarray(table)

    @jax.jit
    def many(q, pool_k, pool_v, table, pos, lens):
        def one(_, q):
            out = kernel(q, pool_k, pool_v, table, pos, BS, new_lens=lens)
            return (q + out * jnp.asarray(1e-3, q.dtype)).astype(q.dtype)

        return jax.lax.fori_loop(0, calls, one, q)

    args = (q, pool_k, pool_v, table, pos, lens)
    out = jax.block_until_ready(many(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(many(*args))
        times.append((time.perf_counter() - t0) / calls)
    least, _ = costs.roofline_seconds(*costs.paged_decode_cost(int(ctx.sum()), N, H, KVH, HD),
                                      peaks.device_peaks(jax.devices()[0].device_kind))
    ms = 1e3 * float(np.median(times))
    return {"table_cols": cols, "seed": seed, "calls": calls, "ms_per_call": ms,
            "ms_per_call_min": 1e3 * min(times), "least_ms": 1e3 * least,
            "roofline_pct": 100.0 * least / (ms / 1e3), "mean_context": float(ctx.mean()),
            "finite": bool(jnp.isfinite(out.astype(jnp.float32)).all())}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--table-cols", default="32,128")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=300)
    a = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("no chip: a kernel's time comes only from a chip run", file=sys.stderr)
        return 1
    from deepspeed_tpu.ops.pallas.paged_attention import flash_decode_paged

    for cols in (int(c) for c in a.table_cols.split(",")):
        print(json.dumps(measure(flash_decode_paged, cols, a.seed, a.calls)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
