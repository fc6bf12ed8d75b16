"""The gated delta rule's one-token update ALONE, on a row of a state pool at
the shapes `qwen3-next-80b-a3b.serve.long-output-wave128` runs it in program
`chain`: the pool [9, 128, 32, 128, 128] float32 (2.42 GB), 128 live rows, 16
key heads over 32 value heads of 128 x 128, in both forms of
`ops/gdn.py::gdn_pool_step`: XLA's (`impl="xla"`) and the kernel
(`ops/pallas/gdn_update.py`).

    chiprun -- python tools/gdn_update_bench.py

Sixty calls a form under one jit with the pool donated and carried (each call
updates the next layer's row, so nothing is hoisted), timed on the host's clock
around `block_until_ready`; one JSON line a form with the time a call and the
GB/s on its own bytes (a live row's state read once and written once: PERF.md,
PR 42's rule: where XLA's form is under 60% of the chip's 819 GB/s, the kernel
ships). A time comes only from a chip: without one this exits 1.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LAYERS, ROWS, HK, HV, DK, DV = 9, 128, 16, 32, 128, 128
CALLS, REPEATS = 60, 5


def measure(impl: str) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import peaks
    from deepspeed_tpu.ops import gdn

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    unit = lambda x: x / jnp.linalg.norm(x.astype(jnp.float32), axis=-1, keepdims=True).astype(x.dtype)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (ROWS, HK, DK), jnp.bfloat16))
    k = unit(jax.random.normal(keys[1], (ROWS, HK, DK), jnp.bfloat16))
    v = jax.random.normal(keys[2], (ROWS, HV, DV), jnp.bfloat16)
    g = -jax.random.uniform(keys[3], (ROWS, HV)) * 0.1
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (ROWS, HV)))
    live = jnp.ones((ROWS,), bool)

    @jax.jit
    def make():
        return 0.01 * jax.random.normal(keys[5], (LAYERS, ROWS, HV, DK, DV), jnp.float32)

    @jax.jit
    def run(pool):
        def call(carry, i):
            pool, acc = carry
            o, pool = gdn.gdn_pool_step(pool, i % LAYERS, q, k, (v + acc).astype(v.dtype), g, beta,
                                        live=live, fresh=~live, impl=impl)
            return (pool, 1e-3 * o), None  # the next call's values rest on this one's output

        (pool, acc), _ = jax.lax.scan(call, (pool, jnp.zeros_like(v)), jnp.arange(CALLS, dtype=jnp.int32))
        return pool, acc

    run = jax.jit(run, donate_argnums=0)
    pool = make()
    pool, acc = run(pool)  # compiles
    jax.block_until_ready(acc)
    times = []
    for _ in range(REPEATS):
        start = time.perf_counter()
        pool, acc = run(pool)
        jax.block_until_ready(acc)
        times.append((time.perf_counter() - start) / CALLS)
    own = 2 * ROWS * HV * DK * DV * 4
    best = min(times)
    kind = jax.devices()[0].device_kind
    return {"impl": impl, "ms_a_call": 1e3 * best, "ms_a_call_all": [1e3 * t for t in times],
            "own_bytes": own, "gb_per_s": 1e-9 * own / best,
            "share_of_bandwidth_pct": 100 * own / best / peaks.device_peaks(kind).hbm_bytes_per_s, "device": kind}


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print(f"tools/gdn_update_bench.py: no TPU (platform {jax.devices()[0].platform!r})", file=sys.stderr)
        return 1
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gdn_update_bench.jsonl", "a") as out:
        for impl in ("xla", "pallas"):
            line = json.dumps(measure(impl))
            print(line, flush=True)
            out.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
