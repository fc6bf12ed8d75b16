"""A routed layer's decode product ALONE, at the shapes the three routed cells
run it in program `chain`, with `n` of the 64 experts touched: in XLA's dense
form (`inference/model.py::_all_experts` on a layer's slice of the stacked
weights, every expert read whatever the rows picked) and as the kernel that
reads the touched ones (`ops/pallas/moe_decode.py`).

    chiprun -- python tools/moe_decode_bench.py [--cell qwen glm xing] [--th 256 512 ...]

| cell | rows | experts | M | H | touched (median of the cell's steps) |
|---|---|---|---|---|---|
| `qwen3-next-80b-a3b.serve.long-output-wave128` | 128 | 64 held | 2048 | 512 | 33 |
| `glm-4.7-flash.serve.batch` | 64 | 64 | 2048 | 1536 | 56 |
| `xing4.0-29b-a4b.serve.long-prompt-batch` | 64 | 64 | 3584 | 1024 | 56 |

Forty-eight calls a form under one jit, each on the next layer's row of a
three-layer stack and on rows that rest on the call before (nothing hoisted,
nothing overlapped), timed on the host's clock around `block_until_ready`; one
JSON line a form, a cell and an `n` with the time a call and the GB/s on the
TOUCHED experts' bytes (`n x 3 x M x H x 2`), which is what a step has to
read; the dense form's line also gives the GB/s on the bytes it moves (all 64).
`--th` times the kernel at those tiles of the hidden width in place of the one
it picks. A time comes only from a chip: without one this exits 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (rows, experts, M, H, the cell's median touched)
CELLS = {"qwen": (128, 64, 2048, 512, 33), "glm": (64, 64, 2048, 1536, 56), "xing": (64, 64, 3584, 1024, 56)}
LAYERS, PICKS, CALLS, REPEATS = 3, 4, 48, 5


def measure(cell: str, weights, n: int, impl: str, th=None) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import peaks
    from deepspeed_tpu.inference import model  # noqa: F401  (registers the dense form)
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import moe_decode  # (registers the kernel)

    T, E, M, H, _ = CELLS[cell]
    form = registry.dispatch("moe_decode", impl)
    kw = {"th": th} if th else {}
    # n touched experts spread over the 64, each row's picks distinct
    picked = (jnp.arange(T)[:, None] * PICKS + jnp.arange(PICKS)[None, :]) % n * E // n
    gate = jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], picked].set(1.0 / PICKS)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, M), jnp.bfloat16)

    @jax.jit
    def run(weights, x):
        def call(x, i):
            out = form(x, gate, *weights, i % LAYERS, "silu_glu", **kw)
            return (x + out).astype(x.dtype), None  # the next call's rows rest on this one's output

        return jax.lax.scan(call, x, jnp.arange(CALLS, dtype=jnp.int32))[0]

    jax.block_until_ready(run(weights, x))  # compiles
    times = []
    for _ in range(REPEATS):
        start = time.perf_counter()
        jax.block_until_ready(run(weights, x))
        times.append((time.perf_counter() - start) / CALLS)
    best, expert = min(times), 3 * M * H * 2
    kind = jax.devices()[0].device_kind
    line = {"cell": cell, "rows": T, "touched": n, "impl": impl,
            "th": th or (moe_decode._tile(M, H, 2) if impl == "pallas" else None),
            "ms_a_call": 1e3 * best, "ms_a_call_all": [1e3 * t for t in times], "touched_bytes": n * expert,
            "gb_per_s": 1e-9 * n * expert / best,
            "share_of_bandwidth_pct": 100 * n * expert / best / peaks.device_peaks(kind).hbm_bytes_per_s, "device": kind}
    if impl == "xla":
        line["moved_gb_per_s"] = 1e-9 * E * expert / best
    return line


def main() -> int:
    import jax
    import jax.numpy as jnp

    parser = argparse.ArgumentParser()
    parser.add_argument("--cell", nargs="+", default=list(CELLS), choices=list(CELLS))
    parser.add_argument("--th", nargs="+", type=int, default=[None])
    args = parser.parse_args()
    if jax.devices()[0].platform != "tpu":
        print(f"tools/moe_decode_bench.py: no TPU (platform {jax.devices()[0].platform!r})", file=sys.stderr)
        return 1
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_decode_bench.jsonl", "a") as out:
        for cell in args.cell:
            T, E, M, H, median = CELLS[cell]
            make = jax.jit(lambda key, shape: 0.02 * jax.random.normal(key, shape, jnp.bfloat16), static_argnums=1)
            keys = jax.random.split(jax.random.PRNGKey(0), 3)
            weights = (make(keys[0], (LAYERS, E, M, H)), make(keys[1], (LAYERS, E, M, H)),
                       make(keys[2], (LAYERS, E, H, M)))
            for n in (8, median, E):
                for impl, th in [("xla", None)] + [("pallas", th) for th in args.th if not th or H % th == 0]:
                    line = json.dumps(measure(cell, weights, n, impl, th))
                    print(line, flush=True)
                    out.write(line + "\n")
            del weights
    return 0


if __name__ == "__main__":
    sys.exit(main())
