"""A state mixer's decode convolution ALONE, on a row of a conv pool at the
shapes the two state cells run it in program `chain`, in both forms of
`ops/ssm.py::conv_pool_step`: XLA's (`impl="xla"`: a prompt's lines at one
token, around a slice of the layer's row and its write back) and the kernel
(`ops/pallas/conv_update.py`).

- `granite-4.0-h-micro.serve.long-output-batch`: the pool [36, 64, 3 x 4352]
  bfloat16, 64 rows, the inputs columns 4096..8448 of `ssm_in_proj`'s
  bfloat16 [64, 8512], a bias;
- `qwen3-next-80b-a3b.serve.long-output-wave128`: the pool [9, 128, 3 x 8192]
  bfloat16, 128 rows, the inputs the first 8192 columns of `gdn_in_proj`'s
  float32 [128, 12288], no bias.

    chiprun -- python tools/conv_update_bench.py [--sweep]

144 calls a form under one jit with the pool donated and carried, 36 calls
unrolled in each of four turns of a loop (each call updates the next layer's
row, and its output rides the carry, so nothing is hoisted or dropped; a turn
of a loop costs the chip about 17 us of its own, which a call of 6 us must not
be charged: one call a turn read 23 us for the kernel that a traced chain runs
in 6.5), timed on the host's clock around `block_until_ready`; one JSON line a
shape and form with the time a call and the GB/s on the bytes the mathematics
needs (a row's tail read once and written once, its inputs read and its
outputs written). The kernel is read twice: with every row live, and with a
dead row in every eight (the same path: its selects are not what it waits
for). Every operand is in HBM here; in a chain the inputs and outputs are the
compiler's to place, and it keeps them in the chip's fast memory, so a traced
chain reads the kernel faster than this does. `--sweep` reads the kernel again at other rows a grid step and lane tiles an inner step
than `conv_update._ROWS` / `_CHUNK_TILES`. A time comes only from a chip:
without one this exits 1.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: layers, rows, X, the projection's width, the inputs' first column, the inputs' dtype, a bias
SHAPES = {"granite": (36, 64, 4352, 8512, 4096, "bfloat16", True),
          "qwen3_next": (9, 128, 8192, 12288, 0, "float32", False)}
K = 4
UNROLLED, TURNS, REPEATS = 36, 4, 5


def measure(shape: str, impl: str, dead_rows: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import peaks
    from deepspeed_tpu.ops import ssm

    layers, rows, X, W, at, dtype, with_bias = SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (rows, W), jnp.float32).astype(dtype)
    taps = (0.5 * jax.random.normal(keys[1], (K, X), jnp.float32)).astype(jnp.bfloat16)
    bias = (0.1 * jax.random.normal(keys[2], (X,), jnp.float32)).astype(jnp.bfloat16) if with_bias else None
    live = jnp.arange(rows) % 8 != 3 if dead_rows else jnp.ones((rows,), bool)

    @jax.jit
    def make():
        return jax.random.normal(keys[3], (layers, rows, (K - 1) * X), jnp.float32).astype(jnp.bfloat16)

    def run(pool):
        def turn(carry, i):
            pool, y = carry
            for j in range(UNROLLED):
                y, pool = ssm.conv_pool_step(pool, (i + j) % layers, x, taps, bias, live=live,
                                             fresh=jnp.zeros_like(live), at=at, impl=impl)
            return (pool, y), None

        (pool, y), _ = jax.lax.scan(turn, (pool, jnp.zeros((rows, X), x.dtype)), jnp.arange(TURNS, dtype=jnp.int32))
        return pool, y

    run = jax.jit(run, donate_argnums=0)
    pool, y = run(make())  # compiles
    jax.block_until_ready(y)
    times = []
    for _ in range(REPEATS):
        start = time.perf_counter()
        pool, y = run(pool)
        jax.block_until_ready(y)
        times.append((time.perf_counter() - start) / (UNROLLED * TURNS))
    own = rows * (2 * (K - 1) * X * pool.dtype.itemsize + 2 * X * x.dtype.itemsize)
    best = min(times)
    kind = jax.devices()[0].device_kind
    return {"shape": shape, "impl": impl, "dead_rows": dead_rows, "ms_a_call": 1e3 * best, "ms_a_call_all": [1e3 * t for t in times],
            "own_bytes": own, "gb_per_s": 1e-9 * own / best,
            "share_of_bandwidth_pct": 100 * own / best / peaks.device_peaks(kind).hbm_bytes_per_s, "device": kind}


def main(argv=None) -> int:
    import argparse

    import jax

    from deepspeed_tpu.ops.pallas import conv_update

    parser = argparse.ArgumentParser()
    parser.add_argument("--sweep", action="store_true")
    sweep = parser.parse_args(argv).sweep

    if jax.devices()[0].platform != "tpu":
        print(f"tools/conv_update_bench.py: no TPU (platform {jax.devices()[0].platform!r})", file=sys.stderr)
        return 1
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/conv_update_bench.jsonl", "a") as out:
        for shape in SHAPES:
            for impl, dead_rows in (("xla", False), ("pallas", False), ("pallas", True)):
                line = json.dumps(measure(shape, impl, dead_rows))
                print(line, flush=True)
                out.write(line + "\n")
            shipped = conv_update._ROWS, conv_update._CHUNK_TILES
            for rows, tiles in [(r, t) for r in (8, 16, 32) for t in (2, 8)] if sweep else []:
                conv_update._ROWS, conv_update._CHUNK_TILES = rows, tiles
                try:
                    read = measure(shape, "pallas")
                except Exception as e:  # noqa: BLE001 - the chip's compiler refuses a block past its VMEM
                    read = {"shape": shape, "refused": f"{type(e).__name__}: {e}"[:300]}
                line = json.dumps({**read, "rows_a_step": rows, "chunk_tiles": tiles})
                print(line, flush=True)
                out.write(line + "\n")
            conv_update._ROWS, conv_update._CHUNK_TILES = shipped
    return 0


if __name__ == "__main__":
    sys.exit(main())
