"""The hyper-connections of a sublayer ALONE, at the shapes the xing cell runs them
(`xing4.0-29b-a4b.serve.long-prompt-batch`: `n` 4, `C` 3,584, bf16, 20 Sinkhorn rounds):

| shape | streams | tokens a call |
|---|---|---|
| `prefill` (`step`) | [4, 8, 2048, 3584] | 16,384 |
| `chain` (`chain`, a decode step) | [4, 64, 1, 3584] | 64 |

in two forms: `pallas`, the kernels `mhc_mix_read` and `mhc_write`
(`ops/pallas/mhc.py`), and `xla`, `ops/mhc.py`'s `mix`, `read` and `write` as
the chip's compiler makes them.

    chiprun -- python tools/mhc_kernel_bench.py [--shapes prefill chain] [--forms pallas xla] [--tokens 128 256 ...]

(`--tokens` adds calls of `[4, tokens, 1, 3584]`: where the kernels start to pay, `ops/pallas/mhc.py::_MIN_STREAM_BYTES`.)

A sublayer here is `(mixed, u) = mix_read(X)`, `X <- write_back(X, u, mixed)`:
the sublayer between them is left out (`y = u`), so a call moves what
`benchmarks/architectures/xing4_0.py::mhc_cost` counts and nothing else: the
streams read twice and written once, `y` read, `u` written, `(3 n C + 2 C) x 2`
B a token. Some tens of sublayers under one jit, each on the streams the one
before wrote (the carry of a loop, as a layer scan has them), run once under
the profiler: `ms_per_sublayer` is the loop's module's device time a turn,
`gb_per_s` `mhc_cost`'s bytes over it, and for the kernels each instruction's
own device time a call beside it (`mhc_mix_read_ms`, `mhc_write_ms`, what a
traced cell's `named_op=` lines read) with the GB/s on that call's own share of
the bytes. `host_ms_per_sublayer` is the host's clock around the same loop.
`err` is the largest difference of ONE sublayer's `u` and `X'` from the `xla`
form's on the same inputs, over the largest entry, at the cell's own draws of
the leaves; the loop's `H_post` logits are those less 4 (`H_post` about 0.04,
so that some hundreds of sublayers in a row, each fed its own read, stay
finite: a time does not depend on the values). A time comes only from a
chip: without one this exits 1.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_STREAMS, C, ITERS = 4, 3584, 20
SHAPES = {"prefill": (8, 2048), "chain": (64, 1)}
KERNELS = ("mhc_mix_read", "mhc_write")


def device_seconds(run) -> dict:
    """Device seconds of one traced `run`: the modules together, and each kernel's instructions."""
    import jax
    from jax.profiler import ProfileData

    out = {"modules": 0.0, **{k: 0.0 for k in KERNELS}, "calls": {k: 0 for k in KERNELS}}
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            run()
        for path in glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb")):
            for plane in ProfileData.from_file(path).planes:
                if not plane.name.startswith("/device:TPU:0"):
                    continue
                for line in plane.lines:
                    for e in line.events:
                        if line.name == "XLA Modules":
                            out["modules"] += e.duration_ns / 1e9
                        elif line.name == "XLA Ops":
                            for k in KERNELS:
                                if e.name.startswith("%" + k):
                                    out[k] += e.duration_ns / 1e9
                                    out["calls"][k] += 1
    return out


def measure(shape: str, form: str, seed: int, calls: int, repeats: int = 3) -> dict:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import mhc

    rows, chunk = SHAPES[shape]
    n, K = N_STREAMS, N_STREAMS * N_STREAMS + 2 * N_STREAMS
    key = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(key[0], (n, rows, chunk, C), jnp.bfloat16)
    # the cell's own draws (benchmarks/configs/xing4.0-29b-a4b.json, `assumed.weights`)
    phi = (jax.random.normal(key[1], (n * C, K)) / np.sqrt(n * C)).astype(jnp.bfloat16)
    b = jax.random.normal(key[2], (K,))
    b = (b + 0.1 * jnp.sign(b)).astype(jnp.bfloat16)
    alpha = (jnp.array([1.0, 1.0, 4.0]) * (1 + 0.1 * jax.random.normal(key[3], (3,)))).astype(jnp.bfloat16)
    sizes = dict(norm_eps=1e-6, iters=ITERS, eps=1e-6, clamp=(-30.0, 30.0))

    def sublayer(x, impl, b=b):
        mixed, u = mhc.mix_read(x, phi, b, alpha, impl=impl, **sizes)
        return mhc.write_back(x, u, mixed), u

    @jax.jit
    def many(x):
        quiet = b.at[n:2 * n].add(-4.0)
        return jax.lax.fori_loop(0, calls, lambda _, x: sublayer(x, form, quiet)[0], x)

    got, want = jax.jit(lambda x: sublayer(x, form))(x), jax.jit(lambda x: sublayer(x, "xla"))(x)
    err = max(float(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)).max() / jnp.abs(w.astype(jnp.float32)).max())
              for g, w in zip(got, want))
    out = jax.block_until_ready(many(x))
    finite = bool(jnp.isfinite(out.astype(jnp.float32)).all())
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(many(x))
        times.append((time.perf_counter() - t0) / calls)
    seen = device_seconds(lambda: jax.block_until_ready(many(x)))
    tokens = rows * chunk
    cost = (3 * n * C + 2 * C) * 2 * tokens  # `mhc_cost`'s bytes of one sublayer
    share = {"mhc_mix_read": (n * C + C) * 2 * tokens, "mhc_write": (2 * n * C + C) * 2 * tokens}
    s = seen["modules"] / calls  # (0 where the trace holds no device line: the host's clock is never written for it)
    line = {"shape": shape, "form": form, "tokens": tokens, "seed": seed, "calls": calls,
            "ms_per_sublayer": 1e3 * s if s else None, "gb_per_s": cost / s / 1e9 if s else None,
            "mb_per_sublayer": cost / 1e6,
            "host_ms_per_sublayer": 1e3 * float(np.median(times)), "finite": finite, "err": err}
    for k in KERNELS:
        if seen["calls"][k]:
            one = seen[k] / seen["calls"][k]
            line.update({k + "_ms": 1e3 * one, k + "_gb_per_s": share[k] / one / 1e9, k + "_calls": seen["calls"][k]})
    return line


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES), choices=list(SHAPES))
    ap.add_argument("--forms", nargs="+", default=["pallas", "xla"], choices=["pallas", "xla"])
    ap.add_argument("--tokens", nargs="*", type=int, default=[], help="further calls of [4, tokens, 1, 3584]")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=0, help="sublayers a timing; default 28 a prefill, 280 a chain step")
    ap.add_argument("--out", default="chiprun_out/mhc_kernel_bench.jsonl")
    a = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("no chip: a kernel's time comes only from a chip run", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    SHAPES.update({f"tokens-{t}": (t, 1) for t in a.tokens})
    with open(a.out, "a") as f:
        for shape in a.shapes + [f"tokens-{t}" for t in a.tokens]:
            for form in a.forms:
                line = measure(shape, form, a.seed, a.calls or (28 if SHAPES[shape][0] * SHAPES[shape][1] >= 4096 else 280))
                print(json.dumps(line), flush=True)
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
