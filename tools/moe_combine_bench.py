"""The routed prefill's combine ALONE, at the shapes the two routed cells run it
in program `step`: 16,384 tokens x 4 picks = 65,536 rows of the experts' output
in sorted order, `M` 3,584 (`xing4.0-29b-a4b`, one `(8, 2048)` prefill) and
2,048 (`glm-4.7-flash`, one `(64, 256)` prefill), bf16.

    chiprun -- python tools/moe_combine_bench.py

One JSON line a reading (also in `chiprun_out/moe_combine_bench.jsonl`): ms a
call (median and least of `--repeats` rounds of ten queued calls on the host's
clock, the last waited for), the program's temporaries, and the largest
difference from the float32 sum. `program` is
`inference/model.py::_gather_combine` as `_moe_ragged` calls it; the others
are the spellings PR 40 weighed it against, kept as its yardsticks:
`scatter_add` (what the program ran before), `one_gather_sum` (one gather of
`[T, k, M]` and a sum over k) and `inverse_by_scatter` (the program's combine
with the sort inverted by an int32 scatter and not by a second sort).
XLA prefetches an ENTRY parameter of up to ~120 MB into fast memory, so a
gather FROM `[16384, M]` (the dispatch) reads 4-5 x faster here than inside
the program and is not timed. A time comes only from a chip: without one this
exits 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, T, k, M, E)
SHAPES = {"xing": (16384, 4, 3584, 64), "glm": (16384, 4, 2048, 64)}


def spellings():
    import jax.numpy as jnp

    from deepspeed_tpu.inference.model import _gather_combine

    f32 = jnp.float32

    def scatter_add(out_g, order, top_p):
        T, k = top_p.shape
        gates = top_p.reshape(-1)[order].astype(out_g.dtype)
        return jnp.zeros((T, out_g.shape[1]), out_g.dtype).at[order // k].add(out_g * gates[:, None])

    def one_gather_sum(out_g, order, top_p):
        inv = jnp.argsort(order).reshape(top_p.shape)
        return (out_g[inv].astype(f32) * top_p.astype(f32)[..., None]).sum(1).astype(out_g.dtype)

    def inverse_by_scatter(out_g, order, top_p):
        T, k = top_p.shape
        inv = jnp.zeros((T * k,), jnp.int32).at[order].set(jnp.arange(T * k, dtype=jnp.int32)).reshape(T, k)
        out = sum(top_p.astype(f32)[:, j:j + 1] * out_g[inv[:, j]].astype(f32) for j in range(k))
        return out.astype(out_g.dtype)

    return {"program": _gather_combine, "scatter_add": scatter_add, "one_gather_sum": one_gather_sum,
            "inverse_by_scatter": inverse_by_scatter}


def operands(shape, seed: int):
    """The experts' rows in sorted order, the sort's permutation and the gates
    of one shape, drawn as a router with a bias draws them."""
    import jax
    import jax.numpy as jnp

    T, k, M, E = shape
    rng = np.random.default_rng(seed)
    score = rng.standard_normal((T, E)) + 0.3 * rng.standard_normal(E)
    top_i = np.argsort(-score, axis=1)[:, :k].reshape(-1)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    order = jnp.argsort(jnp.asarray(top_i, jnp.int32), stable=True)
    top_p = jax.nn.softmax(jax.random.normal(key, (T, k), jnp.float32), axis=-1)
    out_g = jax.random.normal(jax.random.fold_in(key, 1), (T * k, M), jnp.bfloat16)
    want = jnp.einsum("tk,tkm->tm", top_p, out_g[jnp.argsort(order).reshape(T, k)].astype(jnp.float32))
    return out_g, order, top_p, want


def measure(name: str, spelling: str, ops, repeats: int = 5) -> dict:
    """One reading of `spelling` (a key of `spellings()`) on `ops = operands(...)`."""
    import jax
    import jax.numpy as jnp

    *args, want = ops
    fn = jax.jit(spellings()[spelling])
    got = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(10)])
        times.append((time.perf_counter() - t0) / 10)
    T, k = args[2].shape
    return {"shape": name, "T": T, "k": k, "M": args[0].shape[1], "spelling": spelling,
            "ms_per_call": 1e3 * float(np.median(times)), "ms_per_call_min": 1e3 * min(times),
            "temp_mib": fn.lower(*args).compile().memory_analysis().temp_size_in_bytes / 2 ** 20,
            "max_abs_err": float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))),
            "max_abs_ref": float(jnp.max(jnp.abs(want)))}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="xing,glm")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/moe_combine_bench.jsonl")
    a = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("no chip: a combine's time comes only from a chip run", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "a") as f:
        for name in a.shapes.split(","):
            ops = operands(SHAPES[name], a.seed)
            for spelling in spellings():
                line = json.dumps({**measure(name, spelling, ops, a.repeats), "seed": a.seed})
                print(line, flush=True)
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
