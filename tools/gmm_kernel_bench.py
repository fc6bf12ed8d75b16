"""The routed prefill's grouped matmul ALONE, at the shapes
`glm-4.7-flash.serve.batch` runs it in program `step`: one `(64, 256)` prefill
is 16,384 tokens x 4 picks = 65,536 (token, expert) rows sorted by expert,
against 64 experts' `[2048, 1536]` (gate, up) and `[1536, 2048]` (down), bf16.

    chiprun -- python tools/gmm_kernel_bench.py                 # the chooser's tiles
    chiprun -- python tools/gmm_kernel_bench.py --sweep         # and the tile sweep
    chiprun -- python tools/gmm_kernel_bench.py --backward      # d lhs and d rhs of the same calls

One JSON line a reading: ms a call (median and least of `--repeats` timed
calls on the host's clock around `block_until_ready`), TFLOP/s on the rows'
own 2 m K N, the share of the chip's bf16 peak (`benchmarks/lib/peaks.py`),
the grid's steps, and the largest difference from `lax.ragged_dot` over the
same operands. `tiles: "chosen"` goes through `_gmm_padded` as the program
does; a swept tiling calls the library kernel with those tiles. Group sizes
are drawn as a router with a bias draws them (`--pad-share` sends that share
of the tokens to the same picks, as a prompt's padding goes). `--backward`
reads the gradient's two kernels instead (`jax.vjp` through `_gmm_padded`; the
library's own `custom_vjp` at tiles of 128, which is what a `jax.grad` ran
before PR 34; `tgmm` alone at a swept tiling) against plain products a group.
A time comes only from a chip: without one this exits 1.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, m, K, N, E, k): the cell's two calls, the set-up check's prefill of four
# prompts, and a mixtral-shaped layer (8 experts, top-2, groups of a thousand)
SHAPES = {
    "glm-up": (65536, 2048, 1536, 64, 4),
    "glm-down": (65536, 1536, 2048, 64, 4),
    "glm-check": (4096, 2048, 1536, 64, 4),
    "mixtral-up": (8192, 4096, 14336, 8, 2),
    "mixtral-down": (8192, 14336, 4096, 8, 2),
}


def group_sizes(seed: int, m: int, E: int, k: int, pad_share: float) -> np.ndarray:
    """Rows a group for m / k tokens that each pick k distinct experts by
    noise + a drawn bias; `pad_share` of the tokens share one token's picks."""
    rng = np.random.default_rng(seed)
    T = m // k
    score = rng.standard_normal((T, E)) + 0.3 * rng.standard_normal(E)
    score[: int(T * pad_share)] = score[0]
    picks = np.argsort(-score, axis=1)[:, :k]
    return np.bincount(picks.reshape(-1), minlength=E).astype(np.int32)


def grid_steps(gs: np.ndarray, m: int, K: int, N: int, tiles) -> int:
    """Steps of the kernel's grid `(N / tn, visited m-tiles, K / tk)`: an
    m-tile is visited once a group that has rows in it."""
    tm, tk, tn = tiles
    ends = np.cumsum(gs)
    starts = ends - gs
    visits = int(sum(-(-e // tm) - s // tm for s, e in zip(starts, ends) if e > s))
    return -(-N // tn) * visits * -(-K // tk)


def operands(shape, seed: int, pad_share: float):
    """lhs, rhs, group sizes (device and host) and `lax.ragged_dot`'s product of one shape."""
    import jax
    import jax.numpy as jnp

    m, K, N, E, k = shape
    gs_np = group_sizes(seed, m, E, k, pad_share)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    lhs = jax.random.normal(key, (m, K), jnp.bfloat16)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (E, K, N), jnp.bfloat16) * 0.02
    gs = jnp.asarray(gs_np)
    want = jax.block_until_ready(jax.lax.ragged_dot(lhs, rhs, gs))
    return lhs, rhs, gs, gs_np, want.astype(jnp.float32)


def _timed(fn, args, repeats: int):
    """Median and least seconds of `repeats` calls, each waited for."""
    import jax

    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), min(times)


def _refusal(e: Exception) -> str:
    msg = str(e)
    at = msg.find("Scoped allocation")
    return msg[at:at + 100] if at >= 0 else msg[:200].replace("\n", " ")


def measure(name: str, tiles, ops, repeats: int = 5, device_kind: str | None = None,
            interpret: bool = False) -> dict:
    """One reading of shape `name` (a key of SHAPES) on `ops = operands(...)`:
    `tiles` is "chosen" (through `_gmm_padded`, as the program calls it) or a
    `(tm, tk, tn)` handed to the library kernel."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from benchmarks.lib import peaks
    from deepspeed_tpu.inference.model import _gmm_padded, _gmm_tiles

    lhs, rhs, gs, gs_np, want = ops
    (m, K), (E, _, N) = lhs.shape, rhs.shape
    if tiles == "chosen":
        used = _gmm_tiles(m, K, N, E, lhs.dtype.itemsize)
        fn = jax.jit(lambda a, b, g: _gmm_padded(a, b, g, interpret=interpret))
    else:
        used = tuple(tiles)
        fn = jax.jit(lambda a, b, g: gmm(a, b, g, preferred_element_type=a.dtype, tiling=used,
                                         interpret=interpret))
    out = {"shape": name, "m": m, "K": K, "N": N, "E": E, "tiles": "chosen" if tiles == "chosen" else "swept",
           "tm": used[0], "tk": used[1], "tn": used[2], "rows_largest_group": int(gs_np.max()),
           "grid_steps": grid_steps(gs_np, m, K, N, used)}
    try:
        got = jax.block_until_ready(fn(lhs, rhs, gs))
    except Exception as e:  # noqa: BLE001 - a tiling Mosaic refuses is a reading too
        return {**out, "refused": _refusal(e)}
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    med, least = _timed(fn, (lhs, rhs, gs), repeats)
    flops = 2.0 * m * K * N
    peak = peaks.device_peaks(device_kind or jax.devices()[0].device_kind).bf16_flops_per_s
    return {**out, "ms_per_call": 1e3 * med, "ms_per_call_min": 1e3 * least, "tflops": flops / med / 1e12,
            "peak_pct": 100.0 * flops / med / peak, "max_abs_err": err,
            "max_abs_ref": float(jnp.max(jnp.abs(want))), "reference": "lax.ragged_dot"}


def backward_references(ops):
    """A drawn cotangent of one shape's product and the two gradients by plain
    products: `lax.ragged_dot(ct, rhs.T)` and `lhs[rows of g].T @ ct[rows of g]`."""
    import jax
    import jax.numpy as jnp

    lhs, rhs, gs, gs_np, _ = ops
    ct = jax.random.normal(jax.random.PRNGKey(7), (lhs.shape[0], rhs.shape[-1]), lhs.dtype)
    ends = np.cumsum(gs_np)
    want_rhs = jnp.stack([jnp.dot(lhs[s:e].T, ct[s:e], preferred_element_type=jnp.float32)
                          for s, e in zip(ends - gs_np, ends)])
    want_lhs = jax.lax.ragged_dot(ct, rhs.swapaxes(1, 2), gs, preferred_element_type=jnp.float32)
    return ct, want_lhs, want_rhs


def measure_backward(name: str, tiles, ops, refs, repeats: int = 5, device_kind: str | None = None,
                     interpret: bool = False) -> dict:
    """One reading of the gradient's kernels on `ops` and `refs =
    backward_references(ops)`: `tiles` is "chosen" (`jax.vjp` through
    `_gmm_padded`: d lhs at `_gmm_tiles(m, N, K, ...)`, d rhs at
    `_tgmm_tiles`), "library-128" (the library's `custom_vjp` at the forward's
    tiles, the 128s before PR 34) or a `(tm, tk, tn)` for `tgmm` alone. The
    forward's output is not asked for, so the compiled program holds the
    backward's kernels only."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    from benchmarks.lib import peaks
    from deepspeed_tpu.inference.model import _gmm_padded, _gmm_tiles, _tgmm_tiles

    lhs, rhs, gs, _, _ = ops
    ct, want_lhs, want_rhs = refs
    (m, K), (E, _, N) = lhs.shape, rhs.shape
    out = {"shape": name, "m": m, "K": K, "N": N, "E": E, "pass": "backward"}
    if isinstance(tiles, str):
        fwd = ((lambda a, b, g: _gmm_padded(a, b, g, interpret)) if tiles == "chosen" else
               (lambda a, b, g: gmm(a, b, g, preferred_element_type=a.dtype, tiling=(128, 128, 128),
                                    interpret=interpret)))
        fn = jax.jit(lambda a, b, g, c: jax.vjp(lambda x, y: fwd(x, y, g), a, b)[1](c))
        out.update(tiles=tiles, kernels="d_lhs+d_rhs", flops=4.0 * m * K * N)
        if tiles == "chosen":
            size = lhs.dtype.itemsize
            out.update(d_lhs_tiles=list(_gmm_tiles(m, N, K, E, size)), d_rhs_tiles=list(_tgmm_tiles(m, K, N, E, size)))
    else:
        used = tuple(tiles)
        fn = jax.jit(lambda a, b, g, c: (None, tgmm(a.swapaxes(0, 1), c, g, preferred_element_type=b.dtype,
                                                    tiling=used, interpret=interpret)))
        out.update(tiles="swept", kernels="d_rhs", flops=2.0 * m * K * N, d_rhs_tiles=list(used))
    try:
        d_lhs, d_rhs = jax.block_until_ready(fn(lhs, rhs, gs, ct))
    except Exception as e:  # noqa: BLE001 - a tiling Mosaic refuses is a reading too
        return {**out, "refused": _refusal(e)}
    f32 = jnp.float32
    out.update(d_rhs_max_abs_err=float(jnp.max(jnp.abs(d_rhs.astype(f32) - want_rhs))),
               d_rhs_max_abs_ref=float(jnp.max(jnp.abs(want_rhs))))
    if d_lhs is not None:
        out.update(d_lhs_max_abs_err=float(jnp.max(jnp.abs(d_lhs.astype(f32) - want_lhs))),
                   d_lhs_max_abs_ref=float(jnp.max(jnp.abs(want_lhs))))
    med, least = _timed(fn, (lhs, rhs, gs, ct), repeats)
    peak = peaks.device_peaks(device_kind or jax.devices()[0].device_kind).bf16_flops_per_s
    return {**out, "ms_per_call": 1e3 * med, "ms_per_call_min": 1e3 * least,
            "tflops": out["flops"] / med / 1e12, "peak_pct": 100.0 * out["flops"] / med / peak}


def sweep_tilings(K: int, N: int):
    tns = [t for t in (256, 512, 768, 1024) if N % t == 0]
    return [(128, 128, 128)] + list(itertools.product((128, 256, 512), (K, 1024, 512), tns))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="glm-up,glm-down,glm-check,mixtral-up,mixtral-down")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--backward", action="store_true", help="the gradient's kernels instead of the forward's")
    ap.add_argument("--tilings", default="", help="tm:tk:tn,... instead of the sweep's own list")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--pad-share", type=float, default=0.0)
    ap.add_argument("--out", default="chiprun_out/gmm_kernel_bench.jsonl")
    a = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("no chip: a kernel's time comes only from a chip run", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "a") as f:
        for name in a.shapes.split(","):
            _, K, N, _, _ = SHAPES[name]
            todo = ["chosen", "library-128"] if a.backward else ["chosen"]
            if a.tilings:
                todo += [tuple(int(x) for x in t.split(":")) for t in a.tilings.split(",")]
            elif a.sweep:
                todo += (itertools.product((128, 256, 512), (256, 512, 1024), (256, 512)) if a.backward
                         else sweep_tilings(K, N))
            ops = operands(SHAPES[name], a.seed, a.pad_share)
            refs = backward_references(ops) if a.backward else None
            for tiles in todo:
                reading = (measure_backward(name, tiles, ops, refs, a.repeats) if a.backward
                           else measure(name, tiles, ops, a.repeats))
                line = json.dumps({**reading, "seed": a.seed, "pad_share": a.pad_share})
                print(line, flush=True)
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
