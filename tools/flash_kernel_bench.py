"""The flash attention kernels ALONE, at the shapes the two train cells run
them: `pythia-410m.train.seq2048` calls them on [2, 2048, 16, 64] bf16 (micro
batch 2, head_dim 64), `pythia-1.4b.train.zero3-4chip` on [1, 2048, 16, 128]
a chip. Causal, no padding mask, blocks of 512: 10 score blocks a head.

    chiprun -- python tools/flash_kernel_bench.py

Three readings a shape: `fwd` (`_flash_fwd`), `bwd` (`_flash_bwd` as it
chooses from the shape: one pass while a head's dq fits its VMEM budget) and
`bwd_pair` (the same call with the budget set to nothing, so the dq kernel
and the dkv kernel run, as they do for sequences past the budget). The
backward readings include the call's XLA glue (delta = sum(do * o), and the
three adds that chain one call to the next: 0.25 us a block beside the
trace's kernel seconds); since PR 44 the kernels hand dq, dk and dv over in
the inputs' dtype, scaled inside. Several
hundred calls under one jit (each call's input depends on the call before,
so nothing is hoisted), timed on the host's clock around
`block_until_ready`; one JSON line a reading with the time a call, the time a
score block and the share of the roofline by the benchmark's own count and
peaks (`benchmarks/lib/costs.py::flash_forward_cost`, `flash_backward_cost`:
five products a block, however often a kernel forms the scores). Beside each
time, `stat_bytes_per_call`: what the per-query-row statistics (lse out of the
forward; lse and delta into the backward) take in HBM a call AS THE COMPILED
PROGRAM STORES THEM, by kernel, read from the compiled text's shapes and tiles
(`[B, H, 1, S]` rows in `T(1,128)` tiles are B*H*S*4 bytes; a `[B, H, S, 8]`
column in `T(8,128)` tiles was sixteen times its numbers).

Since PR 58 also the forward as `command-a-plus-05-2026.serve.long-prompt-wave8`
calls it for a fresh prompt padded to its bucket, `[1, 16384, 128 over 8, 128]`
under the causal mask alone (`flash_fwd`) and under a band of 4,096 keys
(`swa_flash_fwd`), `reading` `prefill`: the `parent` form (no `lengths`: the
bucket's whole grid, whatever the prompt) and the `live` form (`lengths`: no
cell past the prompt's last token) at prompts of 8,192, 12,288 and 16,384
tokens, each with the kernel's own count of cells, the time a LIVE cell and
the share of the roofline of the live prompt's work
(`benchmarks/architectures/cohere2_moe.py::swa_prefill_cost` /
`full_prefill_cost`), and whether the live rows are the parent form's to the
bit with the pads zeros. A dead step's cost is READ: two rows of 16,384 and
8,192 live tokens in one call run the shorter row's dead cells as steps that
compute and fetch nothing, so `dead_step_us` is that call's time less the two
rows' own calls, over the dead steps. A time comes only from a chip: without
one this exits 1.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from unittest import mock

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = {  # [B, S, H, D] of one call, by the cell that makes it
    "pythia-410m.train.seq2048": (2, 2048, 16, 64),
    "pythia-1.4b.train.zero3-4chip": (1, 2048, 16, 128),
}
READINGS = ("fwd", "bwd", "bwd_pair")
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
_MADE = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ([a-z]\w*\[[\d,]*\]\S*) ")
_F32 = re.compile(r"f32\[([\d,]+)\]\{([\d,]+)(?::T\((\d+),(\d+)\))?")


def stat_bytes(compiled_text: str, shape) -> dict:
    """{kernel: bytes of its float32 statistics as stored}, from a compiled
    program's text: every f32 result or operand of a kernel's call with fewer
    elements than a ``[B, H, S, D]`` gradient and at least ``B*H*S`` (the
    slopes are smaller), each dimension padded as its layout's tile says."""
    B, S, H, D = shape
    lines = compiled_text.splitlines()
    made = {m.group(1): m.group(2) for m in (_MADE.match(line) for line in lines) if m}
    found = {}
    for line in lines:
        if "tpu_custom_call" not in line:
            continue
        name = next((k for k in KERNELS if re.search(r'[/("]%s[/)"]' % k, line)), None)
        if name is None:
            continue
        results, _, rest = line.partition(" custom-call(")
        operands = [made.get(o, "") for o in re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0])]
        total = 0
        for dims, order, sub, lanes in _F32.findall(" ".join([results.split(" = ", 1)[1], *operands])):
            dims = [int(d) for d in dims.split(",")]
            if not B * H * S <= int(np.prod(dims)) < B * H * S * D:
                continue
            minor, second = (int(i) for i in order.split(",")[:2])
            if lanes:
                dims[minor] = -(-dims[minor] // int(lanes)) * int(lanes)
                dims[second] = -(-dims[second] // int(sub)) * int(sub)
            total += 4 * int(np.prod(dims))
        found[name] = total
    return found


def measure(reading: str, shape, block: int = 512, seed: int = 0, calls: int = 200,
            repeats: int = 5, device_kind: str = "") -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import costs, peaks
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    B, S, H, D = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, do = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16) for kk in keys)
    q = q * jnp.asarray(D ** -0.5 * fa._LOG2E, q.dtype)  # as _flash_core hands it over
    mask = jnp.ones((B, 1, S), jnp.int32)
    slopes = jnp.zeros((H, 1, fa._LANES), jnp.float32)
    small = jnp.asarray(1e-3, q.dtype)

    def fwd(q):
        return fa._flash_fwd(q, k, v, mask, slopes, block, block, True, False, False)

    @jax.jit
    def many(q, do):
        if reading == "fwd":
            return jax.lax.fori_loop(0, calls, lambda _, q: q + fwd(q)[0] * small, q)
        out, lse = fwd(q)

        def one(_, do):
            dq, dk, dv = fa._flash_bwd(q, k, v, mask, slopes, out, lse, do, block, block,
                                       True, False, False)
            return do + (dq + dk + dv).astype(do.dtype) * small

        return jax.lax.fori_loop(0, calls, one, do)

    # the pair is what _flash_bwd runs when the dq slab is over its budget
    budget = 0 if reading == "bwd_pair" else fa._ONE_PASS_DQ_BYTES
    with mock.patch.object(fa, "_ONE_PASS_DQ_BYTES", budget):
        traced = many.lower(q, do)
        lowered = traced.as_text(debug_info=True)
        many = traced.compile()  # the one compile: its text is read and it is what runs
        stats = stat_bytes(many.as_text(), shape)
        result = jax.block_until_ready(many(q, do))
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(many(q, do))
            times.append((time.perf_counter() - t0) / calls)

    cost = costs.flash_forward_cost if reading == "fwd" else costs.flash_backward_cost
    least, bound = costs.roofline_seconds(
        *cost(B, H, S, D), peaks.device_peaks(device_kind or jax.devices()[0].device_kind))
    n = -(-S // block)
    blocks = B * H * n * (n + 1) // 2
    call = float(np.median(times))
    return {"reading": reading, "shape": list(shape), "block": block, "seed": seed,
            "calls": calls, "ms_per_call": 1e3 * call, "ms_per_call_min": 1e3 * min(times),
            "blocks": blocks, "us_per_block": 1e6 * call / blocks,
            "least_ms": 1e3 * least, "bound": bound, "roofline_pct": 100.0 * least / call,
            "kernels": [name for name in KERNELS if re.search(r'[/("]%s[/)]' % name, lowered)],
            "stat_bytes_per_call": stats, "stat_numbers_bytes": 4 * B * H * S,
            "finite": bool(jnp.isfinite(result.astype(jnp.float32)).all())}


PREFILL_CELL = "command-a-plus-05-2026.serve.long-prompt-wave8"
PREFILL_SHAPE = (16384, 128, 8, 128)  # S, H, Hkv, D of the cell's (1, 16384) prefill
PREFILL_LENGTHS = (8192, 12288, 16384)


def measure_prefill(window, lengths, form: str, shape=PREFILL_SHAPE, block: int = 512, seed: int = 0,
                    calls: int = 10, repeats: int = 3, device_kind: str = "") -> dict:
    """The forward alone on ``len(lengths)`` rows of the cell's bucket, ``form`` ``parent`` (no ``lengths``)
    or ``live``; beside the time, whether the live rows equal the parent form's and the pads are zeros."""
    import jax
    import jax.numpy as jnp

    from benchmarks.architectures import cohere2_moe
    from benchmarks.lib import costs, peaks
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    S, H, Hkv, D = shape
    B = len(lengths)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, H, S, D), jnp.bfloat16) * jnp.asarray(D ** -0.5 * fa._LOG2E, jnp.bfloat16)
    k, v = (jax.random.normal(kk, (B, Hkv, S, D), jnp.bfloat16) for kk in keys[1:])
    mask, slopes = jnp.ones((B, 1, S), jnp.int32), jnp.zeros((H, 1, fa._LANES), jnp.float32)
    small = jnp.asarray(1e-3, q.dtype)
    told = jnp.asarray(lengths, jnp.int32)

    def fwd(q, k, v, told=None):
        return fa._flash_fwd(q, k, v, mask, slopes, block, block, True, False, False, 1, window, lengths=told)[0]

    @jax.jit
    def many(q, k, v, told):
        return jax.lax.fori_loop(
            0, calls, lambda _, q: q + fwd(q, k, v, told if form == "live" else None) * small, q)

    jax.block_until_ready(many(q, k, v, told))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(many(q, k, v, told))
        times.append((time.perf_counter() - t0) / calls)
    once, whole = jax.jit(fwd)(q, k, v, told if form == "live" else None), jax.jit(fwd)(q, k, v)
    fed = (jnp.arange(S) < told[:, None])[:, None, :, None]
    agrees = bool(jnp.array_equal(jnp.where(fed, once, 0), jnp.where(fed, whole, 0)))
    pads_zero = not bool(jnp.any(jnp.where(fed, 0, once) != 0))
    live, grid = fa.forward_cells(lengths, S, window, block)
    cfg = {"num_attention_heads": H, "num_key_value_heads": Hkv, "head_dim": D, "sliding_window": window}
    work = (cohere2_moe.full_prefill_cost if window is None else cohere2_moe.swa_prefill_cost)(cfg, lengths)
    least, bound = costs.roofline_seconds(*work, peaks.device_peaks(device_kind or jax.devices()[0].device_kind))
    call = float(np.median(times))
    return {"reading": "prefill", "kernel": "flash_fwd" if window is None else "swa_flash_fwd", "form": form,
            "shape": [B, *shape], "window": window, "lengths": list(lengths), "block": block, "seed": seed,
            "calls": calls, "ms_per_call": 1e3 * call, "ms_per_call_min": 1e3 * min(times),
            "cells_live": live, "cells_grid": grid, "us_per_live_cell": 1e6 * call / (live * H),
            "least_ms": 1e3 * least, "bound": bound, "roofline_pct": 100.0 * least / call,
            "live_rows_equal": agrees, "pads_zero": pads_zero if form == "live" else None}


def prefill_readings(seed: int):
    """One line a reading; after a kernel's, what a dead step costs (module docstring)."""
    H = PREFILL_SHAPE[1]
    for window in (None, 4096):
        one = {}
        yield measure_prefill(window, PREFILL_LENGTHS[-1:], "parent", seed=seed)
        for n in PREFILL_LENGTHS:
            one[n] = measure_prefill(window, (n,), "live", seed=seed)
            yield one[n]
        long, short = PREFILL_LENGTHS[-1], PREFILL_LENGTHS[0]
        both = measure_prefill(window, (long, short), "live", seed=seed)
        yield both
        dead = (both["cells_grid"] - both["cells_live"]) * H
        yield {"reading": "prefill", "kernel": both["kernel"], "dead_steps": dead,
               "dead_step_us": 1e3 * (both["ms_per_call"] - one[long]["ms_per_call"] - one[short]["ms_per_call"]) / dead}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--prefill-only", action="store_true", help="the serving prefill's readings alone")
    a = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("no chip: a kernel's time comes only from a chip run", file=sys.stderr)
        return 1
    for cell, shape in ({} if a.prefill_only else SHAPES).items():
        for reading in READINGS:
            print(json.dumps({"cell": cell, **measure(reading, shape, seed=a.seed, calls=a.calls)}),
                  flush=True)
    for line in prefill_readings(a.seed):
        print(json.dumps({"cell": PREFILL_CELL, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
