"""The latent kernel ALONE (`ops/pallas/paged_attention.py::flash_decode_latent`,
the instruction `mla_paged_attn`), at the shapes the two latent cells run it:

| shape | q | pool | table | contexts |
|---|---|---|---|---|
| `xing` (`xing4.0-29b-a4b.serve.long-prompt-batch`, `step`) | [8, 2048, 32, 640] | [7 x 11,234, 16, 640] | 256 | prompts uniform 1,024-2,048, a row's tokens from position 0 |
| `glm` (`glm-4.7-flash.serve.batch`, `step`) | [64, 256, 20, 640] | [8 x 6,553, 16, 640] | 128 | prompts uniform 64-256 |
| `xing-decode` (`chain`) | [64, 1, 32, 640] | xing's | 256 | 1,024-2,048 + 0-31 decoded |
| `glm-decode` (`chain`) | [64, 1, 20, 640] | glm's | 128 | 64-256 + 0-191 decoded |
| `glm5-masked` (`glm-5.serve.long-prompt-wave8`, `step`; NOT in the default list) | [1, 8192, 64, 640] | [6 x 7,281, 16, 640] | 516 | one prompt uniform 4,096-8,192, under a MASK: each query's 2,048 largest of seeded random scores at or before it, all of them under 2,048 |
| `glm5-select` (the same cell's `step`; NOT in the default list) | the choice of the kept tokens ALONE, `ops/dsa.py::select_mask`: scores `f32[1, 8192, 8704]`, 2,048 kept, a bf16 mask out | | | one prompt of 4,096, of 6,144 and of 8,192 tokens from position 0, the bucket's other queries pads at position 0 as the engine has them |

    chiprun -- python tools/latent_kernel_bench.py [--shapes xing glm ...] [--forms 16:16,32:16]

`glm5-select` prints one line a prompt length and form (`xla`: the bisection as
31 reduce fusions over a row's keys in HBM; `kernel`: `dsa_select`,
`ops/pallas/dsa.py`, a tile of queries' keys bisected in fast memory): the
host's ms a call of some tens under one jit, for the kernel the instruction's
own device ms (`ms_per_call`), the bytes of scores it reads, the share of
(query tile, column chunk) cells it does not fetch, and whether the two masks
are equal as arrays. There `--forms` stands in for `_select_form`, `queries a
tile : columns a chunk`.

`glm5-masked` is the kernel under a per-query mask, the instruction
`dsa_paged_attn` (the walk still visits every position at or before a query:
2,048 kept of 6,000 leave no chunk that a whole tile dropped). Its line gives
the share of the bf16 peak on the WALKED positions (`bf16_peak_pct_on_live_work`:
what the kernel multiplies for live queries) and on the KEPT ones
(`bf16_peak_pct_on_kept_work`: what the benchmark's `dsa_attend_roofline.batch`
counts), and `us_per_chunk_step`. There `--forms` stands in for
`_masked_latent_form`; a tree before PR 56 has neither and runs its one masked
form, 16 tokens against 8 pages, which the line calls `16:8`.

Some tens of calls under one jit (each call's block table rests on the call
before, so nothing is hoisted but what does not change: the query's
pre-scale), timed on the host's clock around `block_until_ready` (`host_ms_per_call`: the
pre-scale's pass over q is in it, which a model's program fuses into q's
producer), and once more under the profiler for the instruction's own device
time, what a traced cell reads as a layer-call of `mla_paged_attn`: that is
`ms_per_call`, and the line's other numbers rest on it; one JSON line a shape
and form with the ms a call, the chunk-steps a call (a query
tile's page-chunks, summed), the us a `[512, 256]` of scores COMPUTED (dead
columns of a last chunk and padded rows included: what a chunk-step costs),
and the share of the bf16 peak on LIVE work: for each live query token its
context x `H` x 2 x (`W` + `v_width`) FLOPs, counted here.

`--forms` times other forms than the one the kernel picks from the shapes,
each `tokens a tile : pages a chunk`, by standing in for the module's
`_latent_form`; `16:16` is the form every call had before PR 52. Where the
trace holds no `mla_paged_attn` instruction, `ms_per_call` and what rests on
it are null: the host's clock is never written under that name. A time comes
only from a chip: without one this exits 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

W, V, BS = 640, 512, 16
BF16_PEAK = 197e12  # TPU v5e (benchmarks/lib/peaks.py)
# N, C, H, pages a layer, layers, table columns, prompt lengths, decoded tokens, softmax scale
SHAPES = {
    "xing": (8, 2048, 32, 11234, 7, 256, (1024, 2048), 0, 192 ** -0.5 * 2.00474),
    "glm": (64, 256, 20, 6553, 8, 128, (64, 256), 0, 256 ** -0.5),
    "xing-decode": (64, 1, 32, 11234, 7, 256, (1024, 2048), 31, 192 ** -0.5 * 2.00474),
    "glm-decode": (64, 1, 20, 6553, 8, 128, (64, 256), 191, 256 ** -0.5),
    "glm5-masked": (1, 8192, 64, 7281, 6, 516, (4096, 8192), 0, 256 ** -0.5),
}
KEPT = {"glm5-masked": 2048}  # shapes under a per-query mask: the positions a query keeps
SELECT = {"glm5-select": (8192, 8704, 2048, (4096, 6144, 8192))}  # queries, columns, kept, prompt lengths


def draw(shape: str, seed: int, layer: int = 3):
    """Lengths, positions and a block table as the engine hands them over: a
    row's live pages are distinct pages of `layer`, its dead entries the
    layer's page 0; a prompt's tokens stand at 0 .. C-1, the first `lens` live,
    a decode row's one token at its context's end."""
    N, C, _, pages, _, cols, (lo, hi), decoded, _ = SHAPES[shape]
    rng = np.random.default_rng(seed)
    ctx = rng.integers(lo, hi + 1, N) + (rng.integers(0, decoded + 1, N) if decoded else 0)
    table = np.zeros((N, cols), np.int32)
    free = rng.permutation(np.arange(1, pages))
    for n, c in enumerate(ctx):
        live = -(-int(c) // BS)
        table[n, :live], free = free[:live], free[live:]
    if C == 1:
        positions, lens = (ctx - 1)[:, None], np.ones(N)
    else:
        positions, lens = np.tile(np.arange(C), (N, 1)), ctx
    return positions.astype(np.int32), lens.astype(np.int32), table + layer * pages


def counts(shape: str, positions, lens, tq: int, ppcb: int) -> dict:
    """Chunk-steps, scores computed and live FLOPs of a call, from its shapes."""
    N, C, H = SHAPES[shape][:3]
    T = ppcb * BS
    rows = -(-tq * H // 16) * 16
    live = np.arange(C)[None, :] < lens[:, None]
    seen = np.where(live, positions + 1, 0)
    pad = -C % tq
    tiles = np.pad(seen, ((0, 0), (0, pad))).reshape(N, -1, tq).max(-1)
    steps = int((-(-tiles // T)).sum())
    kept = np.minimum(seen, KEPT.get(shape, C))
    return {"chunk_steps": steps, "scores": steps * rows * T,
            "live_flops": int(seen.sum()) * H * 2 * (W + V), "kept_flops": int(kept.sum()) * H * 2 * (W + V),
            "grid_steps": int(tiles.size), "rows": rows}


def chosen_mask(shape: str, seed: int, positions, columns: int):
    """bool [N, C, columns]: each query's `KEPT[shape]` largest of seeded random
    scores at or before its position (every one of them while there are no more)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import dsa

    scores = jax.random.uniform(jax.random.PRNGKey(seed + 7), positions.shape + (columns,), jnp.float32)
    seen = jnp.arange(columns)[None, None, :] <= jnp.asarray(positions)[..., None]
    return jax.jit(lambda s: dsa.select_mask(s, KEPT[shape]))(jnp.where(seen, scores, -jnp.inf))


def instruction_seconds(run, name: str):
    """(the instruction's text up to its operands, its device seconds) in one
    traced `run`: the kernel without the pre-scale and the slices around it."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            run()
        events = [e for path in glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
                  for plane in ProfileData.from_file(path).planes if plane.name.startswith("/device:TPU")
                  for line in plane.lines if line.name == "XLA Ops"
                  for e in line.events if e.name.startswith("%" + name)]
    return (events[0].name.split(" custom-call(")[0] if events else None), sum(e.duration_ns for e in events) / 1e9


def measure(shape: str, form, seed: int, calls: int, repeats: int = 5) -> dict:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import paged_attention as pa

    N, C, H, pages, layers, _, _, _, scale = SHAPES[shape]
    positions, lens, table = draw(shape, seed)
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (N, C, H, W), jnp.bfloat16)
    pool = jnp.tile(jax.random.normal(jax.random.fold_in(key, 1), (pages, BS, W), jnp.bfloat16), (layers, 1, 1))
    masked = shape in KEPT
    chooser, name = ("_masked_latent_form", "dsa_paged_attn") if masked else ("_latent_form", "mla_paged_attn")
    # a tree before PR 52 has no `_latent_form`: tiles of 16 tokens, chunks of 16 pages, and no other; one
    # before PR 56 no `_masked_latent_form`: 16 tokens against 8 pages
    picks = getattr(pa, chooser, lambda C, H, W, V, itemsize, P, bs: (min(C, 16), min(P, 8 if masked else 16)))
    if form is not None:
        assert hasattr(pa, chooser), "a tree without `%s` has one form" % chooser
        setattr(pa, chooser, lambda *shapes: form)  # every call below is traced in here; `main` puts it back
    tq, ppcb = form or picks(C, H, W, V, 2, table.shape[1], BS)
    # (the index kernel's scores, and so the model's mask, come 512 columns a tile: 8,704 for 516 pages)
    mask = (chosen_mask(shape, seed, positions, -(-table.shape[1] * BS // 512) * 512),) if masked else ()

    def kernel(q, pool, table, pos, n, *mask):
        return pa.flash_decode_latent(q, pool, table, pos, BS, scale, V, new_lens=n, mask=mask[0] if mask else None)

    @jax.jit
    def many(q, pool, table, pos, n, *mask):
        def one(_, table):
            out = kernel(q, pool, table, pos, n, *mask)
            # never true, and the compiler cannot know: the next call waits for this one
            return table + (out[0, 0, 0, 0].astype(jnp.float32) > 1e30).astype(jnp.int32)

        return jax.lax.fori_loop(0, calls, one, table)

    args = (q, pool, jnp.asarray(table), jnp.asarray(positions), jnp.asarray(lens)) + mask
    out = jax.jit(kernel)(*args)
    finite = bool(jnp.isfinite(out.astype(jnp.float32)).all())
    # the first row against the gather, its live tokens (under a mask 128 of them, from the first that
    # keeps fewer than it sees: the gather's scores of 8,192 queries are 17 GB): the largest difference
    # over the largest entry
    from deepspeed_tpu.inference import paged

    live = slice(KEPT[shape], KEPT[shape] + 128) if masked else slice(0, int(lens[0]))

    def gather(q, pool, table, pos, n, *mask):
        return paged._xla_latent_paged_attention(q[:, live], pool, table, pos[:, live], BS, scale, V,
                                                 mask=mask[0][:, live] if mask else None)

    want = jax.jit(gather)(*(a[:1] if a is not pool else a for a in args))
    want, got = want[0].astype(jnp.float32), out[0, live].astype(jnp.float32)
    err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    jax.block_until_ready(many(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(many(*args))
        times.append((time.perf_counter() - t0) / calls)
    traced = instruction_seconds(lambda: jax.block_until_ready(many(*args)), name)
    c = counts(shape, positions, lens, tq, ppcb)
    host = float(np.median(times))
    s = traced[1] / calls if traced[0] else None  # the instruction's own time, or nothing: never the host's clock

    def of_s(f):
        return None if s is None else f(s)

    return {"shape": shape, "form": "chosen" if form is None else ":".join(str(f) for f in form),
            "tile_tokens": tq, "pages_a_chunk": ppcb, "seed": seed, "calls": calls,
            "ms_per_call": of_s(lambda s: 1e3 * s), "host_ms_per_call": 1e3 * host, "instruction": traced[0],
            "grid_steps": c["grid_steps"], "rows": c["rows"], "chunk_steps": c["chunk_steps"],
            "us_per_chunk_step": of_s(lambda s: 1e6 * s / c["chunk_steps"]),
            "us_per_512x256_scores": of_s(lambda s: 1e6 * s / (c["scores"] / (512 * 256))),
            "live_tflop": c["live_flops"] / 1e12,
            "bf16_peak_pct_on_live_work": of_s(lambda s: 100 * c["live_flops"] / s / BF16_PEAK),
            "bf16_peak_pct_on_kept_work": of_s(lambda s: 100 * c["kept_flops"] / s / BF16_PEAK),
            "finite": finite, "row0_err_against_the_gather": err}


def measure_select(shape: str, form, seed: int, calls: int, repeats: int = 5):
    """The choice of the kept tokens alone, both forms, a line a prompt length."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import dsa
    from deepspeed_tpu.ops.pallas import dsa as kernel

    C, S, topk, lengths = SELECT[shape]
    if form is not None:
        kernel._select_form = lambda *shapes: form  # every call below is traced in here; `main` puts it back
    tq, T = form or kernel._select_form(C, S, 2)
    for length in lengths:
        pos = jnp.asarray(np.where(np.arange(C) < length, np.arange(C), 0)[None], jnp.int32)
        scores = jax.random.normal(jax.random.PRNGKey(seed + length), (1, C, S), jnp.float32)
        scores = jnp.where(jnp.arange(S)[None, None] <= pos[..., None], scores, -jnp.inf)
        last = np.asarray(pos).reshape(-1, tq).max(-1)
        fetched = int((last // T + 1).sum())  # (query tile, column chunk) cells
        masks = {}
        for impl in ("xla", "pallas"):
            choose = lambda s, p: dsa.select_mask(s, topk, jnp.bfloat16, p, impl=impl)  # noqa: E731

            @jax.jit
            def many(scores, pos):
                def one(i, carry):
                    s, _ = jax.lax.optimization_barrier((scores, i))  # nothing of a call is hoisted out of the loop
                    return carry + choose(s, pos)[0, 0, :8].astype(jnp.float32)

                return jax.lax.fori_loop(0, calls, one, jnp.zeros((8,), jnp.float32))

            masks[impl] = jax.jit(choose)(scores, pos)
            jax.block_until_ready(many(scores, pos))
            times = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(many(scores, pos))
                times.append((time.perf_counter() - t0) / calls)
            traced = instruction_seconds(lambda: jax.block_until_ready(many(scores, pos)), "dsa_select")  # noqa: B023
            line = {"shape": shape, "prompt": length, "form": "xla", "seed": seed, "calls": calls,
                    "host_ms_per_call": 1e3 * float(np.median(times)), "kept": int(masks[impl].astype(jnp.int32).sum()),
                    "score_bytes_read": 32 * C * S * 4}  # (the keys, once a step of the bisection)
            if impl == "pallas":
                line.update(form="kernel", tile_queries=tq, chunk_columns=T, instruction=traced[0],
                            ms_per_call=1e3 * traced[1] / calls if traced[0] else None,  # never the host's clock
                            score_bytes_read=fetched * tq * T * 4,
                            cells_skipped_share=1 - fetched / (C // tq * (S // T)),
                            equal_to_xla=bool((masks["pallas"] == masks["xla"]).all()))
            yield line


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=[s for s in SHAPES if s not in KEPT],
                    choices=list(SHAPES) + list(SELECT))
    ap.add_argument("--forms", default="", help="tokens a tile:pages a chunk[,...]; default: what the kernel picks")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=0, help="calls a timing; default 24 a prompt shape, 200 a decode shape")
    ap.add_argument("--out", default="chiprun_out/latent_kernel_bench.jsonl")
    a = ap.parse_args()

    import jax

    from deepspeed_tpu.ops.pallas import paged_attention as pa

    if jax.default_backend() != "tpu":
        print("no chip: a kernel's time comes only from a chip run", file=sys.stderr)
        return 1
    forms = [tuple(int(x) for x in f.split(":")) for f in a.forms.split(",") if f] or [None]
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "a") as f:
        for shape in a.shapes:
            for form in forms:
                if shape in SELECT:
                    from deepspeed_tpu.ops.pallas import dsa as select_kernel

                    picks = select_kernel._select_form
                    try:
                        for line in measure_select(shape, form, a.seed, a.calls or 24):
                            print(json.dumps(line), flush=True)
                            f.write(json.dumps(line) + "\n")
                    finally:
                        select_kernel._select_form = picks
                    continue
                calls = a.calls or (200 if SHAPES[shape][1] == 1 else 24)
                chooser = "_masked_latent_form" if shape in KEPT else "_latent_form"
                picks = getattr(pa, chooser, None)
                try:
                    line = measure(shape, form, a.seed, calls)
                except Exception as e:  # a form the compiler refuses (VMEM) is a reading too
                    line = {"shape": shape, "form": form, "refused": f"{type(e).__name__}: {str(e)[:300]}"}
                finally:
                    if picks is not None:
                        setattr(pa, chooser, picks)
                print(json.dumps(line), flush=True)
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
