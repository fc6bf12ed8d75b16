"""The paged decode kernel ALONE, at the shapes a cell's program `chain` runs it,
one token a row, by the cell's name for the geometry:

    pythia          q [64, 1, 16, 128] over 16 kv heads, a table of 128 columns (a page 128 KiB of keys and
                    values), prompts uniform 64-256 plus 0-191 decoded tokens
    eva             q [24, 1, 32, 128] over 32 kv heads, 160 columns (256 KiB), positions 4,096-8,192 + 0-1,279:
                    128 summary rows a closed window of 2,048 plus the open window's rows
    qwen3-next      q [128, 1, 16, 256] over 2 kv heads, 64 columns (32 KiB), 64-256 + 0-511
    command-a-plus  q [8, 1, 128, 128] over 8 kv heads, 1,032 columns (64 KiB), 8,192-16,384 + 0-127
    mimo-global     q [128, 1, 64, 192] over 4 kv heads, values of 128, 194 columns (40 KiB), 1,024-2,048 + 0-1,023
    mimo-ring       the same rows over 8 kv heads on a ring of 9 columns (80 KiB) rolled under `first_live`
                    (a band of 128 keys), a sink a head: kernel `swa_paged_attn`

    chiprun -- python tools/paged_kernel_bench.py --geometry mimo-global,mimo-ring
    chiprun -- python tools/paged_kernel_bench.py --table-cols 32,128,1024      # pythia at other table widths

Several hundred calls under one jit (each call's query depends on the call
before, so nothing is hoisted), timed on the host's clock around
`block_until_ready`; prints one JSON line a reading with the time a call, the
pages a chunk the kernel chose and its share of the roofline by the
benchmark's own count and peaks: `benchmarks/lib/two_width.py::decode_cost`
for the two-width geometries (the key's columns and the value's apart, the
band's keys alone), `benchmarks/lib/costs.py::paged_decode_cost` for the rest
(live tokens only; `architectures/evabyte.py::eva_decode_cost` is the same
count at 32 heads). `--kernel-file` times another copy of
`ops/pallas/paged_attention.py` (the parent's, a step of a change) on the same
inputs; `--pages-per-block` hands the kernel a chunk instead of its own rule.
A time comes only from a chip: without one this exits 1.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BS = 16


@dataclasses.dataclass(frozen=True)
class Geometry:
    rows: int
    heads: int
    kv_heads: int
    key: int  # a key's columns
    value: int  # a value's
    columns: int  # the block table's width
    prompt: tuple  # uniform, inclusive
    decoded: int  # plus 0 .. decoded - 1 tokens
    band: int = 0  # a ring rolled under first_live, with a sink a head
    eva_window: int = 0  # summaries of closed windows before the open window's rows

    @property
    def page_bytes(self) -> int:
        return BS * self.kv_heads * (self.key + self.value) * 2


GEOMETRIES = {
    "pythia": Geometry(64, 16, 16, 128, 128, 128, (64, 256), 192),
    "eva": Geometry(24, 32, 32, 128, 128, 160, (4096, 8192), 1280, eva_window=2048),
    "qwen3-next": Geometry(128, 16, 2, 256, 256, 64, (64, 256), 512),
    "command-a-plus": Geometry(8, 128, 8, 128, 128, 1032, (8192, 16384), 128),
    "mimo-global": Geometry(128, 64, 4, 192, 128, 194, (1024, 2048), 1024),
    "mimo-ring": Geometry(128, 64, 8, 192, 128, 9, (1024, 2048), 1024, band=128),
}


def draw(g: Geometry, seed: int, columns: int = 0):
    """What the engine hands the kernel for a step of `g`'s cell: each row's query position and first live slot
    (in the table's own units: a ring's count from its oldest live page), the keys its query sees, and a block
    table whose live entries are distinct pages and whose dead entries are page 0."""
    rng = np.random.default_rng(seed)
    columns = columns or g.columns
    t = rng.integers(g.prompt[0], g.prompt[1] + 1, g.rows) + rng.integers(0, g.decoded, g.rows) - 1
    low = np.zeros_like(t)
    if g.band:
        low = np.maximum(t - g.band + 1, 0)
        oldest = low // BS
        t, low = t - oldest * BS, low - oldest * BS
    elif g.eva_window:
        t = g.eva_window // BS * (t // g.eva_window) + t % g.eva_window
    live = t // BS + 1
    assert live.max() <= columns, (live.max(), columns)
    table = np.zeros((g.rows, columns), np.int32)
    free = rng.permutation(np.arange(1, g.rows * g.columns + 1))
    for n, pages in enumerate(live):
        table[n, :pages], free = free[:pages], free[pages:]
    return t.astype(np.int32), low.astype(np.int32), (t + 1 - low).astype(np.int64), table


def least_seconds(g: Geometry, keys: int, device_kind: str) -> float:
    from benchmarks.lib import costs, peaks, two_width

    if g.key != g.value or g.band:
        cost = two_width.decode_cost(keys, g.rows, 1, g.heads, g.kv_heads, g.key, g.value, sink=bool(g.band))
    else:
        cost = costs.paged_decode_cost(keys, g.rows, g.heads, g.kv_heads, g.key)
    return costs.roofline_seconds(*cost, peaks.device_peaks(device_kind))[0]


def chunk_of(call, *args) -> int:
    """The pages a chunk of `call(*args)`, read off the kernel's scratch: its K slots are `[2, pages, bs, D]`."""
    import jax

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                shapes = [getattr(v.aval, "shape", ()) for v in eqn.params["jaxpr"].invars]
                found.extend(s[1] for s in shapes if len(s) == 4 and s[0] == 2)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(call)(*args).jaxpr)
    return int(found[0]) if found else 0


def measure(kernel, name: str, seed: int = 0, calls: int = 300, repeats: int = 5, columns: int = 0,
            pages_per_block: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    g = GEOMETRIES[name]
    pos, low, keys, table = draw(g, seed, columns)
    key = jax.random.PRNGKey(seed)
    pages = g.rows * g.columns + 1
    q = jax.random.normal(key, (g.rows, 1, g.heads, g.key), jnp.bfloat16)
    pool_k = jax.random.normal(jax.random.fold_in(key, 1), (pages, BS, g.kv_heads * g.key), jnp.bfloat16)
    pool_v = jax.random.normal(jax.random.fold_in(key, 2), (pages, BS, g.kv_heads * g.value), jnp.bfloat16)
    kw = {"new_lens": jnp.ones((g.rows,), jnp.int32)}
    if g.band:
        kw.update(first_live=jnp.asarray(low)[:, None], sink=jax.random.normal(jax.random.fold_in(key, 3), (g.heads,)))
    chunk = {"pages_per_block": pages_per_block} if pages_per_block else {}
    table, pos = jnp.asarray(table), jnp.asarray(pos)[:, None]

    def call(q, pool_k, pool_v, table, pos, kw):
        return kernel(q, pool_k, pool_v, table, pos, BS, **kw, **chunk)

    @jax.jit
    def many(q, *rest):
        def one(_, q):
            return q.at[..., :g.value].add(call(q, *rest) * jnp.asarray(1e-3, q.dtype))

        return jax.lax.fori_loop(0, calls, one, q)

    args = (q, pool_k, pool_v, table, pos, kw)
    out = jax.block_until_ready(many(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(many(*args))
        times.append((time.perf_counter() - t0) / calls)
    least = least_seconds(g, int(keys.sum()), jax.devices()[0].device_kind)
    ms = 1e3 * float(np.median(times))
    return {"geometry": name, "table_cols": table.shape[1], "page_kib": g.page_bytes / 1024,
            "pages_a_chunk": chunk_of(call, *args), "seed": seed, "calls": calls,
            "ms_per_call": ms, "ms_per_call_min": 1e3 * min(times), "least_ms": 1e3 * least,
            "roofline_pct": 100.0 * least / (ms / 1e3), "mean_keys": float(keys.mean()),
            "finite": bool(jnp.isfinite(out.astype(jnp.float32)).all())}


def kernel_from(path: str):
    """`flash_decode_paged` of the tree's module, or of a copy of it at `path`."""
    if not path:
        from deepspeed_tpu.ops.pallas.paged_attention import flash_decode_paged

        return flash_decode_paged
    spec = importlib.util.spec_from_file_location("paged_attention_copy", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.flash_decode_paged


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--geometry", default="", help="names of GEOMETRIES, or `all`")
    ap.add_argument("--table-cols", default="", help="pythia at these table widths")
    ap.add_argument("--kernel-file", default="", help="a copy of ops/pallas/paged_attention.py to time instead")
    ap.add_argument("--pages-per-block", type=int, default=0, help="a chunk of this many pages, not the kernel's rule")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=300)
    a = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("no chip: a kernel's time comes only from a chip run", file=sys.stderr)
        return 1
    kernel = kernel_from(a.kernel_file)
    names = list(GEOMETRIES) if a.geometry == "all" else [n for n in a.geometry.split(",") if n]
    readings = [(n, 0) for n in names] + [("pythia", int(c)) for c in a.table_cols.split(",") if c]
    for name, columns in readings or [("pythia", 32), ("pythia", 128)]:
        line = measure(kernel, name, a.seed, a.calls, columns=columns, pages_per_block=a.pages_per_block)
        print(json.dumps({**line, "kernel_file": a.kernel_file or "tree"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
