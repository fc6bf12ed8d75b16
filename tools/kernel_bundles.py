"""The scheduled bundles of the paged decode kernel, region by region, as the
TPU's compiler leaves them for the described v5e: no chip, nothing runs,
nothing here is a time. A bundle is a cycle at best (v5e runs 1.5 GHz), so a
loop's count against the cycles of the bytes it moves (819 GB/s) says whether
instructions or bytes bound it (PERF.md, PR 62).

    python tools/kernel_bundles.py --geometry mimo-global [--pages-per-block 8] [--kernel-file <copy>]

The kernel of `tools/paged_kernel_bench.py`'s geometry is compiled for
`v5e:2x2`'s first chip in a SUBPROCESS under
`LIBTPU_INIT_ARGS="--xla_jf_dump_to=<dir> --xla_jf_dump_llo_text=true"` (the
process aborts on a report's missing template AFTER the kernel's dump is
written, so its exit code says nothing), and the kernel's
`*-final_bundles.txt` is read: a region is a run of bundles at one loop depth
(the `>` marks) from one loop body's start (`LB:`) to the next change, printed
with its first bundle, its length and its counts of `dma` starts, `dma.done`
waits, `vmatpush`, `vmatmul` and `shalt.err` (a bounds check's halt). One JSON
line a region, then one for the kernel. `--keep` leaves the dump (100 MB).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))  # (``paged_kernel_bench`` puts the repo's root there)

BUNDLE = re.compile(r"^\s*(0x[0-9a-f]+|\d+)\s+(?:(LH|LB|LE|PB|PF|CT):)?\s*:?\s*(>*)\s*\{(.*)$")
COUNTED = {"dma": r"= dma\.(?!done)", "dma_wait": r"= dma\.done\.wait", "vmatpush": r"\bvmatpush\.",
           "vmatmul": r"\bvmatmul\.", "shalt_err": r"\bshalt\.err"}


def regions(text: str) -> list:
    """`[{first, bundles, depth, empty, dma, ...}]` of a `final_bundles.txt`."""
    out = []
    for line in text.splitlines():
        m = BUNDLE.match(line)
        if not m:
            continue
        address, mark, depth, body = m.groups()
        empty = body.strip().startswith("}")  # (a branch's delay slots carry no depth mark: they are its region's)
        if not out or mark == "LB" or (out[-1]["depth"] != len(depth) and not empty):
            out.append({"first": address, "depth": len(depth), "bundles": 0, "empty": 0, **dict.fromkeys(COUNTED, 0)})
        region = out[-1]
        region["bundles"] += 1
        region["empty"] += empty
        for name, pattern in COUNTED.items():
            region[name] += len(re.findall(pattern, body))
    return out


def compile_one(geometry: str, pages_per_block: int, kernel_file: str) -> None:
    """(the subprocess) Compile the geometry's one call for the described chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paged_kernel_bench as bench

    jax.config.update("jax_enable_compilation_cache", False)
    kernel = bench.kernel_from(kernel_file)
    kernel.__globals__["_interpret"] = lambda: False  # the compile is for the chip, whatever backend this process has
    one_chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    sds = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    g = bench.GEOMETRIES[geometry]
    pages = g.rows * g.columns + 1
    ring = [sds((g.rows, 1), jnp.int32), sds((g.heads,), jnp.float32)] * bool(g.band)
    chunk = {"pages_per_block": pages_per_block} if pages_per_block else {}

    def call(q, pool_k, pool_v, table, pos, lens, *ring):
        return kernel(q, pool_k, pool_v, table, pos, bench.BS, new_lens=lens,
                      **dict(zip(("first_live", "sink"), ring)), **chunk)

    jax.jit(call).lower(sds((g.rows, 1, g.heads, g.key)), sds((pages, bench.BS, g.kv_heads * g.key)),
                        sds((pages, bench.BS, g.kv_heads * g.value)), sds((g.rows, g.columns), jnp.int32),
                        sds((g.rows, 1), jnp.int32), sds((g.rows,), jnp.int32), *ring).compile()


def bundles_of(geometry: str, pages_per_block: int = 0, kernel_file: str = "", keep: str = "") -> list:
    """Compile in a subprocess under the dump flags and read the kernel's final bundles."""
    dump = keep or tempfile.mkdtemp(prefix="kernel_bundles_")
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} --xla_jf_dump_llo_text=true")
    done = subprocess.run([sys.executable, os.path.abspath(__file__), "--compile", "--geometry", geometry,
                           "--pages-per-block", str(pages_per_block), "--kernel-file", kernel_file],
                          env=env, capture_output=True, text=True)
    try:
        files = [f for f in glob.glob(os.path.join(dump, "*paged_attn*-final_bundles.txt"))
                 if "schedule-analysis" not in f]
        if len(files) != 1:
            raise RuntimeError(f"no dump of one kernel (exit {done.returncode}, {len(files)} files):\n"
                               + done.stderr[-2000:])
        with open(files[0]) as f:
            return regions(f.read())
    finally:
        if not keep:
            shutil.rmtree(dump, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--geometry", required=True)
    ap.add_argument("--pages-per-block", type=int, default=0)
    ap.add_argument("--kernel-file", default="")
    ap.add_argument("--keep", default="", help="a directory to leave the compiler's dump in")
    ap.add_argument("--compile", action="store_true", help=argparse.SUPPRESS)
    a = ap.parse_args()
    if a.compile:
        compile_one(a.geometry, a.pages_per_block, a.kernel_file)
        return 0
    found = bundles_of(a.geometry, a.pages_per_block, a.kernel_file, a.keep)
    for region in found:
        print(json.dumps(region))
    print(json.dumps({"geometry": a.geometry, "pages_per_block": a.pages_per_block or "rule",
                      "kernel_file": a.kernel_file or "tree", "regions": len(found),
                      **{k: sum(r[k] for r in found) for k in ("bundles", "empty", *COUNTED)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
