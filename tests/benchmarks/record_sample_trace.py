#!/usr/bin/env python3
"""Record the small device trace that ``tests/benchmarks`` checks the reducer on.

``python tests/benchmarks/record_sample_trace.py --out chiprun_out/sample`` needs a
TPU. It traces a few calls of one tiny jitted program, ``sample_step`` (a
matmul, the repo's flash-attention kernel and, on more than one chip, an
all-reduce, an all-gather and a reduce-scatter), copies the ``.xplane.pb`` to
``<out>.xplane.pb`` and writes what a reader needs to see by hand (planes,
lines, a few events of each with their stats) to ``<out>.txt``.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)))

from benchmarks.lib import xplane  # noqa: E402


def describe(path: str, per_line: int = 6) -> str:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r} lines={len(lines)} stats={dict(plane.stats)}")
        for line in lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(events)}")
            seen = set()
            for ev in events:
                if ev.name in seen or len(seen) >= per_line:
                    continue
                seen.add(ev.name)
                stats = {k: (str(v)[:120]) for k, v in ev.stats}
                out.append(f"    {ev.name!r} start_ns={ev.start_ns} dur_ns={ev.duration_ns} {stats}")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--calls", type=int, default=3)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU: jax reports {jax.devices()[0].platform!r}", file=sys.stderr)
        return 1
    print({k: v for k, v in os.environ.items() if k.startswith(("JAX", "TPU", "XLA"))})

    from deepspeed_tpu.ops import causal_attention
    from deepspeed_tpu.topology.mesh import build_mesh, set_mesh
    from deepspeed_tpu.utils.compat import shard_map

    n = len(jax.devices())
    mesh = build_mesh(axis_sizes={"fsdp": n})
    set_mesh(mesh)

    def collectives(y):
        # one of each kind the ZeRO-3 step uses, by their lax names
        y = jax.lax.psum(y, "fsdp")
        g = jax.lax.all_gather(y, "fsdp", tiled=True)
        return jax.lax.psum_scatter(g, "fsdp", tiled=True)

    def sample_step(x, w, q):
        y = jnp.tanh(x @ w)
        if n > 1:
            y = shard_map(collectives, mesh=mesh, in_specs=P("fsdp"),
                          out_specs=P("fsdp"), check_vma=False)(y)
        a = causal_attention(q, q, q)
        return y.sum() + a.astype(jnp.float32).sum()

    rows = NamedSharding(mesh, P("fsdp"))
    key = jax.random.PRNGKey(0)
    x = jax.device_put(jax.random.normal(key, (n * 512, 1024), jnp.bfloat16), rows)
    w = jax.device_put(jax.random.normal(key, (1024, 1024), jnp.bfloat16),
                       NamedSharding(mesh, P()))
    q = jax.device_put(jax.random.normal(key, (n * 2, 512, 4, 64), jnp.bfloat16), rows)
    step = jax.jit(sample_step)
    jax.block_until_ready(step(x, w, q))

    trace_dir = args.out + ".trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    with jax.profiler.trace(trace_dir):
        for _ in range(args.calls):
            jax.block_until_ready(step(x, w, q))
    shutil.copy(xplane.find_xplane(trace_dir), args.out + ".xplane.pb")
    shutil.rmtree(trace_dir)
    text = describe(args.out + ".xplane.pb")
    with open(args.out + ".txt", "w") as f:
        f.write(text + "\n")
    print(text[-6000:])
    print("bytes", os.path.getsize(args.out + ".xplane.pb"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
