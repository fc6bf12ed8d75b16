#!/usr/bin/env python3
"""A train cell's OWN ``train_step``, compiled on the CPU for the chips the
cell runs on, described and not attached (since PR 44):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 tools/train_step_for_described_chip.py --workload pythia-1.4b.train.zero3-4chip --out /root/scratch/step.txt

The engine is built as the benchmark's train runner builds it, on as many CPU
devices as the cell has chips (real widths: 17 GB of host memory for the 1.4B
cell, a few seconds); its shardings are then moved, spec for spec, onto
``build_mesh(devices=<the described v5e's>)`` (the device ORDER matters: a
plain reshape of the devices is no ring, and the compiler then answers the
weight contractions with gathers, not permutes), the kernels are told they
are on the chip, ``_build_train_step()`` is traced again and compiled for
shapes alone. PR 44 checked the result against ``compile().as_text()`` fetched
from the chip: the same program to the instruction (the layer scan's backward
body, 291 instructions, the collectives in the same order), about a minute a
compile and no chip time. It prints, for the computation that holds the flash
backward kernel (the layer scan's backward body), the order of the collectives'
starts and dones with the memory space of each one's buffers (``V`` fast
memory, ``H`` HBM), which is what moved the ZeRO-3 cell by 1.24% in PR 44
while every sub-layer got faster (``PERF.md`` section 6, ROADMAP S4).

Since PR 45 it also prints, for every computation of the step (a while
loop's body is one), its collectives by kind, count and MB (``computation=``
lines; a gather inside an ``async_collective_fusion`` is counted once, with
the computation that calls the fusion), and it finds the backward body by the
flash kernel alone: under ZeRO-3 that body now holds whole all-gathers and
reduce-scatters (``runtime/zero.py``) and no ring of permutes. A one-chip cell
compiles for the first of the described chips.

Nothing runs and nothing here is a time. Two compiled texts are compared with
``--against <other.txt>``: whether the collectives' order is equal, and which
buffers changed memory space.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import pkgutil
import re
import sys
from typing import Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_COMPUTATION = re.compile(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)")
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(")


def compile_train_step(model, engine_cfg: dict, batch, topology_name: str = "v5e:2x2"):
    """The compiled ``train_step`` of an engine built from ``model`` and
    ``engine_cfg`` (with its ``mesh`` entry), for the described topology."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding

    import deepspeed_tpu
    import deepspeed_tpu.ops.pallas as pallas_pkg
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.topology.mesh import build_mesh, set_mesh

    engine_cfg = dict(engine_cfg)
    axes = engine_cfg.pop("mesh")
    chips = int(np.prod(list(axes.values())))  # the first of the CPU's devices stand in for them
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=engine_cfg, mesh=build_mesh(devices=jax.devices()[:chips], axis_sizes=axes), seed=1)

    seen = {}

    def capture(state, placed):  # what train_batch hands the step: the state and the batch as placed
        seen["args"] = (state, placed)
        raise KeyboardInterrupt

    engine._train_step = capture
    try:
        engine.train_batch(batch)
    except KeyboardInterrupt:
        pass

    topo = topologies.get_topology_desc(platform="tpu", topology_name=topology_name)
    mesh = build_mesh(devices=topo.devices[:chips], axis_sizes=axes)  # a one-chip cell takes the first
    named = lambda x: isinstance(x, NamedSharding)  # noqa: E731
    moved = lambda x: NamedSharding(mesh, x.spec) if named(x) else x  # noqa: E731
    for name, value in list(vars(engine).items()):
        try:
            leaves = jax.tree_util.tree_leaves(value, is_leaf=named)
        except Exception:  # noqa: BLE001 - not a tree
            continue
        if any(named(leaf) for leaf in leaves):
            setattr(engine, name, jax.tree_util.tree_map(moved, value, is_leaf=named))
    engine.mesh = mesh
    set_mesh(mesh)
    registry._default_backend = lambda: "tpu"  # what 'auto' sees on the chip
    for info in pkgutil.iter_modules(pallas_pkg.__path__):
        module = importlib.import_module(f"deepspeed_tpu.ops.pallas.{info.name}")
        if hasattr(module, "_interpret"):
            module._interpret = lambda: False
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=moved(a.sharding)), seen["args"])
    return engine._build_train_step().lower(*shapes).compile()


_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all")
_ARRAY = re.compile(r"(pred|[a-z]+\d+)\[([\d,]*)\]")


def array_mb(shape: str) -> float:
    """MB of the largest array of an instruction's (possibly tuple) shape."""
    sizes = [int(re.sub(r"\D", "", dtype) or 8) // 8 * int(np.prod([int(d) for d in dims.split(",") if d]))
             for dtype, dims in _ARRAY.findall(shape)]  # ``pred`` names no width: a byte
    return max(sizes, default=0) / 1e6


def name_of(computation: str) -> Optional[str]:
    """A computation's name, from its text as ``_COMPUTATION`` splits it off."""
    head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", computation)
    return head.group(1) if head else None


def census(text: str):
    """{computation: [(kind, MB, op_name)]}: the collectives each computation
    of a compiled text runs, the ones inside the fusions it calls (an
    ``async_collective_fusion`` holds a gather beside a compute fusion)
    counted with the caller, once a channel (the fusions that start, step and
    end one gather each hold its instruction); a ``-done`` is its ``-start``'s
    other half and not counted. A while loop's body is a computation of its own."""
    bodies = {name_of(computation): computation.splitlines()[1:] for computation in _COMPUTATION.split(text)}
    bodies.pop(None, None)

    def own(name, seen=()):
        found = {}
        for line in bodies.get(name, ()):
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            opcode = m.group(3)
            kind = opcode[:-len("-start")] if opcode.endswith("-start") else opcode
            if kind in _KINDS:
                op_name, channel = re.search(r'op_name="([^"]*)"', line), re.search(r"channel_id=(\d+)", line)
                found.setdefault((kind, channel.group(1)) if seen and channel else m.group(1),
                                 (kind, array_mb(m.group(2)), op_name.group(1) if op_name else ""))
            elif opcode == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", line)
                if called and called.group(1) not in seen:
                    for key, value in own(called.group(1), seen + (name,)).items():
                        found.setdefault(key, value)
        return found

    fused = {re.search(r"calls=%?([\w.\-]+)", line).group(1)
             for lines in bodies.values() for line in lines if " fusion(" in line and "calls=" in line}
    return {name: list(own(name).values()) for name in bodies if name not in fused}


def by_kind(found):
    """{kind: (count, MB)} of one computation's ``census`` entry."""
    return {kind: (sum(1 for k, _, _ in found if k == kind), round(sum(mb for k, mb, _ in found if k == kind), 1))
            for kind in _KINDS if any(k == kind for k, _, _ in found)}


def holding(text: str, kernel: str) -> str:
    """The computation of a compiled text that holds the Mosaic kernel named
    ``kernel``, whatever collectives stand beside it: ``flash_fwd`` is in the
    layer scan's forward body, ``flash_bwd_dkv`` in its backward body."""
    for computation in _COMPUTATION.split(text):
        if re.search(rf"%{kernel}[\w.]* = [^\n]*tpu_custom_call", computation):
            return computation
    raise ValueError(f"no computation holds the {kernel} kernel")


def backward_body(text: str):
    """[(name, opcode, shape, last three components of op_name)] of the layer
    scan's backward body."""
    rows = []
    for line in holding(text, "flash_bwd_dkv").splitlines()[1:]:
        m = _INSTRUCTION.match(line)
        if m and m.group(3) not in ("get-tuple-element", "bitcast", "constant", "tuple", "parameter"):
            op_name = re.search(r'op_name="([^"]*)"', line)
            rows.append((m.group(1), m.group(3), m.group(2),
                         "/".join(op_name.group(1).split("/")[-3:]) if op_name else ""))
    return rows


def collectives(rows):
    """[(name, memory spaces of its first two buffers)] in schedule order."""
    found = []
    for name, opcode, shape, _ in rows:
        if "permute" in opcode or opcode.startswith(("all-", "reduce-scatter")) or name.startswith("flash"):
            spaces = "".join("V" if "S(1)" in part else "H" for part in re.findall(r"\][^\]]*?\}", shape)[:2])
            found.append((name, spaces))
    return found


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True, help="a train cell of BENCHMARK.json")
    ap.add_argument("--out", required=True, help="where the compiled text goes")
    ap.add_argument("--against", help="another compiled text to compare the collectives with")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_compilation_cache", False)  # an entry for a described chip cannot be read back
    from benchmarks.lib import program
    from deepspeed_tpu.models import causal_lm_spec

    workload = json.load(open(os.path.join(ROOT, "benchmarks", "workloads", a.workload + ".json")))
    config = json.load(open(os.path.join(ROOT, "benchmarks", "configs", workload["config"] + ".json")))
    traffic = workload["traffic"]
    if len(jax.devices()) < workload["chips"]:
        print(f"the cell has {workload['chips']} chips: set XLA_FLAGS=--xla_force_host_platform_device_count="
              f"{workload['chips']} and JAX_PLATFORMS=cpu", file=sys.stderr)
        return 1
    engine_cfg = dict(workload["engine"])
    engine_cfg.setdefault("mesh", {"dp": workload["chips"]})
    compiled = compile_train_step(
        causal_lm_spec(program.model_config(config, jnp.bfloat16), example_seq_len=int(traffic["seq_len"])),
        engine_cfg, {"input_ids": np.zeros((int(traffic["sequences"]), int(traffic["seq_len"])), np.int32)})
    text = compiled.as_text()
    with open(a.out, "w") as f:
        f.write(text)
    mine = collectives(backward_body(text))
    print(json.dumps({"out": a.out, "temp_gb": compiled.memory_analysis().temp_size_in_bytes / 1e9,
                      "backward_body_instructions": len(backward_body(text)), "collectives": len(mine)}))
    for name, spaces in mine:
        print(f"collective={name} buffers={spaces}")
    for name, found in census(text).items():
        if found:
            print(f"computation={name} " + " ".join(
                f"{kind}={count}:{mb}MB" for kind, (count, mb) in by_kind(found).items()))
    if a.against:
        theirs = collectives(backward_body(open(a.against).read()))
        other = dict(theirs)
        print(json.dumps({"against": a.against, "order_equal": [n for n, _ in mine] == [n for n, _ in theirs],
                          "memory_space_changed": [(n, other[n], s) for n, s in mine if other.get(n, s) != s]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
