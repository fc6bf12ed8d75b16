"""Device self-time by program scope, from xprof's ``hlo_stats`` over a trace.

Since PR 25 the program names its layers with ``jax.named_scope`` and its
Pallas kernels with ``pallas_call(name=)``; both end up as path components of
an HLO instruction's ``op_name`` (``jit(chain)/while/body/pool_scan/while/
body/layer/kv_write/scatter``; under autodiff ``jvp(lm_head_ce)`` and
``transpose(jvp(lm_head_ce))``). ``jax.profiler.ProfileData`` does not expose
the ``/host:metadata`` plane that maps a traced instruction to its
``op_name``; xprof's ``hlo_stats`` tool does (column ``tf_op_name``), with
each instruction's self time summed over its occurrences and over the chips.

``hlo_stats`` covers the whole trace, not the ``bench:window`` clip; both
runners start and stop the profiler at the window's edges, so the shares are
of the trace. A fusion carries the ``op_name`` of one of the ops fused into
it, so attribution blurs at scope borders: the ``UNSCOPED`` entry is what
could be given to no scope of ours, and every reader prints that share.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import re
import tempfile
from typing import Dict, List, Tuple

from benchmarks.lib import harness, spans, xplane

# every scope and kernel name the program opens (PERF.md, section 3), plus
# flax's own ``layers`` (the trainer's layer scan)
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "paged_attn", "rms_norm", "layer_norm",
           "quantize_int8", "dequantize_int8", "sparse_attn_fwd", "sparse_attn_bwd_dq",
           "sparse_attn_bwd_dkv")
SCOPES = ("embed", "layers", "layer", "kv_write", "pool_scan", "lm_head", "sample",
          "lm_head_ce", "optimizer") + KERNELS
UNSCOPED = "(no scope)"
_WRAPPED = re.compile(r"^(?:[a-z_]+\()+(.*?)\)+$")  # transpose(jvp(x)) -> x
_COLLECTIVE = re.compile(r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")


@dataclasses.dataclass(frozen=True)
class Instruction:
    program: str       # the jitted program's name, as xplane.module_name gives it
    name: str          # fusion.3
    category: str      # xprof's: "custom-call", "data formatting", "loop fusion" ...
    text: str          # the whole HLO instruction
    op_name: str       # jit(chain)/while/body/pool_scan/...
    seconds: float     # self time, summed over occurrences and chips
    count: int


def innermost_scope(op_name: str) -> str:
    """The last path component of ``op_name`` that is a scope of ours, its
    autodiff wrappers taken off; ``UNSCOPED`` if there is none."""
    for part in reversed(op_name.rstrip(":").split("/")):
        m = _WRAPPED.match(part)
        if (m.group(1) if m else part) in SCOPES:
            return m.group(1) if m else part
    return UNSCOPED


def _hlo_stats(path: str) -> List[dict]:
    from xprof.convert import raw_to_tool_data

    # xprof keeps a cache beside the file it is given: give it a link in a
    # directory of its own, so nothing appears beside the trace
    with tempfile.TemporaryDirectory() as tmp:
        link = os.path.join(tmp, os.path.basename(path))
        os.symlink(os.path.abspath(path), link)
        data, _ = raw_to_tool_data.xspace_to_tool_data([link], "hlo_stats", {})
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    return [dict(zip(cols, (c["v"] for c in row["c"]))) for row in table["rows"]]


def _programs(path: str) -> Dict[str, str]:
    """``program_id -> name`` from the ``XLA Modules`` events, ``jit_chain(123)``."""
    out = {}
    for plane in spans.profile(path).planes:
        for line in plane.lines:
            if plane.name.startswith("/device:TPU:") and line.name == xplane.MODULES_LINE:
                for ev in line.events:
                    out[ev.name.rpartition("(")[2].rstrip(")")] = xplane.module_name(ev.name)
    return out


@functools.lru_cache(maxsize=2)
def instructions(path: str) -> Tuple[Instruction, ...]:
    programs = _programs(path)
    return tuple(Instruction(programs.get(r["program_id"], ""), r["hlo_op_name"], r["category"],
                             r["hlo_op_expression"], r["tf_op_name"].rstrip(":"),
                             1e-6 * float(r["total_self_time"]), int(r["occurrences"]))
                 for r in _hlo_stats(path))


def scope_seconds(path: str, n_devices: int = 1) -> Dict[str, float]:
    """``{scope: device seconds}`` of the trace, mean over the chips, by the
    innermost scope of ours in each instruction's ``op_name``; what falls
    under none of them is under ``UNSCOPED``."""
    by_scope = {UNSCOPED: 0.0}
    for ins in instructions(path):
        scope = innermost_scope(ins.op_name)
        by_scope[scope] = by_scope.get(scope, 0.0) + ins.seconds / n_devices
    return by_scope


def seconds_under(run: dict, trace, *names: str) -> float:
    """Device seconds under the named scopes in the traced run being read
    (0.0 without its file or without the scopes), with the scope table
    printed on the way."""
    path = spans.trace_file(run)
    by_scope = report(path, trace.n_devices) if path else {}
    return sum(by_scope.get(name, 0.0) for name in names)


@functools.lru_cache(maxsize=2)
def report(path: str, n_devices: int = 1) -> Dict[str, float]:
    """``scope_seconds``, printed once per trace: a ``scope=`` line each, the
    share that no scope holds among them with its five largest instructions,
    and (across chips) the collectives' seconds by ``op_name``."""
    by_scope = scope_seconds(path, n_devices)
    total = sum(by_scope.values())
    for scope, seconds in sorted(by_scope.items(), key=lambda kv: -kv[1]):
        harness.say(scope=scope.replace(" ", "_"), device_s=seconds,
                    share_of_trace=seconds / total if total else 0.0)
    unscoped = [i for i in instructions(path) if innermost_scope(i.op_name) == UNSCOPED]
    for ins in sorted(unscoped, key=lambda i: -i.seconds)[:5]:
        harness.say(largest_without_scope=ins.name, program=ins.program, device_s=ins.seconds / n_devices,
                    op_name=(ins.op_name or "(no op_name)").replace(" ", "_"))
    if n_devices > 1:
        by_op: Dict[Tuple[str, str], float] = {}
        for ins in instructions(path):
            kind = _COLLECTIVE.search(ins.category) or _COLLECTIVE.search(ins.name)
            if kind:
                key = (kind.group(1), ins.op_name or "(no op_name)")
                by_op[key] = by_op.get(key, 0.0) + ins.seconds / n_devices
        for (kind, op_name), seconds in sorted(by_op.items(), key=lambda kv: -kv[1])[:16]:
            harness.say(collective=kind, op_name=op_name.replace(" ", "_"), device_s=seconds)
    return by_scope
