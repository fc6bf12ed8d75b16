"""What the readers of the sub-layer names share (since PR 37).

Inside a layer the program names each piece by the parameter key it reads:
flax writes its modules' names into an instruction's ``op_name`` in training
(``.../layers/attn/wq/dot_general``), and the serving path opens the same
names where it indexes the same tree (``models/transformer.py::reading``:
``.../layer/attn/wq/...``). The train step besides names the layer scan
(``layer_scan``: what reads it and no ``layers`` is the scan's own stacking
and slicing), the micro-batch accumulator (``grad_accum``) and the norm of the
gradients (``grad_norm``). ``lib/scopes.py``'s list is closed and attributes
by the innermost of ITS names, so these readers match the path components of
an ``op_name`` themselves, autodiff's wrappers taken off.

What cannot be split: a fusion carries the ``op_name`` of ONE of its ops, so
a bias add, an activation or a residual add fused into a weight's product is
counted with that weight or takes the product with it to the enclosing name;
``named_op=`` lines say which name each of the ten largest instructions got.
In a trace of a program without these names every function here finds
nothing and the readers return None.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, List, Optional, Tuple

from benchmarks.lib import eva, harness, kernels, routed, scopes, spans

# a layer's weights that meet a token in a matrix product, by parameter key
WEIGHTS = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down", "wq_a", "wq_b", "wkv_a", "wkv_b")
LAYER_SCAN, LAYERS = "layer_scan", "layers"
GRAD_PARTS = ("grad_accum", "grad_norm")
SUBLAYERS = WEIGHTS + ("attn", "mlp", "attn_norm", "mlp_norm", "q_norm", "kv_norm", "final_norm",
                       "rope", LAYER_SCAN) + GRAD_PARTS
_SUBLAYERS = frozenset(SUBLAYERS)
# every name the program writes: the closed list's, the routed, latent and EVA cells', these
PROGRAM_NAMES = frozenset(scopes.SCOPES + (routed.MOE_SCOPE, routed.MLA_KERNEL, "mla", eva.EVA_SCOPE,
                                           "eva_prefill", "eva_close") + routed.MOE_PARTS + SUBLAYERS)
UNNAMED = "(no name)"
_OPERAND = re.compile(r"([a-z]+)([0-9]*)\[([0-9,]*)\](?:\{[^}]*\})? %")


def components(op_name: str) -> List[str]:
    """The path components of ``op_name``, autodiff's wrappers taken off."""
    out = []
    for part in op_name.rstrip(":").split("/"):
        m = scopes._WRAPPED.match(part)  # transpose(jvp(x)) -> x
        out.append(m.group(1) if m else part)
    return out


def path(op_name: str) -> Tuple[str, ...]:
    """The components of ``op_name`` that are names of the program's, outermost first."""
    return tuple(c for c in components(op_name) if c in PROGRAM_NAMES)


def label(op_name: str) -> str:
    """The innermost two of the program's names in ``op_name``: ``mlp/w_down``."""
    return "/".join(path(op_name)[-2:]) or UNNAMED


def weight_of(op_name: str) -> Optional[str]:
    """The weight a dense layer's instruction is named by (the innermost, if
    several), or None: no weight's name, or a routed layer's (``moe*``), whose
    experts and shared expert have readers of their own."""
    parts = components(op_name)
    if any(c.startswith(routed.MOE_SCOPE) for c in parts):
        return None
    return next((c for c in reversed(parts) if c in WEIGHTS), None)


def is_scan_stacking(op_name: str) -> bool:
    """Under the layer scan and in no layer: the scan's own work."""
    parts = components(op_name)
    return LAYER_SCAN in parts and LAYERS not in parts


def is_grad_part(op_name: str) -> bool:
    return any(c in GRAD_PARTS for c in components(op_name))


def is_scan_slicing(op_name: str) -> bool:
    """The serving layer scan's own work (``pool_scan`` and no ``layer``): the
    pool rides in the carry, so this is the slices of the stacked parameters."""
    parts = components(op_name)
    return "pool_scan" in parts and "layer" not in parts


WEIGHT_SHARE = 8  # an operand within this factor of the largest is a weight too


def operands(text: str, stacked: int = 0) -> List[Tuple[str, int]]:
    """``[(shape, bytes)]`` of an HLO instruction's operands, from its own
    text (``bf16[8192,2048]{1,0:T(8,128)(2,1)} %x``); an operand of three or
    more dimensions that leads with ``stacked`` is a scan's stacked parameter
    that the instruction slices itself, and counts as one layer's slice."""
    out = []
    for kind, bits, dims in _OPERAND.findall(text.partition(" = ")[2]):
        shape = [int(d) for d in dims.split(",") if d]
        if stacked and len(shape) >= 3 and shape[0] == stacked:
            shape = shape[1:]
        size = (int(bits) // 8 if bits else 1) or 1
        for d in shape:
            size *= d
        out.append((f"{kind}{bits}[{dims}]", size))
    return out


def weight_bytes(text: str, stacked: int = 0) -> int:
    """Bytes of the weights an instruction reads: its largest operand and
    every operand within ``WEIGHT_SHARE`` of it (two products fused into one
    instruction read two weights; an activation of a decode step is a
    hundredth of either). A heuristic, so the reader that uses it holds what
    it found a layer against the architecture file's count and leaves its
    metric out where they differ."""
    sizes = [size for _, size in operands(text, stacked)]
    return sum(size for size in sizes if size * WEIGHT_SHARE >= max(sizes)) if sizes else 0


def layer_matmul_params(arch, cfg: dict) -> int:
    """``matmul_params`` less the output head: the layers' share."""
    return arch.matmul_params(cfg) - cfg["hidden_size"] * cfg["vocab_size"]


def is_serving(run: dict) -> bool:
    return run["workload"].get("kind") == "serve"


def instructions(run: dict, trace=None) -> Tuple[scopes.Instruction, ...]:
    """The instructions of the traced run being read: a serving cell's two
    programs, a training cell's all; none without a trace file. Given the
    reduced ``trace``, the tables are printed on the way, once a trace."""
    file = spans.trace_file(run)
    if file is None:
        return ()
    found = scopes.instructions(file)
    if is_serving(run):
        found = tuple(i for i in found if i.program in routed.SERVING_PROGRAMS)
    if trace is not None and found:
        report(file, trace.n_devices, trace.busy_s, is_serving(run))
    return found


def seconds_of(run: dict, trace, pick) -> float:
    """Device seconds, mean over the chips, of the run's instructions whose
    ``op_name`` ``pick`` takes."""
    return sum(i.seconds for i in instructions(run, trace) if pick(i.op_name)) / trace.n_devices


def has_names(run: dict) -> bool:
    """Whether the program that was traced writes the sub-layer names at all
    (the parent of PR 37 does not: its readers' metrics are left out)."""
    return any(not _SUBLAYERS.isdisjoint(components(i.op_name)) for i in instructions(run))


def weight_traffic(run: dict, trace, stacked: int) -> Dict[str, Tuple[float, float, int]]:
    """``{weight: (seconds, bytes, layer-steps)}`` of the decode-chain program:
    the seconds of every instruction a weight names, and the bytes of the
    weights its largest instruction reads (``weight_bytes``; one layer's
    slice) times the times the device ran that instruction."""
    by_weight: Dict[str, List[scopes.Instruction]] = {}
    for ins in instructions(run, trace):
        weight = weight_of(ins.op_name)
        if weight and ins.program == kernels.CHAIN_PROGRAM:
            by_weight.setdefault(weight, []).append(ins)
    out = {}
    for weight, found in by_weight.items():
        size, count = max((weight_bytes(i.text, stacked), i.count) for i in found)
        out[weight] = (sum(i.seconds for i in found), float(size) * count, count)
    return out


@functools.lru_cache(maxsize=2)
def report(file: str, n_devices: int, busy_s: float, serving: bool) -> None:
    """Printed once a trace: a ``sublayer=`` line for each pair of innermost
    names (the twenty largest), and a ``named_op=`` line for each of the ten
    largest instructions, so that a ledger's ``breakdown`` reads against names."""
    found = [i for i in scopes.instructions(file) if not serving or i.program in routed.SERVING_PROGRAMS]
    by_label: Dict[str, float] = {}
    for ins in found:
        by_label[label(ins.op_name)] = by_label.get(label(ins.op_name), 0.0) + ins.seconds / n_devices
    for name, seconds in sorted(by_label.items(), key=lambda kv: -kv[1])[:20]:
        harness.say(sublayer=name.replace(" ", "_"), device_s=seconds, share=seconds / busy_s if busy_s else 0.0)
    for ins in sorted(found, key=lambda i: -i.seconds)[:10]:
        harness.say(named_op=ins.name, program=ins.program, path=label(ins.op_name).replace(" ", "_"),
                    device_s=ins.seconds / n_devices, count=ins.count,
                    operands=",".join(shape for shape, _ in operands(ins.text)) or "()",
                    op_name=(ins.op_name or "(no op_name)").replace(" ", "_"))
