"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else. What a v5e trace
holds, as looked at by hand on ``tests/benchmarks/data/*.xplane.pb``:

* one plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one
  event per run of a jitted program, named ``jit_<fn>(<id>)``), ``XLA Ops``
  (the TensorCore's instruction stream; an event's name is the whole HLO
  instruction, ``%fusion.3 = bf16[..] fusion(..)``; a ``while``, ``conditional``
  or ``call`` spans the instructions of its body, which are events of their
  own, so those three are containers and are left out of every sum here) and
  ``Async XLA Ops`` (one event per asynchronous operation, lasting from its
  ``-start`` to its ``-done``: copies, slices and, across chips, collectives);
* a Pallas kernel is a ``custom-call`` whose text has
  ``custom_call_target="tpu_custom_call"``. Since PR 25 the program gives
  every ``pallas_call`` a ``name=``, which is the instruction's name
  (``%paged_attn.12``), and ``lib/kernels.py`` picks a kernel by it; a kernel
  without one is ``%custom-call.N`` and can only be told by its operand shapes;
* ``/host:CPU`` holds the host threads; ``jax.profiler.TraceAnnotation`` spans
  made by the benchmark land there under their own names. Host and device
  clocks of one trace differ by about a millisecond.

All times leave here in seconds.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench:window"
SPAN_PREFIX = "bench:"
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
CONTAINERS = ("while", "conditional", "call")
MIN_LABELLED_GAP_S = 20e-6  # shorter gaps are the device's own, not the host's

_OPCODE = re.compile(r"[\}\]\)] ([a-z][a-z0-9_\-]*)\(")
_COLLECTIVE = re.compile(r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


@functools.lru_cache(maxsize=8192)
def split_instruction(text: str) -> Tuple[str, str, str]:
    """``(name, opcode, first result shape)`` of one HLO instruction text."""
    name, _, rest = text.partition(" = ")
    if not rest:
        return text.lstrip("%"), "", ""
    m = _OPCODE.search(rest)
    shape = _SHAPE.search(rest)
    return name.lstrip("%"), m.group(1) if m else "", shape.group(0) if shape else ""


def collective_kind(text: str) -> Optional[str]:
    """all-gather | all-reduce | ... when the instruction is a collective (by
    its opcode, or by its name where the compiler wrapped it in async-start)."""
    name, opcode, _ = split_instruction(text)
    m = _COLLECTIVE.search(opcode) or _COLLECTIVE.search(name)
    return m.group(1) if m else None


def module_name(event_name: str) -> str:
    """``jit_train_step(123)`` -> ``train_step``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge ``[n, 2]`` start/end rows into disjoint sorted rows."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > ends[:-1]])
    starts = iv[new, 0]
    last = np.concatenate([np.nonzero(new)[0][1:] - 1, [len(iv) - 1]])
    return np.stack([starts, ends[last]], axis=1)


def length(intervals: np.ndarray) -> float:
    return float((intervals[:, 1] - intervals[:, 0]).sum()) if len(intervals) else 0.0


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(intervals) == 0:
        return intervals
    iv = np.clip(intervals, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The part of the disjoint sorted rows ``a`` that no row of ``b`` covers."""
    out = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j, 1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k, 0] < e:
            if b[k, 0] > cur:
                out.append((cur, b[k, 0]))
            cur = max(cur, b[k, 1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return np.asarray(out, dtype=float).reshape(-1, 2)


@dataclasses.dataclass
class OpTotal:
    module: str      # the jitted program the instruction ran in ("" if none)
    text: str        # the whole HLO instruction, as the trace names it
    count: int = 0
    seconds: float = 0.0

    @property
    def label(self) -> str:
        name, opcode, shape = split_instruction(self.text)
        kind = "pallas" if PALLAS_TARGET in self.text else opcode
        return " ".join(x for x in (name, "" if kind == name else kind, shape) if x)


@dataclasses.dataclass
class Reduced:
    n_devices: int
    window_s: float
    busy_s: float                          # mean over the devices
    modules: Dict[str, List[float]]        # program -> seconds of each run, first device
    ops: List[OpTotal]                     # summed over the devices
    collective_s: float                    # mean over the devices
    collective_exposed_s: float
    collective_by_kind: Dict[str, float]
    idle_gaps: List[Tuple[str, float]]     # what the host was doing -> idle seconds, first device

    def op_seconds(self, pick: Callable[[OpTotal], bool]) -> float:
        """Device seconds, mean over the devices, of the instructions picked."""
        return sum(o.seconds for o in self.ops if pick(o)) / max(self.n_devices, 1)

    def op_count(self, pick: Callable[[OpTotal], bool]) -> int:
        return sum(o.count for o in self.ops if pick(o))

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        by_label: Dict[str, float] = {}
        for o in self.ops:
            by_label[o.label] = by_label.get(o.label, 0.0) + o.seconds / max(self.n_devices, 1)
        return sorted(by_label.items(), key=lambda kv: -kv[1])[:n]


def _events(line) -> Tuple[List[str], np.ndarray]:
    names, rows = [], []
    for ev in line.events:
        names.append(ev.name)
        rows.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return names, np.asarray(rows, dtype=float).reshape(-1, 2) * 1e-9


def _host_spans(planes) -> List[Tuple[str, float, float]]:
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9))
    return sorted(spans, key=lambda s: s[1])


def _label_gaps(gaps: np.ndarray, spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle seconds by what the benchmark's host spans say the host was doing:
    inside a span, or between the span that ended last and the next to start."""
    spans = [s for s in spans if s[0] != WINDOW_SPAN]
    out: Dict[str, float] = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        inside = [n for n, a, b in spans if a <= mid < b]
        if inside:
            label = "in " + inside[-1][len(SPAN_PREFIX):]
        else:
            before = [n for n, a, b in spans if b <= mid]
            after = [n for n, a, b in spans if a > mid]
            label = "host, after %s before %s" % (
                before[-1][len(SPAN_PREFIX):] if before else "start",
                after[0][len(SPAN_PREFIX):] if after else "end")
        out[label] = out.get(label, 0.0) + float(e - s)
    return out


def reduce_trace(path: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), path)


def reduce_profile(profile, path: str = "<profile>") -> Reduced:
    planes = list(profile.planes)
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise ValueError(f"{path}: no /device:TPU:<n> plane, so no device operation was traced")
    spans = _host_spans(planes)
    window = next(((a, b) for n, a, b in spans if n == WINDOW_SPAN), None)

    per_device = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        op_names, op_iv = _events(lines[OPS_LINE]) if OPS_LINE in lines else ([], np.zeros((0, 2)))
        as_names, as_iv = _events(lines[ASYNC_LINE]) if ASYNC_LINE in lines else ([], np.zeros((0, 2)))
        mod_names, mod_iv = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else ([], np.zeros((0, 2)))
        per_device.append((op_names, op_iv, as_names, as_iv, mod_names, mod_iv))

    if window is None:
        # no host span marks the window: it is what the devices were seen doing
        starts = [d[1][:, 0].min() for d in per_device if len(d[1])]
        ends = [d[1][:, 1].max() for d in per_device if len(d[1])]
        if not starts:
            raise ValueError(f"{path}: no operation ran on a device")
        window = (min(starts), max(ends))
    lo, hi = window

    totals: Dict[Tuple[str, str], OpTotal] = {}
    busy, coll, exposed = [], [], []
    by_kind: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    gaps_by_label: Dict[str, float] = {}
    for d, (op_names, op_iv, as_names, as_iv, mod_names, mod_iv) in enumerate(per_device):
        inside = (op_iv[:, 1] > lo) & (op_iv[:, 0] < hi) if len(op_iv) else np.zeros(0, bool)
        # the program each instruction ran in: the last module run to start before it
        order = np.argsort(mod_iv[:, 0]) if len(mod_iv) else np.zeros(0, int)
        mod_starts = mod_iv[order, 0] if len(mod_iv) else np.zeros(0)
        owner = np.searchsorted(mod_starts, op_iv[:, 0], side="right") - 1 if len(op_iv) else []
        kinds: Dict[str, Tuple[Optional[str], bool]] = {}
        is_coll = np.zeros(len(op_names), bool)
        for i, text in enumerate(op_names):
            if not inside[i]:
                continue
            if text not in kinds:
                kinds[text] = (collective_kind(text), split_instruction(text)[1] in CONTAINERS)
            if kinds[text][1]:
                inside[i] = False  # a container: its body's instructions are counted
                continue
            is_coll[i] = kinds[text][0] is not None
            mod = ""
            if len(order) and owner[i] >= 0 and op_iv[i, 0] < mod_iv[order[owner[i]], 1]:
                mod = module_name(mod_names[order[owner[i]]])
            t = totals.setdefault((mod, text), OpTotal(mod, text))
            t.count += 1
            t.seconds += float(min(op_iv[i, 1], hi) - max(op_iv[i, 0], lo))
        ops_u = clip(union(op_iv[inside]), lo, hi) if len(op_iv) else op_iv
        busy.append(length(ops_u))
        compute_u = clip(union(op_iv[inside & ~is_coll]), lo, hi) if len(op_iv) else ops_u
        as_coll = np.asarray([collective_kind(n) is not None for n in as_names], bool)
        coll_iv = np.concatenate([op_iv[inside & is_coll] if len(op_iv) else np.zeros((0, 2)),
                                  as_iv[as_coll] if len(as_iv) else np.zeros((0, 2))])
        coll_u = clip(union(coll_iv), lo, hi)
        coll.append(length(coll_u))
        exposed.append(length(subtract(coll_u, compute_u)))
        for names, iv, mask in ((op_names, op_iv, inside & is_coll), (as_names, as_iv, as_coll)):
            for i in np.nonzero(mask)[0]:
                k = collective_kind(names[i])
                by_kind[k] = by_kind.get(k, 0.0) + float(
                    min(iv[i, 1], hi) - max(iv[i, 0], lo)) / len(per_device)
        if d == 0:
            for n, (s, e) in zip(mod_names, mod_iv):
                if e > lo and s < hi:
                    modules.setdefault(module_name(n), []).append(float(e - s))
            gaps = subtract(np.asarray([[lo, hi]]), ops_u)
            long = (gaps[:, 1] - gaps[:, 0]) >= MIN_LABELLED_GAP_S
            gaps_by_label = _label_gaps(gaps[long], spans)
            if (~long).any():
                gaps_by_label["between operations, under %g us each" % (
                    1e6 * MIN_LABELLED_GAP_S)] = length(gaps[~long])

    return Reduced(
        n_devices=len(per_device), window_s=float(hi - lo), busy_s=float(np.mean(busy)),
        modules=modules, ops=sorted(totals.values(), key=lambda o: -o.seconds),
        collective_s=float(np.mean(coll)), collective_exposed_s=float(np.mean(exposed)),
        collective_by_kind=by_kind,
        idle_gaps=sorted(gaps_by_label.items(), key=lambda kv: -kv[1])[:10])
