"""Compiled-program registry: what did XLA actually build for this process?

Host-side observability (spans, SLO metrics, flight records) watches the
*dispatch* of programs; this module watches the *programs themselves*. Every
jitted callable the engines build — train/eval/grad/apply/offload steps, the
v2 prefill/decode-chain programs — is captured once per
compile at the same wrap point the recompile detector already owns, and per
program the registry records:

  - compile wall time (the call that paid the compile) and capture overhead
  - ``cost_analysis()`` flops / bytes accessed — exact for the program run
  - ``memory_analysis()`` argument/output/temp/alias bytes and the derived
    peak HBM (argument + output − alias + temp: XLA's own live-set bound)
  - a donation/aliasing summary (aliased bytes + input→output alias pairs)
  - the collective ops in the compiled HLO text: op kind, tensor bytes,
    replica groups — the measured per-program comm volume
  - an HLO fingerprint (content hash + instruction count) so a recompile
    report can say *what grew*, not just which argument shape changed

Everything lands in the shared ``MetricsRegistry`` as ``program/*`` gauges
and ``compile/*`` counters labelled ``{program="<label>"}``, rides the
Prometheus exposition and Perfetto counter tracks for free, and feeds the
HBM calibration loop: engines register their pre-flight ``utils/hbm.py``
estimate and every captured program's XLA peak is reconciled against it
(``hbm/estimate_ratio`` — see :func:`deepspeed_tpu.utils.hbm.record_calibration`).

Capture cost, honestly: JAX does not expose the executable its dispatch
cache just built, so capture goes through the AOT path
(``fn.lower(args).compile()``). Tracing/lowering are cache hits from the
dispatch compile; the backend compile is partially cached by XLA's in-memory
caches (measured ~0.4x of a cold compile on CPU). This is paid ONCE per
compile event — exactly when the dispatch path is already paying a full
compile — never per step, and the ``compile/capture_ms`` gauge reports it.
Disabled (the default when telemetry is off), nothing is allocated, wrapped
callables fall straight through, and the dispatched program is byte-identical.
"""

from __future__ import annotations

import hashlib
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from deepspeed_tpu.utils.logging import logger

# HLO opcodes that move tensors across participants. ``-start`` variants are
# counted (async collectives are captured at issue); ``-done`` halves are not
# (same transfer, second instruction).
_COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
    "collective-broadcast",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_RG_RE = re.compile(r"replica_groups=(\{\{[0-9,{} ]*\}\}|\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)")


def _shape_bytes(segment: str) -> int:
    """Total bytes of every shape literal (``f32[8,128]``) in ``segment``."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(segment):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def extract_collectives(hlo_text: str) -> List[Dict[str, Any]]:
    """Collective ops in compiled HLO text: kind, result tensor bytes,
    replica groups. Pure text analysis — works on any backend's ``as_text()``
    (post-optimization HLO, so fused/rewritten collectives are what is
    actually on the wire)."""
    out: List[Dict[str, Any]] = []
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if not (line.startswith("%") or line.startswith("ROOT ")):
            continue
        eq = line.find(" = ")
        if eq < 0:
            continue
        rest = line[eq + 3:]
        for kind in _COLLECTIVE_KINDS:
            m = re.search(r"\b" + re.escape(kind) + r"(-start)?\(", rest)
            if m is None:
                continue
            if re.search(r"\b" + re.escape(kind) + r"-done\(", rest):
                break  # the -start half already carried the bytes
            rg = _RG_RE.search(line)
            out.append({
                "kind": kind,
                # result shapes sit between '=' and the opcode; for tuple-
                # shaped fused collectives every element contributes
                "bytes": _shape_bytes(rest[: m.start()]),
                "replica_groups": rg.group(1) if rg else "",
            })
            break
    return out


# custom-call targets that are HAND-WRITTEN kernels (vs partitioning /
# placement annotations GSPMD sprinkles through every sharded program)
_KERNEL_TARGETS = ("tpu_custom_call", "mosaic", "triton")


def extract_custom_kernels(hlo_text: str) -> List[Dict[str, Any]]:
    """Custom-call targets in compiled HLO text: ``[{target, count,
    kernel}]`` where ``kernel`` marks hand-written kernels (Pallas/Mosaic/
    Triton) as opposed to GSPMD/placement annotations. This is how a FUSED
    collective hop reads in a program inventory — e.g. one
    ``tpu_custom_call`` per hop where the ppermute path showed separate
    quantize custom calls (or fused HLO) plus a ``collective-permute``;
    see docs/telemetry.md."""
    counts: Dict[str, int] = {}
    for m in re.finditer(r'custom_call_target="([^"]+)"', hlo_text):
        target = m.group(1)
        counts[target] = counts.get(target, 0) + 1
    return [{"target": t, "count": c,
             "kernel": any(k in t.lower() for k in _KERNEL_TARGETS)}
            for t, c in sorted(counts.items())]


def hlo_fingerprint(hlo_text: str) -> Tuple[str, int]:
    """(content hash, instruction count) of an HLO module's text — the
    identity a recompile report diffs to say what grew."""
    digest = hashlib.sha256(hlo_text.encode("utf-8", "replace")).hexdigest()[:12]
    n_instr = sum(1 for ln in hlo_text.splitlines() if " = " in ln)
    return digest, n_instr


@dataclass
class ProgramRecord:
    """One captured compile of one labelled program."""

    label: str
    index: int                       # capture sequence number (process-wide)
    fingerprint: str = ""
    instruction_count: int = 0
    compile_wall_s: Optional[float] = None   # the call that paid the compile
    capture_s: float = 0.0                   # cost of this capture itself
    flops: float = 0.0
    bytes_accessed: float = 0.0
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    alias_bytes: int = 0                     # donated/aliased input bytes
    alias_pairs: int = 0                     # input→output alias entries
    generated_code_bytes: int = 0
    peak_hbm_bytes: int = 0                  # argument + output − alias + temp
    collectives: List[Dict[str, Any]] = field(default_factory=list)
    custom_kernels: List[Dict[str, Any]] = field(default_factory=list)
    hbm_estimate_bytes: Optional[int] = None
    hbm_estimate_ratio: Optional[float] = None
    @property
    def collective_bytes(self) -> int:
        return sum(c["bytes"] for c in self.collectives)

    @property
    def custom_kernel_count(self) -> int:
        # hand-written kernels only — GSPMD/annotation custom calls are in
        # the list (kernel=False) but must not inflate the kernel census
        return sum(k["count"] for k in self.custom_kernels if k.get("kernel"))

    def as_dict(self) -> Dict[str, Any]:
        return {
            "label": self.label,
            "index": self.index,
            "fingerprint": self.fingerprint,
            "instruction_count": self.instruction_count,
            "compile_wall_ms": (round(self.compile_wall_s * 1e3, 3)
                                if self.compile_wall_s is not None else None),
            "capture_ms": round(self.capture_s * 1e3, 3),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "alias_bytes": self.alias_bytes,
            "alias_pairs": self.alias_pairs,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "collective_count": len(self.collectives),
            "collective_bytes": self.collective_bytes,
            "collectives": list(self.collectives),
            "custom_kernel_count": self.custom_kernel_count,
            "custom_kernels": list(self.custom_kernels),
            "hbm_estimate_bytes": self.hbm_estimate_bytes,
            "hbm_estimate_ratio": self.hbm_estimate_ratio,
        }


class _Watch:
    """Minimal cache-growth watcher for jitted callables outside the
    recompile detector's reach (telemetry-without-diagnostics engines, the
    v2 step programs). Same probe discipline as the detector's wrapper: two
    ``_cache_size()`` reads per call, capture only when a compile actually
    happened, attribute access forwards to the wrapped function."""

    __slots__ = ("_fn", "_label", "_registry", "_hbm_scope", "_program_record")

    def __init__(self, fn: Callable, label: str, registry: "ProgramRegistry",
                 hbm_scope: Optional[str]):
        self._fn = fn
        self._label = label
        self._registry = registry
        self._hbm_scope = hbm_scope
        # freshest ProgramRecord captured for THIS watcher's program (the
        # flops profiler reads it instead of AOT-compiling a second copy)
        self._program_record = None

    def __call__(self, *args, **kwargs):
        reg = self._registry
        if not reg.enabled:
            return self._fn(*args, **kwargs)
        before = self._cache_size()
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        if before is not None:
            after = self._cache_size()
            if after is not None and after > before:
                record = reg.on_compile(self._label, self._fn, args, kwargs,
                                        wall_s=time.perf_counter() - t0,
                                        hbm_scope=self._hbm_scope)
                if record is not None:
                    self._program_record = record
        return out

    def _cache_size(self) -> Optional[int]:
        try:
            return self._fn._cache_size()
        except Exception:  # noqa: BLE001 - non-pjit callables
            return None

    def __getattr__(self, name):
        return getattr(self._fn, name)


def unwrap_program_watch(fn: Callable) -> Callable:
    """The underlying jitted callable of a registry watcher (identity for
    anything else)."""
    return fn._fn if isinstance(fn, _Watch) else fn


class ProgramRegistry:
    """Process-wide inventory of captured compiled programs.

    ``enabled`` follows the process-global tracer by default (telemetry on ⇒
    programs on) and can be pinned either way with :meth:`configure` — the
    ``telemetry.programs`` config knob. All mutation is lock-guarded; capture
    never raises into the training/serving loop (a failed capture logs at
    debug and returns None).
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._explicit_enabled: Optional[bool] = None
        self._records: Dict[str, List[ProgramRecord]] = {}
        self._hbm_estimates: Dict[str, int] = {}
        self._seq = 0
        self.capture_failures = 0

    # ------------------------------------------------------------- config
    @property
    def enabled(self) -> bool:
        if self._explicit_enabled is not None:
            return self._explicit_enabled
        from deepspeed_tpu.telemetry.tracer import get_tracer

        return get_tracer().enabled

    def configure(self, enabled: Optional[bool] = None) -> "ProgramRegistry":
        """Pin enablement (True/False) or restore follow-the-tracer (None)."""
        self._explicit_enabled = enabled
        return self

    def reset(self) -> None:
        with self._lock:
            self._records = {}
            self._hbm_estimates = {}
            self._seq = 0
            self.capture_failures = 0

    # ------------------------------------------------------------ queries
    def labels(self) -> List[str]:
        with self._lock:
            return list(self._records)

    def latest(self, label: str) -> Optional[ProgramRecord]:
        with self._lock:
            hist = self._records.get(label)
            return hist[-1] if hist else None

    def history(self, label: str) -> List[ProgramRecord]:
        with self._lock:
            return list(self._records.get(label, ()))

    def records(self) -> List[ProgramRecord]:
        """Every capture, in capture order."""
        with self._lock:
            out = [r for hist in self._records.values() for r in hist]
        return sorted(out, key=lambda r: r.index)

    # ------------------------------------------------------- hbm estimates
    def set_hbm_estimate(self, estimate_bytes: int, scope: str = "train") -> None:
        """Register a pre-flight ``utils/hbm.py`` estimate for calibration.

        ``scope`` names which programs the estimate covers ("train" for the
        runtime engine's step programs, "serving" for the v2 engine's) — the
        wrap point tags each program with its scope. Last writer wins per
        scope (one live engine per scope is the norm; multi-engine tests
        overwrite, which is the honest reading of "the current engine").
        """
        if estimate_bytes and estimate_bytes > 0:
            with self._lock:
                self._hbm_estimates[scope] = int(estimate_bytes)

    def hbm_estimate(self, scope: str) -> Optional[int]:
        with self._lock:
            return self._hbm_estimates.get(scope)

    # ------------------------------------------------------------- wrapping
    def wrap(self, fn: Callable, label: str,
             hbm_scope: Optional[str] = None) -> Callable:
        """Cache-growth watcher for a jitted callable (engines with the
        recompile detector installed get capture through the detector's
        wrapper instead — one probe, not two)."""
        if fn is None:
            return fn
        return _Watch(fn, label, self, hbm_scope)

    # -------------------------------------------------------------- capture
    def on_compile(self, label: str, fn: Callable, args: Tuple, kwargs: Dict,
                   wall_s: Optional[float] = None,
                   hbm_scope: Optional[str] = None) -> Optional[ProgramRecord]:
        """Capture the program ``fn`` just compiled for ``(args, kwargs)``.

        Called from the wrap points right after a dispatch compile was
        detected; must never raise. Lowering only needs avals, so donated
        (already-deleted) argument buffers are fine.
        """
        if not self.enabled:
            return None
        t0 = time.perf_counter()
        try:
            compiled = fn.lower(*args, **kwargs).compile()
            record = self._record_compiled(label, compiled, wall_s, hbm_scope, t0)
        except Exception as e:  # noqa: BLE001 — observability must not break the step
            self.capture_failures += 1
            logger.debug(f"program capture failed for {label!r}: {e}")
            return None
        return record

    def capture(self, fn: Callable, *args, label: Optional[str] = None,
                hbm_scope: Optional[str] = None, **kwargs) -> Optional[ProgramRecord]:
        """Explicit capture of a jittable/jitted ``fn`` (the
        ``flops_profiler.compiled_cost`` entry point). Reuses an existing
        record when one was already captured for this label's current
        program fingerprint-equivalent signature; otherwise lowers+compiles
        once (XLA's in-memory caches absorb repeats) and records it.
        Works even when the registry is disabled — explicit calls are their
        own opt-in — but publishes metrics only when telemetry is enabled.
        """
        import jax

        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        label = label or f"capture:{getattr(fn, '__name__', 'fn')}"
        t0 = time.perf_counter()
        try:
            compiled = jitted.lower(*args, **kwargs).compile()
            return self._record_compiled(label, compiled, None, hbm_scope, t0,
                                         dedupe=True)
        except Exception as e:  # noqa: BLE001
            self.capture_failures += 1
            logger.debug(f"program capture failed for {label!r}: {e}")
            return None

    # ------------------------------------------------------------ internals
    def _record_compiled(self, label: str, compiled, wall_s: Optional[float],
                         hbm_scope: Optional[str], t0: float,
                         dedupe: bool = False) -> ProgramRecord:
        """``dedupe``: return the label's existing record when the program
        content is unchanged (explicit ``capture()`` calls may repeat per
        step — without this they would grow the inventory unboundedly; the
        wrap-point path never dedupes: each dispatch compile IS an event)."""
        costs = compiled.cost_analysis()
        if isinstance(costs, list):  # older jax returns [dict]
            costs = costs[0] if costs else {}
        costs = dict(costs or {})
        flops = float(costs.get("flops", 0.0))
        bytes_accessed = float(
            costs.get("bytes accessed", costs.get("bytes_accessed", 0.0)))

        arg_b = out_b = temp_b = alias_b = code_b = 0
        try:
            mem = compiled.memory_analysis()
        except Exception:  # noqa: BLE001 - not all backends implement it
            mem = None
        if mem is not None:
            arg_b = int(getattr(mem, "argument_size_in_bytes", 0))
            out_b = int(getattr(mem, "output_size_in_bytes", 0))
            temp_b = int(getattr(mem, "temp_size_in_bytes", 0))
            alias_b = int(getattr(mem, "alias_size_in_bytes", 0))
            code_b = int(getattr(mem, "generated_code_size_in_bytes", 0))
        peak = max(arg_b + out_b - alias_b + temp_b, 0)

        fingerprint, n_instr, colls, kernels, alias_pairs = "", 0, [], [], 0
        try:
            text = compiled.as_text()
            fingerprint, n_instr = hlo_fingerprint(text)
            colls = extract_collectives(text)
            kernels = extract_custom_kernels(text)
            header = text.split("\n", 1)[0]
            if "input_output_alias=" in header:
                alias_pairs = header.count(": (")
        except Exception as e:  # noqa: BLE001 - text dump is best-effort
            logger.debug(f"HLO text analysis unavailable for {label!r}: {e}")

        if dedupe and fingerprint:
            prev = self.latest(label)
            if prev is not None and prev.fingerprint == fingerprint:
                return prev

        with self._lock:
            index = self._seq
            self._seq += 1
        record = ProgramRecord(
            label=label, index=index,
            fingerprint=fingerprint, instruction_count=n_instr,
            compile_wall_s=wall_s, capture_s=time.perf_counter() - t0,
            flops=flops, bytes_accessed=bytes_accessed,
            argument_bytes=arg_b, output_bytes=out_b, temp_bytes=temp_b,
            alias_bytes=alias_b, alias_pairs=alias_pairs,
            generated_code_bytes=code_b, peak_hbm_bytes=peak,
            collectives=colls, custom_kernels=kernels,
        )

        estimate = self.hbm_estimate(hbm_scope) if hbm_scope else None
        if estimate:
            from deepspeed_tpu.utils.hbm import record_calibration

            record.hbm_estimate_bytes = estimate
            record.hbm_estimate_ratio = record_calibration(
                estimate, peak, what=label)

        with self._lock:
            self._records.setdefault(label, []).append(record)
        self._publish(record)
        return record

    def _publish(self, r: ProgramRecord) -> None:
        from deepspeed_tpu.telemetry.tracer import get_tracer

        tracer = get_tracer()
        if not tracer.enabled:
            return
        reg = tracer.registry
        for name, value in (
            ("program/flops", r.flops),
            ("program/bytes_accessed", r.bytes_accessed),
            ("program/peak_hbm_bytes", r.peak_hbm_bytes),
            ("program/argument_bytes", r.argument_bytes),
            ("program/output_bytes", r.output_bytes),
            ("program/temp_bytes", r.temp_bytes),
            ("program/alias_bytes", r.alias_bytes),
            ("program/instruction_count", r.instruction_count),
            ("program/collective_count", len(r.collectives)),
            ("program/collective_bytes", r.collective_bytes),
            ("program/custom_kernel_count", r.custom_kernel_count),
        ):
            reg.gauge(name, program=r.label).set(float(value))
        reg.counter("compile/count", program=r.label).add(1.0)
        if r.compile_wall_s is not None:
            reg.gauge("compile/last_wall_ms", program=r.label).set(
                r.compile_wall_s * 1e3)
            reg.counter("compile/wall_ms_total", program=r.label).add(
                r.compile_wall_s * 1e3)
            # Perfetto counter track: compile activity over the run
            tracer.sample_counter("compile/wall_ms", r.compile_wall_s * 1e3)
        tracer.sample_counter("compile/capture_ms", r.capture_s * 1e3)
        tracer.sample_counter("program/peak_hbm_bytes", float(r.peak_hbm_bytes))
        tracer.instant(
            f"program:{r.label}", cat="programs",
            fingerprint=r.fingerprint, instructions=r.instruction_count,
            flops=r.flops, peak_hbm_bytes=r.peak_hbm_bytes,
            collectives=len(r.collectives),
        )


_registry = ProgramRegistry()


def get_program_registry() -> ProgramRegistry:
    return _registry


def configure(enabled: Optional[bool] = None) -> ProgramRegistry:
    """Configure the process-global program registry (the
    ``telemetry.programs`` config knob routes here)."""
    return _registry.configure(enabled=enabled)
