"""FLOPs profiler.

TPU-native analog of the reference flops profiler
(``profiling/flops_profiler/profiler.py:30 FlopsProfiler``): where the
reference patches ``torch.nn.functional`` and hooks every module to count
MACs, here the numbers come from the places XLA already knows them:

  - compiled-program cost analysis (``Compiled.cost_analysis()``: flops,
    bytes accessed, peak memory) — exact for the program XLA will run
  - jaxpr traversal for the per-op breakdown (dot_general / conv / einsum
    shapes → flops), the analog of the per-module table
  - wall-clock from timing real executions → achieved TFLOPS and MFU

Works on any jittable fn; ``FlopsProfiler`` wraps an engine's train step
(config section ``flops_profiler`` — reference ``profiling/config.py``).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from deepspeed_tpu.utils.logging import log_dist, logger

@dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float        # dense bf16 FLOP/s per chip
    hbm_bytes_per_s: float
    hbm_bytes: float


# THE peaks table: published per-chip figures (Google Cloud TPU documentation,
# the "TPU v4" / "TPU v5e" / "TPU v5p" / "TPU v6e" system-architecture pages),
# keyed by the ``device_kind`` jax reports. Every MFU, roofline share and
# attribution in the repo reads it; a kind that is not here is an error, not a
# default — add the row with its source.
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v4": DevicePeaks(275e12, 1228e9, 32e9),
    "TPU v5 lite": DevicePeaks(197e12, 819e9, 16e9),  # v5e, as the chip reports itself
    "TPU v5": DevicePeaks(459e12, 2765e9, 95e9),      # v5p
    "TPU v6 lite": DevicePeaks(918e12, 1640e9, 32e9),  # v6e
}


def device_peaks(device_kind: Optional[str] = None) -> DevicePeaks:
    """Peaks of ``device_kind`` (default: this process's first device)."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} — add it to "
            "profiling/flops_profiler.DEVICE_PEAKS with its source")
    return DEVICE_PEAKS[device_kind]


def _peak_tflops() -> float:
    """bf16 peak of this process's device for MFU math; 0.0 — "no MFU" — on
    the CPU test lane only. An accelerator without a row raises."""
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return 0.0
    return device_peaks(dev.device_kind).bf16_flops / 1e12


# ------------------------------------------------------------- jaxpr walk
def _dot_flops(eqn) -> int:
    """2*M*N*K for dot_general from operand shapes."""
    a, b = eqn.invars[0].aval, eqn.invars[1].aval
    dims = eqn.params["dimension_numbers"]
    (a_contract, _), (a_batch, _) = dims
    batch = int(np.prod([a.shape[i] for i in a_batch])) if a_batch else 1
    k = int(np.prod([a.shape[i] for i in a_contract])) if a_contract else 1
    m = int(np.prod(a.shape)) // (batch * k)
    n = int(np.prod(b.shape)) // (batch * k)
    return 2 * batch * m * n * k


def _conv_flops(eqn) -> int:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params.get("dimension_numbers")
    o_dim = dn.rhs_spec[0] if dn is not None else 0  # kernel's output-feature dim
    per_output = int(np.prod(rhs.shape)) // int(rhs.shape[o_dim])
    return 2 * int(np.prod(out.shape)) * per_output


def flops_by_op(fn: Callable, *args, **kwargs) -> Dict[str, int]:
    """Per-primitive flop breakdown via jaxpr traversal (the per-module
    table analog — on TPU the natural unit is the XLA op, not nn.Module)."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    counts: Dict[str, int] = {}

    def walk(jx, mult: int):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name == "dot_general":
                counts[name] = counts.get(name, 0) + mult * _dot_flops(eqn)
            elif name == "conv_general_dilated":
                counts[name] = counts.get(name, 0) + mult * _conv_flops(eqn)
            else:
                # scan bodies run `length` times; other sub-jaxprs once
                sub_mult = mult * int(eqn.params.get("length", 1)) if name == "scan" else mult
                def _sub(v):
                    if hasattr(v, "jaxpr"):  # ClosedJaxpr (pjit/scan/cond bodies)
                        return v.jaxpr
                    if hasattr(v, "eqns"):  # open core.Jaxpr (remat2/custom_jvp)
                        return v
                    return None

                for v in eqn.params.values():
                    for u in v if isinstance(v, (list, tuple)) else (v,):
                        sub = _sub(u)
                        if sub is not None:
                            walk(sub, sub_mult)
        return counts

    return walk(jaxpr.jaxpr, 1)


# --------------------------------------------------------- compiled costs
def compiled_cost(fn: Callable, *args, **kwargs) -> Dict[str, float]:
    """XLA cost analysis of the compiled program: exact flops/bytes.

    Routed through the compiled-program registry (telemetry/programs.py) so
    the analysis pass is recorded once and shared — repeated calls ride
    XLA's in-memory lowering/compile caches instead of re-analyzing, and
    with telemetry enabled the program lands in the ``program/*`` inventory
    like every engine-built program."""
    from deepspeed_tpu.telemetry.programs import get_program_registry

    rec = get_program_registry().capture(fn, *args, **kwargs)
    if rec is None:  # capture failed (non-jittable edge): old direct path
        return _costs_of(jax.jit(fn).lower(*args, **kwargs).compile())
    out = {"flops": rec.flops, "bytes accessed": rec.bytes_accessed}
    if rec.peak_hbm_bytes:
        out["peak_memory_bytes"] = float(rec.peak_hbm_bytes)
    return out


def _costs_of(compiled) -> Dict[str, float]:
    costs = compiled.cost_analysis()
    if isinstance(costs, list):  # older jax returns [dict]
        costs = costs[0] if costs else {}
    costs = dict(costs or {})
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            costs["peak_memory_bytes"] = float(
                getattr(mem, "temp_size_in_bytes", 0) + getattr(mem, "argument_size_in_bytes", 0)
            )
    except Exception:  # noqa: BLE001 - not all backends implement it
        pass
    return costs


@dataclass
class ProfileResult:
    flops_per_step: float
    bytes_accessed: float
    params: int
    latency_s: float
    achieved_tflops: float
    mfu: float
    per_op_flops: Dict[str, int] = field(default_factory=dict)

    def as_dict(self) -> Dict[str, Any]:
        return {
            "flops_per_step": self.flops_per_step,
            "bytes_accessed": self.bytes_accessed,
            "params": self.params,
            "latency_s": self.latency_s,
            "achieved_tflops": self.achieved_tflops,
            "mfu": self.mfu,
            "per_op_flops": dict(self.per_op_flops),
        }

    def publish_to_telemetry(self, tracer=None) -> None:
        """Feed achieved-TFLOPS/MFU into the shared ``MetricsRegistry`` so
        MFU rides the same trace (Perfetto counter tracks), CSV/monitor
        scalars, and flight-recorder dumps as step time and comm bytes.
        No-op when telemetry is disabled (the zero-overhead contract)."""
        if tracer is None:
            from deepspeed_tpu.telemetry import get_tracer

            tracer = get_tracer()
        if not tracer.enabled:
            return
        # sample_counter = registry gauge + a plotted Perfetto counter track
        tracer.sample_counter("flops/mfu", self.mfu)
        tracer.sample_counter("flops/achieved_tflops", self.achieved_tflops)
        tracer.registry.gauge("flops/flops_per_step").set(self.flops_per_step)
        tracer.registry.gauge("flops/step_latency_ms").set(self.latency_s * 1e3)
        tracer.registry.gauge("flops/bytes_accessed").set(self.bytes_accessed)


def get_model_profile(fn: Callable, *args, warmup: int = 1, iters: int = 3,
                      params: Any = None, peak_tflops: Optional[float] = None,
                      n_devices: int = 1, **kwargs) -> ProfileResult:
    """Profile a jittable fn (reference ``get_model_profile``
    flops_profiler/profiler.py — same deliverables: flops, params, latency).

    ``n_devices``: how many devices the program is sharded over — XLA cost
    analysis reports PER-DEVICE flops while the jaxpr walk counts GLOBAL
    logical flops; the per-op table is divided by this so both agree.
    """
    # ONE lower+compile serves both execution (AOT call) and cost analysis —
    # a second jit of the same fn would recompile the whole program.
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    jfn = lambda *a, **kw: compiled(*a, **kw)

    for _ in range(max(warmup, 1)):
        out = jfn(*args, **kwargs)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jfn(*args, **kwargs)
    jax.block_until_ready(out)
    latency = (time.perf_counter() - t0) / iters

    costs = _costs_of(compiled)
    flops = float(costs.get("flops", 0.0))
    bytes_accessed = float(costs.get("bytes accessed", costs.get("bytes_accessed", 0.0)))
    n_params = 0
    if params is not None:
        n_params = int(sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(params)))
    peak = peak_tflops if peak_tflops is not None else _peak_tflops()
    try:
        per_op = flops_by_op(fn, *args, **kwargs)
    except Exception as e:  # noqa: BLE001 - breakdown is best-effort
        logger.debug(f"per-op flop breakdown unavailable: {e}")
        per_op = {}
    per_op = {k: v // max(n_devices, 1) for k, v in per_op.items()}
    if flops <= 0 and per_op:
        # some backends (CPU) omit an aggregate 'flops' key — fall back to the
        # jaxpr-derived matmul/conv count (a lower bound on true flops)
        flops = float(sum(per_op.values()))
    achieved = flops / latency / 1e12 if latency > 0 else 0.0
    result = ProfileResult(
        flops_per_step=flops,
        bytes_accessed=bytes_accessed,
        params=n_params,
        latency_s=latency,
        achieved_tflops=achieved,
        mfu=(achieved / peak if peak else 0.0),
        per_op_flops=per_op,
    )
    result.publish_to_telemetry()
    return result


class FlopsProfiler:
    """Engine-attached profiler (reference ``FlopsProfiler`` profiler.py:30).

    Two triggers, both honored by ``engine.train_batch``: the config
    (``flops_profiler.enabled`` + ``profile_step``, fires once), or an
    explicit ``start_profile()`` (fires on the next batch). Each profile
    disarms itself; ``print_model_profile()`` emits the report.
    """

    def __init__(self, engine=None, config=None):
        self.engine = engine
        # the single config the engine trigger reads (engine.train_batch)
        self.config = config or (engine.config.model.flops_profiler if engine else None)
        self.result: Optional[ProfileResult] = None
        self._armed = False

    def start_profile(self) -> None:
        self._armed = True

    def stop_profile(self) -> None:
        self._armed = False

    @property
    def armed(self) -> bool:
        return self._armed

    def profile_engine_step(self, batch):
        """Profile THE engine's compiled step on ``batch`` and execute it once.

        Lowers+compiles the engine's step once (AOT — donation and shardings
        preserved from the jit wrapper) and EXECUTES that same AOT object for
        the timed step, returning ``(new_state, metrics)``: the caller applies
        this as the real training step for the batch, so profiling never
        double-steps and the timed program is exactly the profiled one.
        """
        e = self.engine
        state = e.state
        from deepspeed_tpu.diagnostics.recompile import unwrap_jit
        from deepspeed_tpu.telemetry.programs import unwrap_program_watch

        step_wrapper = e._train_step
        step_fn = unwrap_program_watch(unwrap_jit(step_wrapper))

        import jax.numpy as jnp

        # The program registry already analyzed THIS wrapper's compiled step
        # at its dispatch compile — reuse that record and dispatch the normal
        # wrapped step (a cache hit) instead of lowering+compiling a second
        # throwaway copy of the program just to read costs.
        rec = getattr(step_wrapper, "_program_record", None)
        if rec is not None and (rec.flops or rec.bytes_accessed):
            costs = {"flops": rec.flops, "bytes accessed": rec.bytes_accessed}
            t0 = time.perf_counter()
            new_state, metrics = step_wrapper(state, batch)
            np.asarray(jnp.sum(metrics["loss"]))  # scalar-transfer execution barrier
            latency = time.perf_counter() - t0
        else:
            # registry off (or capture failed): the original AOT path
            compiled = step_fn.lower(state, batch).compile()
            costs = _costs_of(compiled)
            t0 = time.perf_counter()
            new_state, metrics = compiled(state, batch)
            np.asarray(jnp.sum(metrics["loss"]))  # scalar-transfer execution barrier
            latency = time.perf_counter() - t0
        flops = float(costs.get("flops", 0.0))

        n_dev = max(e.mesh.size, 1)
        try:
            per_op = {k: v // n_dev for k, v in flops_by_op(step_fn, state, batch).items()}
        except Exception as ex:  # noqa: BLE001 - breakdown is best-effort
            logger.debug(f"per-op flop breakdown unavailable: {ex}")
            per_op = {}
        if flops <= 0 and per_op:
            flops = float(sum(per_op.values()))
        n_params = int(sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(state.params)))
        peak = _peak_tflops()
        achieved = flops / latency / 1e12 if latency > 0 else 0.0
        self.result = ProfileResult(
            flops_per_step=flops,
            bytes_accessed=float(costs.get("bytes accessed", 0.0)),
            params=n_params,
            latency_s=latency,
            achieved_tflops=achieved,
            mfu=(achieved / peak if peak else 0.0),
            per_op_flops=per_op,
        )
        self.result.publish_to_telemetry()
        self._armed = False
        return new_state, metrics

    # ------------------------------------------------------------ reporting
    def get_total_flops(self) -> float:
        return self.result.flops_per_step if self.result else 0.0

    def get_total_params(self) -> int:
        return self.result.params if self.result else 0

    def get_total_duration(self) -> float:
        return self.result.latency_s if self.result else 0.0

    def print_model_profile(self, top: int = 10) -> str:
        if self.result is None:
            return "flops profiler: no profile recorded"
        r = self.result
        lines = [
            "----------------- flops profiler (XLA cost analysis) -----------------",
            f"params:             {r.params/1e6:.2f} M",
            f"flops per step:     {r.flops_per_step/1e9:.2f} GFLOPs",
            f"bytes accessed:     {r.bytes_accessed/1e9:.3f} GB",
            f"step latency:       {r.latency_s*1e3:.2f} ms",
            f"achieved:           {r.achieved_tflops:.2f} TFLOPS (MFU {r.mfu*100:.1f}%)",
        ]
        if r.per_op_flops:
            total = max(sum(r.per_op_flops.values()), 1)
            lines.append("top ops by flops:")
            for name, fl in sorted(r.per_op_flops.items(), key=lambda kv: -kv[1])[:top]:
                lines.append(f"  {name:<24} {fl/1e9:>10.2f} GFLOPs  ({fl/total*100:.0f}% of matmul/conv)")
        report = "\n".join(lines)
        log_dist(report, ranks=[0])
        return report
