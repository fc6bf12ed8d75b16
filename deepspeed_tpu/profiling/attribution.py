"""Step-time attribution: where did the wall time of one step actually go?

Joins three measured sources the telemetry stack already collects —

  - the PR-7 **program registry**'s ``cost_analysis()`` flops +
    bytes-accessed for the compiled step program,
  - the PR-11 **collective observatory**'s per-route hop timings
    (``coll/hop_ms`` histogram children),
  - **tracer span** deltas (``span/<name>`` histograms, e.g. the host
    input-pipeline ``data`` span),

— into an exact four-bucket decomposition of the measured wall time::

    wall = compute + collective + host + stall

``compute`` is the roofline estimate ``max(flops/peak_flops,
bytes/peak_bw)`` clamped to the wall; ``collective`` and ``host`` are the
measured estimates clamped to what remains (each source is a lower bound
— a hop probe can't exceed the step that contained it); ``stall`` is the
non-negative residual (dispatch gaps, sync waits, anything unattributed).
The buckets sum to the wall **by construction** — the decomposition never
invents time, it only allocates the measured wall.

The verdict names the dominant bucket — ``compute`` / ``memory`` (the two
roofline regimes), ``comm``, ``host``, or ``stall`` — alongside
achieved-vs-peak fractions, published as ``perf/attribution_*`` and
``perf/roofline_*`` gauges so the ledger's trajectory and a step's
decomposition read from one registry. This is the measured objective the
ROADMAP's schedule-compiler and overlap work optimize against.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

from deepspeed_tpu.profiling.flops_profiler import DEVICE_PEAKS
from deepspeed_tpu.telemetry.perfledger import backend_stamp

#: peak envelopes per ledger backend stamp. Accelerator rows are DERIVED from
#: the one ``device_kind``-keyed table (profiling/flops_profiler.DEVICE_PEAKS);
#: the cpu / interpret rows are nominal envelopes for the CPU test lane, where
#: an attribution shows the decomposition works and is never a device claim
PEAK_FLOPS: Dict[str, float] = {
    "cpu": 1e12, "interpret": 1e12,
    **{backend_stamp(k): p.bf16_flops for k, p in DEVICE_PEAKS.items()}}
PEAK_BYTES_PER_S: Dict[str, float] = {
    "cpu": 50e9, "interpret": 50e9,
    **{backend_stamp(k): p.hbm_bytes_per_s for k, p in DEVICE_PEAKS.items()}}


@dataclass
class Attribution:
    label: str
    wall_ms: float
    compute_ms: float
    collective_ms: float
    host_ms: float
    stall_ms: float
    bound: str               # compute | memory | comm | host | stall
    flops: float = 0.0
    bytes_accessed: float = 0.0
    flops_fraction: float = 0.0   # achieved flops rate / peak
    bw_fraction: float = 0.0      # achieved HBM rate / peak

    def buckets(self) -> Dict[str, float]:
        return {"compute": self.compute_ms, "collective": self.collective_ms,
                "host": self.host_ms, "stall": self.stall_ms}

    def as_dict(self) -> Dict[str, Any]:
        d = {"label": self.label, "wall_ms": self.wall_ms,
             "bound": self.bound, "flops": self.flops,
             "bytes_accessed": self.bytes_accessed,
             "flops_fraction": self.flops_fraction,
             "bw_fraction": self.bw_fraction}
        d.update({f"{k}_ms": v for k, v in self.buckets().items()})
        return d

    def render(self) -> str:
        parts = [f"{k}={v:.2f}ms ({v / self.wall_ms:.0%})" if self.wall_ms
                 else f"{k}={v:.2f}ms" for k, v in self.buckets().items()]
        return (f"{self.label}: wall={self.wall_ms:.2f}ms -> "
                + " ".join(parts)
                + f" | {self.bound}-bound, {self.flops_fraction:.1%} of peak "
                  f"flops, {self.bw_fraction:.1%} of peak bw")


def attribute(label: str, wall_s: float, *, flops: float = 0.0,
              bytes_accessed: float = 0.0,
              peak_flops: Optional[float] = None,
              peak_bytes_per_s: Optional[float] = None,
              collective_s: float = 0.0, host_s: float = 0.0,
              registry=None, publish: bool = True) -> Attribution:
    """The pure decomposition. All inputs are seconds/flops/bytes for ONE
    step (or one serving chain); estimates are clamped so the four buckets
    always sum exactly to ``wall_s``."""
    wall_s = max(float(wall_s), 0.0)
    flop_term = (flops / peak_flops) if (peak_flops and flops > 0) else 0.0
    bw_term = (bytes_accessed / peak_bytes_per_s) \
        if (peak_bytes_per_s and bytes_accessed > 0) else 0.0
    compute_s = min(max(flop_term, bw_term), wall_s)
    coll_s = min(max(float(collective_s), 0.0), wall_s - compute_s)
    hst_s = min(max(float(host_s), 0.0), wall_s - compute_s - coll_s)
    stall_s = wall_s - compute_s - coll_s - hst_s

    buckets = {"compute": compute_s, "comm": coll_s, "host": hst_s,
               "stall": stall_s}
    bound = max(buckets, key=lambda k: buckets[k])
    if bound == "compute" and bw_term > flop_term:
        bound = "memory"

    flops_frac = (flops / wall_s / peak_flops) \
        if (wall_s > 0 and peak_flops) else 0.0
    bw_frac = (bytes_accessed / wall_s / peak_bytes_per_s) \
        if (wall_s > 0 and peak_bytes_per_s) else 0.0

    attr = Attribution(
        label=label, wall_ms=wall_s * 1e3, compute_ms=compute_s * 1e3,
        collective_ms=coll_s * 1e3, host_ms=hst_s * 1e3,
        stall_ms=stall_s * 1e3, bound=bound, flops=float(flops),
        bytes_accessed=float(bytes_accessed), flops_fraction=flops_frac,
        bw_fraction=bw_frac)
    if publish:
        _publish(attr, registry)
    return attr


def _publish(attr: Attribution, registry=None) -> None:
    if registry is None:
        from deepspeed_tpu.telemetry import get_tracer

        tracer = get_tracer()
        if not tracer.enabled:
            return
        registry = tracer.registry
    g = registry.gauge
    g("perf/attribution_wall_ms", program=attr.label).set(attr.wall_ms)
    g("perf/attribution_compute_ms", program=attr.label).set(attr.compute_ms)
    g("perf/attribution_collective_ms",
      program=attr.label).set(attr.collective_ms)
    g("perf/attribution_host_ms", program=attr.label).set(attr.host_ms)
    g("perf/attribution_stall_ms", program=attr.label).set(attr.stall_ms)
    g("perf/attribution_bound", program=attr.label, bound=attr.bound).set(1.0)
    g("perf/roofline_flops_fraction",
      program=attr.label).set(attr.flops_fraction)
    g("perf/roofline_bw_fraction", program=attr.label).set(attr.bw_fraction)


# ------------------------------------------------------- measured sources
def measured_collective_s(registry=None) -> float:
    """Lower-bound estimate of one step's collective time: the sum of each
    routed signature's most recent per-hop probe (``coll/hop_ms``
    children, PR 11). Probes are per-hop samples, so this undercounts
    multi-hop rings — honest as a floor, never as a ceiling."""
    if registry is None:
        from deepspeed_tpu.telemetry import get_tracer

        registry = get_tracer().registry
    total_ms = 0.0
    for kind, _key, metric in registry.iter_metrics():
        if kind == "histogram" and metric.name == "coll/hop_ms" \
                and metric.count:
            total_ms += float(metric.last)
    return total_ms / 1e3


def span_last_s(name: str, registry=None) -> float:
    """Most recent duration of tracer span ``name`` (0.0 when the span
    never ran — e.g. ``data`` before the first host batch)."""
    if registry is None:
        from deepspeed_tpu.telemetry import get_tracer

        registry = get_tracer().registry
    h = registry.peek_histogram(f"span/{name}")
    return float(h.last) if h is not None and h.count else 0.0


def attribute_program(label: str, wall_s: float, *,
                      backend: Optional[str] = None, registry=None,
                      host_span: str = "data", publish: bool = True,
                      ) -> Attribution:
    """Attribution for a registered compiled program (e.g. the engine's
    ``train_step``): flops/bytes from the program registry's latest
    capture, collective floor from the observatory, host time from the
    ``host_span`` tracer span, peaks from the ledger backend."""
    from deepspeed_tpu.telemetry.perfledger import default_backend
    from deepspeed_tpu.telemetry.programs import get_program_registry

    backend = backend or default_backend()
    rec = get_program_registry().latest(label)
    return attribute(
        label, wall_s,
        flops=float(rec.flops) if rec else 0.0,
        bytes_accessed=float(rec.bytes_accessed) if rec else 0.0,
        peak_flops=PEAK_FLOPS.get(backend),
        peak_bytes_per_s=PEAK_BYTES_PER_S.get(backend),
        collective_s=measured_collective_s(registry),
        host_s=span_last_s(host_span, registry),
        registry=registry, publish=publish)
