"""The program's own host spans in a profiler trace, and idle time by span.

Since PR 25 ``deepspeed_tpu/telemetry/tracer.py`` opens every span as a
``jax.profiler.TraceAnnotation("dstpu:<name>", **args)``, so a traced run's
``/host:CPU`` plane holds them on the trace's own clock, with the args as the
event's stats (``chain``, ``rows``, ``live``, ``k`` ...). A program without
such spans (the parent of PR 25) gives an empty list here and every reader of
a span metric then returns None.

While the per-layer metrics are read, the traced run's file still lies under
``.bench_trace/<cell>/`` (``run.py`` removes it afterwards): ``trace_file``
finds it from the run.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.lib import harness, xplane

PREFIX = "dstpu:"
OUTSIDE = "outside,"  # how the label of a gap that no dstpu: span holds starts


@dataclasses.dataclass(frozen=True)
class Span:
    name: str          # without the prefix: "serve:dispatch"
    start_s: float
    end_s: float
    args: Dict[str, object]

    @property
    def seconds(self) -> float:
        return self.end_s - self.start_s


def trace_file(run: dict) -> Optional[str]:
    """The ``.xplane.pb`` of the traced run being read, or None."""
    trace_dir = os.path.join(harness.BENCH_DIR, os.pardir, ".bench_trace", run["workload"]["name"])
    try:
        return xplane.find_xplane(trace_dir)
    except FileNotFoundError:
        return None


@functools.lru_cache(maxsize=2)
def profile(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _window(planes) -> Optional[Tuple[float, float]]:
    return next(((a, b) for n, a, b in xplane._host_spans(planes) if n == xplane.WINDOW_SPAN), None)


@functools.lru_cache(maxsize=2)
def read_spans(path: str) -> Tuple[Span, ...]:
    """Every ``dstpu:`` event of the host planes that overlaps the
    ``bench:window`` span (the whole trace where there is none), sorted by
    start and clipped to the window."""
    planes = list(profile(path).planes)
    window = _window(planes)
    lo, hi = window if window else (-np.inf, np.inf)
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(PREFIX):
                    continue
                a, b = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
                if b > lo and a < hi:
                    spans.append(Span(ev.name[len(PREFIX):], max(a, lo), min(b, hi), dict(ev.stats)))
    return tuple(sorted(spans, key=lambda s: (s.start_s, -s.end_s)))


def of_run(run: dict) -> Tuple[Span, ...]:
    """The spans of the traced run being read (none without its file), with
    the idle table printed on the way."""
    path = trace_file(run)
    if path is None:
        return ()
    report_idle(path)
    return read_spans(path)


def named(spans, name: str, **args) -> List[Span]:
    """The spans of one name whose args hold every ``key=value`` given."""
    return [s for s in spans if s.name == name
            and all(s.args.get(k) == v for k, v in args.items())]


def idle_by_span(path: str) -> Dict[str, float]:
    """Idle seconds of the first device in gaps of at least 20 us, each gap
    put down to the innermost ``dstpu:`` span over its midpoint (the span that
    started last among those that hold it). A gap that no span holds is
    labelled ``outside, after <span that ended last> before <next to start>``:
    time the host spent in the caller, not in the program. Host and device
    clocks of one trace differ by about a millisecond, so a gap at a span's
    edge can fall to its neighbour."""
    planes = list(profile(path).planes)
    device = next((p for p in planes if p.name.startswith("/device:TPU:")), None)
    lines = {ln.name: ln for ln in device.lines} if device is not None else {}
    if xplane.OPS_LINE not in lines:
        return {}
    names, iv = xplane._events(lines[xplane.OPS_LINE])
    leaf = np.asarray([xplane.split_instruction(n)[1] not in xplane.CONTAINERS for n in names], bool)
    iv = iv[leaf] if len(iv) else iv
    if not len(iv):
        return {}
    lo, hi = _window(planes) or (iv[:, 0].min(), iv[:, 1].max())
    gaps = xplane.subtract(np.asarray([[lo, hi]]), xplane.clip(xplane.union(iv), lo, hi))
    spans = read_spans(path)
    out: Dict[str, float] = {}
    for s, e in gaps:
        if e - s < xplane.MIN_LABELLED_GAP_S:
            continue
        mid = 0.5 * (s + e)
        inside = [sp for sp in spans if sp.start_s <= mid < sp.end_s]
        if inside:
            label = inside[-1].name
        else:
            before = max((sp for sp in spans if sp.end_s <= mid), key=lambda sp: sp.end_s, default=None)
            after = next((sp for sp in spans if sp.start_s > mid), None)
            label = "%s after %s before %s" % (OUTSIDE, before.name if before else "start",
                                               after.name if after else "end")
        out[label] = out.get(label, 0.0) + float(e - s)
    return out


def share_inside(table: Dict[str, float]) -> float:
    """The share of ``idle_by_span``'s seconds that some span holds."""
    total = sum(table.values())
    return sum(v for k, v in table.items() if not k.startswith(OUTSIDE)) / total if total else 0.0


@functools.lru_cache(maxsize=2)
def report_idle(path: str) -> Dict[str, float]:
    """``idle_by_span``, printed once per trace as ``idle_in_span=`` lines
    with the share of the labelled idle time that some span holds."""
    table = idle_by_span(path)
    for name, seconds in sorted(table.items(), key=lambda kv: -kv[1]):
        harness.say(idle_in_span=name.replace(" ", "_"), seconds=seconds)
    if table:
        harness.say(idle_seconds_in_gaps_over_20us=sum(table.values()),
                    share_inside_a_dstpu_span=share_inside(table))
    return table
