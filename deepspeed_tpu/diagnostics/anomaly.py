"""Step-time anomaly detection: rolling median + MAD over step wall times.

T3-style transparent runtime tracking (arXiv:2401.16677) argues the runtime
itself should notice when steps slow down, not a human reading dashboards
hours later. Two detectors over one rolling window:

  - **straggler**: a single step beyond ``median + k * MAD`` (MAD is robust —
    one slow step cannot inflate its own threshold the way a stddev would);
  - **regression**: the median of the most recent quarter of the window drifts
    past ``regression_factor`` x the window median — a sustained slowdown
    (thermal throttling, a neighbor job, a recompile storm), not a blip.

Results land as registry gauges (``anomaly/...``) so they ride the existing
telemetry export/monitor paths, plus tracer instants for the Perfetto view.
All host-side floats — never touches the device.

The serving loop (``inference/engine_v2.py``) judges every device call by the
same rule, ``beyond``, and where one is slow says why: ``CallLog`` keeps each
call's host stamps (always on, the profiler and the tracer off) and puts a
stall down to a late device, a host that did not run, the collector or the
program's own host code. See ``docs/diagnostics.md``, "A slow call".
"""

from __future__ import annotations

import collections
import statistics
import time
from typing import Callable, Deque, Dict, NamedTuple, Optional, Sequence, Tuple

from deepspeed_tpu.telemetry.events import emit_event
from deepspeed_tpu.telemetry.tracer import gc_seconds
from deepspeed_tpu.utils.logging import logger


def beyond(prior: Sequence[float], value: float, mads: float) -> Tuple[bool, float, float]:
    """The one median + MAD rule: whether ``value`` lies past the median of
    ``prior`` by more than ``mads`` MADs, with that median and MAD. MAD is
    robust: one slow sample cannot inflate its own threshold the way a
    standard deviation would. Its floor is 1% of the median: identical timings
    give MAD 0 and any jitter would flag."""
    med = statistics.median(prior)
    mad = statistics.median(abs(x - med) for x in prior)
    mad = max(mad, 0.01 * med, 1e-6)
    return value > med + mads * mad, med, mad


class StepTimeAnomalyDetector:
    def __init__(
        self,
        window: int = 64,
        straggler_mads: float = 6.0,
        regression_factor: float = 1.3,
        min_samples: int = 8,
        name: str = "step",
        tracer=None,
    ):
        self.window = int(window)
        self.straggler_mads = float(straggler_mads)
        self.regression_factor = float(regression_factor)
        self.min_samples = max(int(min_samples), 4)
        self.name = name
        self._durs: collections.deque = collections.deque(maxlen=self.window)
        self.stragglers = 0
        self._regressing = False
        if tracer is None:
            from deepspeed_tpu.telemetry import get_tracer

            tracer = get_tracer()
        self._tracer = tracer

    def observe(self, dur_s: float, step: Optional[int] = None) -> Dict[str, float]:
        """Record one step duration; returns this step's anomaly flags."""
        flags = {"straggler": False, "regression": False}
        prior = list(self._durs)
        self._durs.append(float(dur_s))
        if len(prior) < self.min_samples:
            return flags
        slow, med, mad = beyond(prior, dur_s, self.straggler_mads)
        if slow:
            flags["straggler"] = True
            self.stragglers += 1
            msg = (f"[anomaly/{self.name}] straggler step"
                   + (f" {step}" if step is not None else "")
                   + f": {dur_s * 1e3:.1f} ms vs median {med * 1e3:.1f} ms "
                   f"(MAD {mad * 1e3:.2f} ms)")
            logger.warning(msg)
            self._tracer.instant(f"straggler:{self.name}", cat="diagnostics",
                                 dur_ms=round(dur_s * 1e3, 3),
                                 median_ms=round(med * 1e3, 3))
            emit_event("anomaly", "straggler", msg, severity="warn",
                       labels={"name": self.name}, step=step,
                       dedup_key=f"anomaly:straggler:{self.name}")
        recent_n = max(len(self._durs) // 4, self.min_samples // 2)
        recent = list(self._durs)[-recent_n:]
        recent_med = statistics.median(recent)
        regressing = recent_med > self.regression_factor * med
        flags["regression"] = regressing
        if regressing and not self._regressing:
            msg = (f"[anomaly/{self.name}] sustained step-time regression: "
                   f"recent median {recent_med * 1e3:.1f} ms vs window median "
                   f"{med * 1e3:.1f} ms (> {self.regression_factor:.2f}x)")
            logger.warning(msg)
            self._tracer.instant(f"regression:{self.name}", cat="diagnostics",
                                 recent_ms=round(recent_med * 1e3, 3),
                                 median_ms=round(med * 1e3, 3))
            emit_event("anomaly", "regression", msg, severity="warn",
                       labels={"name": self.name}, step=step)
        self._regressing = regressing

        reg = self._tracer.registry
        reg.gauge(f"anomaly/{self.name}_median_ms").set(med * 1e3)
        reg.gauge(f"anomaly/{self.name}_mad_ms").set(mad * 1e3)
        reg.gauge(f"anomaly/{self.name}_straggler").set(float(flags["straggler"]))
        reg.gauge(f"anomaly/{self.name}_regression").set(float(regressing))
        return flags


# ---------------------------------------------------------------- a slow call
# A serving call takes 80-200 ms and the stalls on record 1-10 s: a call is
# slow where its cadence passes the rule over the last STALL_WINDOW calls of
# its class AND by an excess that a wave's rate would show.
STALL_MADS = 6.0
STALL_WINDOW = 64
STALL_MIN_SAMPLES = 3       # a prefill comes once a wave: judged from the fourth on
STALL_MIN_EXCESS_S = 0.25
CALLS_KEPT = 4096
STALLS_KEPT = 256
CPU_IDLE_SHARE = 1 / 20     # under this share of the wall time the thread did not run
MOST = 0.5                  # "the excess lies in", "the collector's seconds are", "CPU is": at least half
READY_AT_ONCE_SHARE = 0.25  # of a class's usual wait in serve:fetch: the device had gone on
CAUSES = ("device_late", "host_not_running", "collector", "host_busy", "unknown")


class Stamp(NamedTuple):
    """What the host knows of itself at one edge of a call."""

    wall: float     # time.perf_counter
    cpu: float      # time.thread_time: this thread's CPU seconds
    gc_s: float     # the process's collector seconds (telemetry/tracer.py)


def host_stamp() -> Stamp:
    return Stamp(time.perf_counter(), time.thread_time(), gc_seconds())


class CallRecord:
    """One device call of the serving loop: what it was, and the host's stamps
    where its ``serve:dispatch`` and ``serve:fetch`` spans open and close."""

    __slots__ = ("kind", "chain", "rows", "k",
                 "dispatch_open", "dispatch_close", "fetch_open", "fetch_close", "cadence_s")

    def __init__(self, kind: str, chain: int, rows: int, k: int):
        self.kind, self.chain, self.rows = kind, chain, rows
        self.k = k          # a chain's steps; a prefill's or a put's tokens a row (its program's chunk)
        self.dispatch_open = self.dispatch_close = self.fetch_open = self.fetch_close = None
        self.cadence_s: Optional[float] = None  # what the rule judges, known at the fetch's end

    @property
    def dispatched_at(self) -> float:
        return self.dispatch_open.wall

    @property
    def in_fetch_s(self) -> float:
        return self.fetch_close.wall - self.fetch_open.wall


class StallRecord(NamedTuple):
    """A slow call and what the stamps say of it; ``line()`` is what an
    operator reads (docs/diagnostics.md, "A slow call")."""

    chain: int
    kind: str
    rows: int
    k: int
    seconds: float         # the cadence: this fetch's end less the last one's (its own dispatch, nothing in flight)
    excess_s: float        # over the median of its class
    in_fetch_s: float      # of the seconds, inside serve:fetch
    usual_fetch_s: float   # the class's median there
    cpu_s: float           # the thread's CPU over the seconds
    fetch_cpu_s: float     # and inside serve:fetch
    gc_s: float            # the collector's seconds over them
    next_wait_s: float     # serve:fetch of the call that was in flight meanwhile; -1: none was
    usual_next_wait_s: float
    span: str              # the longest host span of the interval, where it holds most of the excess
    cause: str

    def line(self) -> str:
        fields = dict(self._asdict(), span=self.span or "-")
        return "[serving] stall: " + " ".join(
            f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}" for k, v in fields.items())

    def span_args(self) -> Dict[str, object]:
        return {k: getattr(self, k) for k in (
            "chain", "kind", "rows", "seconds", "excess_s", "in_fetch_s", "cpu_s", "gc_s", "next_wait_s", "cause")}


class CallLog:
    """Every device call's record, the rule over each class's cadence, and the
    cause of a slow one, decided when the NEXT call is fetched: a chain is
    queued ahead, so a host that froze finds it ready at once when it comes
    back, and a device that was late makes it wait its usual time."""

    def __init__(self, stamp: Callable[[], Stamp] = host_stamp):
        self.stamp = stamp
        self.calls: Deque[CallRecord] = collections.deque(maxlen=CALLS_KEPT)
        self.stalls: Deque[StallRecord] = collections.deque(maxlen=STALLS_KEPT)
        self._cadences: Dict[Tuple[str, int, int], Deque[float]] = collections.defaultdict(
            lambda: collections.deque(maxlen=STALL_WINDOW))
        self._last: Optional[CallRecord] = None
        self._pending = None  # (the slow call, the Stamp its cadence starts at, its class's median, longest host span)
        self._longest = ("", 0.0)

    def open(self, kind: str, chain: int, rows: int, k: int) -> CallRecord:
        rec = CallRecord(kind, chain, rows, k)
        self.calls.append(rec)
        return rec

    def host_span(self, name: str, seconds: float) -> None:
        """A host span of the loop closed: the longest since the last fetch is
        where a busy host's excess fell."""
        if seconds > self._longest[1]:
            self._longest = (name, seconds)

    def fetched(self, rec: CallRecord) -> Optional[StallRecord]:
        """``rec``'s fetch has closed: its cadence, the verdict on the slow
        call before it (``rec`` is its witness), and whether ``rec`` is slow."""
        prev, end = self._last, rec.fetch_close
        in_flight = prev is not None and rec.dispatch_open.wall < prev.fetch_close.wall
        start = prev.fetch_close if in_flight else rec.dispatch_open
        rec.cadence_s = cadence = end.wall - start.wall
        decided = self.settle(rec)
        seen = self._cadences[rec.kind, rec.rows, rec.k]
        # (the median is at least the least: most calls stop at this comparison)
        if len(seen) >= STALL_MIN_SAMPLES and cadence - min(seen) >= STALL_MIN_EXCESS_S:
            slow, med, _ = beyond(seen, cadence, STALL_MADS)
            if slow and cadence - med >= STALL_MIN_EXCESS_S:
                self._pending = (rec, start, med, self._longest)
        seen.append(cadence)
        self._last, self._longest = rec, ("", 0.0)
        return decided

    def _usual(self, like: CallRecord, of: Callable[[CallRecord], float]) -> float:
        """The median of ``of`` over the fetched calls of ``like``'s class before it."""
        values = []
        for rec in reversed(self.calls):
            if (rec is not like and rec.fetch_close is not None and rec.fetch_close.wall <= like.fetch_close.wall
                    and (rec.kind, rec.rows, rec.k) == (like.kind, like.rows, like.k)):
                values.append(of(rec))
                if len(values) == STALL_WINDOW:
                    break
        return statistics.median(values) if values else 0.0

    def settle(self, witness: Optional[CallRecord] = None) -> Optional[StallRecord]:
        """The verdict on the pending slow call, if any; ``witness`` is the
        call fetched after it (None at ``serve:finish``: none follows)."""
        if self._pending is None:
            return None
        (rec, start, med, (span, span_s)), self._pending = self._pending, None
        end, opened = rec.fetch_close, rec.fetch_open
        seconds = end.wall - start.wall
        excess = seconds - med
        usual_fetch = self._usual(rec, lambda r: r.in_fetch_s)
        fetch_excess = rec.in_fetch_s - usual_fetch
        outside_excess = excess - fetch_excess
        cpu, fetch_cpu = end.cpu - start.cpu, end.cpu - opened.cpu
        gc_s = end.gc_s - start.gc_s
        in_fetch = fetch_excess >= MOST * excess
        # the call that was in flight while the host was away, and its wait in serve:fetch: after an excess
        # inside the fetch the next call, if it was dispatched before that fetch opened; after one outside it the
        # slow call itself, if it was dispatched before the interval began
        if in_fetch:
            flown = witness if witness is not None and witness.dispatch_close.wall <= opened.wall else None
        else:
            flown = rec if rec.dispatch_close.wall <= start.wall else None
        next_wait, usual_wait = (flown.in_fetch_s, self._usual(flown, lambda r: r.in_fetch_s)) if flown else (-1.0, 0.0)
        at_once = flown is not None and next_wait <= READY_AT_ONCE_SHARE * usual_wait
        cause = "unknown"
        if gc_s >= MOST * excess:
            cause = "collector"
        elif in_fetch:
            if fetch_cpu < CPU_IDLE_SHARE * rec.in_fetch_s and flown is not None:
                if at_once:
                    cause = "host_not_running"  # the device went on while the host did not
                elif next_wait >= MOST * usual_wait:
                    cause = "device_late"       # the call queued behind it then took its usual time
        else:
            outside_s, outside_cpu = seconds - rec.in_fetch_s, cpu - fetch_cpu
            if outside_cpu >= MOST * outside_excess:
                cause = "host_busy"
            elif outside_cpu < CPU_IDLE_SHARE * outside_s and at_once:
                cause = "host_not_running"
        stall = StallRecord(
            chain=rec.chain, kind=rec.kind, rows=rec.rows, k=rec.k, seconds=seconds, excess_s=excess,
            in_fetch_s=rec.in_fetch_s, usual_fetch_s=usual_fetch, cpu_s=cpu, fetch_cpu_s=fetch_cpu, gc_s=gc_s,
            next_wait_s=next_wait, usual_next_wait_s=usual_wait,
            span=span if not in_fetch and span_s >= MOST * outside_excess else "", cause=cause)
        self.stalls.append(stall)
        return stall
