"""Metrics registry: counters, gauges, quantile-capable histograms — with labels.

The numeric half of the telemetry subsystem (the ``Tracer`` in ``tracer.py``
is the temporal half). Closest reference analogs are the scattered aggregates
in ``utils/comms_logging.py`` (bytes/counts per op) and the monitor scalars —
here they share ONE registry so the ``MonitorMaster`` backends and the
exporters all read the same numbers.

Labels (serving SLO observability): every factory accepts keyword labels —
``registry.histogram("serving/ttft_ms", k=8)`` — producing one child metric
per label set, keyed ``name{k="8"}`` in the flat snapshot and exposed as a
proper labelled family by ``exposition.render_prometheus``. The unlabelled
call is unchanged (same object identity, same snapshot keys), so every
pre-existing call site keeps its exact behavior.

Histograms are **log-bucketed**: each observation lands in a sparse
geometric bucket (growth ``2**(1/8)`` per bucket, so any quantile estimate
carries at most ~4.4% relative error — ``sqrt(growth)-1``). That answers
p50/p95/p99 queries in O(populated buckets) with O(1) per observe (one
``log2`` + one dict bump), which is what lets per-request serving latencies
(TTFT/TPOT/queue-wait) stay cheap enough for the decode hot path while still
producing honest tail percentiles and a Prometheus histogram exposition.

Thread-safe end to end: creation AND mutation run under the registry's lock
(spans may close on any thread — the tracer records per-thread ids), so
concurrent increments never drop. Contention is negligible: updates happen
per span/collective/chain-boundary, not per tensor element.

Creation is get-or-create so call sites never coordinate.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, Iterator, List, Optional, Tuple

# Log-bucket growth factor: 2**(1/8) per bucket. A value v>0 lands in bucket
# floor(log2(v) * 8); the bucket's representative (geometric midpoint) is at
# most sqrt(growth) ~ 1.044x away from any value in it -> bounded ~4.4%
# relative error on every quantile estimate.
_BUCKETS_PER_OCTAVE = 8
_GROWTH = 2.0 ** (1.0 / _BUCKETS_PER_OCTAVE)


def bucket_upper_bound(idx: Optional[int]) -> float:
    """Inclusive upper bound of a log bucket (``le`` in Prometheus terms).
    ``idx=None`` is the underflow bucket for values <= 0 (le == 0)."""
    if idx is None:
        return 0.0
    return 2.0 ** ((idx + 1) / _BUCKETS_PER_OCTAVE)


def encode_labels(labels: Dict[str, object]) -> str:
    """Canonical label suffix: ``{a="1",b="x"}`` sorted by key; "" when
    empty. This is the ONE spelling — snapshot keys, registry child keys and
    the Prometheus exposition all use it."""
    if not labels:
        return ""
    return "{" + ",".join(
        f'{k}="{labels[k]}"' for k in sorted(labels)) + "}"


_KEY_LABEL_RE = None


def decode_key(key: str) -> Tuple[str, Dict[str, str]]:
    """Inverse of ``name + encode_labels(labels)``: split a flat registry
    key back into (base name, label dict). The fleet collector re-labels
    per-process gauges (``{proc=}``) from dump keys, so the parse must
    round-trip exactly what :func:`encode_labels` writes — plain
    ``k="v"`` pairs, no escaping (registry label values never contain
    quotes; the Prometheus exposition escapes separately)."""
    global _KEY_LABEL_RE
    brace = key.find("{")
    if brace < 0 or not key.endswith("}"):
        return key, {}
    if _KEY_LABEL_RE is None:
        import re

        _KEY_LABEL_RE = re.compile(r'([a-zA-Z0-9_]+)="([^"]*)"')
    labels = {m.group(1): m.group(2)
              for m in _KEY_LABEL_RE.finditer(key[brace + 1:-1])}
    return key[:brace], labels


class Counter:
    """Monotonic accumulator (e.g. ``comm/bytes``)."""

    __slots__ = ("name", "labels", "value", "_lock")

    def __init__(self, name: str, lock: threading.Lock,
                 labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.labels = labels or {}
        self.value = 0.0
        self._lock = lock

    def add(self, v: float) -> None:
        with self._lock:
            self.value += v


class Gauge:
    """Last-write-wins sample (e.g. ``mem/device_bytes_in_use``)."""

    __slots__ = ("name", "labels", "value", "_lock")

    def __init__(self, name: str, lock: threading.Lock,
                 labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.labels = labels or {}
        self.value = 0.0
        self._lock = lock

    def set(self, v: float) -> None:
        with self._lock:
            self.value = v


class Histogram:
    """Streaming summary (count/total/min/max/last) plus sparse log buckets
    for cheap bounded-error quantiles (p50/p95/p99)."""

    __slots__ = ("name", "labels", "count", "total", "min", "max", "last",
                 "_buckets", "_lock")

    def __init__(self, name: str, lock: threading.Lock,
                 labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.labels = labels or {}
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self.last = 0.0
        # sparse log buckets: {idx: count}; idx None = underflow (v <= 0)
        self._buckets: Dict[Optional[int], int] = {}
        self._lock = lock

    @staticmethod
    def _bucket_idx(v: float):
        """Sparse bucket key for ``v``: None (underflow, le=0) for v <= 0 or
        NaN; a finite int for finite v > 0; ``...`` (Ellipsis sentinel) for
        +inf — counted only by the implicit +Inf bucket (= count) in the
        exposition, and pushing high quantiles to ``max`` rather than
        raising (floor(log2(inf)) would OverflowError)."""
        if not (v > 0):  # catches <= 0 and NaN
            return None
        lg = math.log2(v) * _BUCKETS_PER_OCTAVE
        if lg == float("inf"):
            return ...
        return math.floor(lg)

    def observe(self, v: float) -> None:
        with self._lock:
            self.count += 1
            self.total += v
            self.last = v
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v
            idx = self._bucket_idx(v)
            if idx is not ...:
                self._buckets[idx] = self._buckets.get(idx, 0) + 1

    def observe_n(self, v: float, n: int) -> None:
        """``n`` observations of the same value in one lock/bucket hit — the
        serving loop groups a chain's identical per-row TPOT samples."""
        if n <= 0:
            return
        with self._lock:
            self.count += n
            self.total += v * n
            self.last = v
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v
            idx = self._bucket_idx(v)
            if idx is not ...:
                self._buckets[idx] = self._buckets.get(idx, 0) + n

    def buckets(self) -> List[Tuple[Optional[int], int]]:
        """Populated log buckets sorted ascending (underflow first)."""
        with self._lock:
            return sorted(self._buckets.items(),
                          key=lambda kv: -math.inf if kv[0] is None else kv[0])

    def state(self) -> Dict[str, object]:
        """Wire-portable full state (JSON-safe): summary scalars plus the
        RAW sparse buckets — the piece a cross-process merge needs that
        ``summary()`` drops. Bucket keys stringify (JSON objects can't key
        on ints/None): ``"u"`` is the underflow bucket, ints are
        ``str(idx)``."""
        with self._lock:
            return {
                "count": self.count,
                "total": self.total,
                "min": self.min if self.count else 0.0,
                "max": self.max if self.count else 0.0,
                "last": self.last,
                "buckets": {("u" if k is None else str(k)): v
                            for k, v in self._buckets.items()},
            }

    def merge_state(self, state: Dict[str, object]) -> None:
        """Fold another histogram's :meth:`state` into this one — EXACTLY
        equivalent to having observed the other histogram's sample stream
        here (bucket counts add, count/total add, min/max widen; ``last``
        is taken from the incoming state, the per-process notion of
        "latest" — label merged streams per process if that matters).
        The log buckets make this exact by construction: a sample lands in
        the same bucket no matter which process observed it."""
        n = int(state.get("count", 0))
        if n <= 0:
            return
        with self._lock:
            self.count += n
            self.total += float(state.get("total", 0.0))
            self.last = float(state.get("last", 0.0))
            s_min = float(state.get("min", 0.0))
            s_max = float(state.get("max", 0.0))
            if s_min < self.min:
                self.min = s_min
            if s_max > self.max:
                self.max = s_max
            for k, v in (state.get("buckets") or {}).items():
                idx = None if k == "u" else int(k)
                self._buckets[idx] = self._buckets.get(idx, 0) + int(v)

    def quantile(self, q: float) -> float:
        """Bounded-relative-error quantile estimate from the log buckets.

        Walks the sparse buckets to the target rank and returns the bucket's
        geometric midpoint, clamped to the exact observed [min, max] — so
        p0/p100 are exact and everything between carries at most
        ``sqrt(growth) - 1`` (~4.4%) relative error.
        """
        with self._lock:
            if self.count == 0:
                return 0.0
            target = max(1, math.ceil(q * self.count))
            cum = 0
            items = sorted(self._buckets.items(),
                           key=lambda kv: -math.inf if kv[0] is None else kv[0])
            for idx, c in items:
                cum += c
                if cum >= target:
                    if idx is None:
                        return self.min  # underflow bucket: v <= 0
                    mid = 2.0 ** ((idx + 0.5) / _BUCKETS_PER_OCTAVE)
                    return min(max(mid, self.min), self.max)
            return self.max  # unreachable; defensive

    def summary(self) -> Dict[str, float]:
        with self._lock:
            if self.count == 0:
                return {"count": 0, "total": 0.0, "mean": 0.0, "min": 0.0, "max": 0.0}
            out = {
                "count": self.count,
                "total": self.total,
                "mean": self.total / self.count,
                "min": self.min,
                "max": self.max,
            }
        # quantiles re-take the (reentrant) registry lock per call
        out["p50"] = self.quantile(0.50)
        out["p95"] = self.quantile(0.95)
        out["p99"] = self.quantile(0.99)
        return out


class MetricsRegistry:
    """Get-or-create registry of named metrics (one shared lock — see module
    docstring). Labels produce one child per label set, keyed
    ``name{k="v",...}`` in the flat dicts."""

    def __init__(self):
        self._lock = threading.RLock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}

    def counter(self, name: str, **labels) -> Counter:
        key = name + encode_labels(labels)
        with self._lock:
            c = self._counters.get(key)
            if c is None:
                c = self._counters[key] = Counter(
                    name, self._lock, {k: str(v) for k, v in labels.items()})
            return c

    def gauge(self, name: str, **labels) -> Gauge:
        key = name + encode_labels(labels)
        with self._lock:
            g = self._gauges.get(key)
            if g is None:
                g = self._gauges[key] = Gauge(
                    name, self._lock, {k: str(v) for k, v in labels.items()})
            return g

    def histogram(self, name: str, **labels) -> Histogram:
        key = name + encode_labels(labels)
        with self._lock:
            h = self._histograms.get(key)
            if h is None:
                h = self._histograms[key] = Histogram(
                    name, self._lock, {k: str(v) for k, v in labels.items()})
            return h

    def peek_histogram(self, name: str, **labels) -> Optional[Histogram]:
        """Read-only lookup — never creates (keeps snapshots free of
        zero-count entries from probes)."""
        with self._lock:
            return self._histograms.get(name + encode_labels(labels))

    def iter_metrics(self) -> Iterator[Tuple[str, str, object]]:
        """``(kind, base_name, metric)`` for every registered metric —
        label-aware iteration for the exposition layer (labels live on the
        metric objects)."""
        with self._lock:
            items = (
                [("counter", c.name, c) for c in self._counters.values()]
                + [("gauge", g.name, g) for g in self._gauges.values()]
                + [("histogram", h.name, h) for h in self._histograms.values()]
            )
        return iter(items)

    def snapshot(self) -> Dict[str, object]:
        """Flat dict of every metric's current value(s); labelled children
        appear under their ``name{k="v"}`` key."""
        with self._lock:
            out: Dict[str, object] = {}
            for n, c in self._counters.items():
                out[n] = c.value
            for n, g in self._gauges.items():
                out[n] = g.value
            for n, h in self._histograms.items():
                out[n] = h.summary()
            return out

    def size(self) -> int:
        """Number of registered metric children (labelled children count
        individually) — the ``/healthz`` registry-size signal."""
        with self._lock:
            return (len(self._counters) + len(self._gauges)
                    + len(self._histograms))

    def counters(self) -> Dict[str, float]:
        with self._lock:
            return {n: c.value for n, c in self._counters.items()}

    def gauges(self) -> Dict[str, float]:
        with self._lock:
            return {n: g.value for n, g in self._gauges.items()}

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()
