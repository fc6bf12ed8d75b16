"""Numerics observatory: live quantization-fidelity and replica-integrity.

The system runs lossy numerics on several wires — the int8 wire with LoCo
error feedback in the ZeRO++ gathers, quantized KV / weight-only-quant
serving, n-gram speculative decode — yet until this module the only evidence
any of it stayed accurate was a fixed bound in a one-off test. The perf
ledger closed the *performance* feedback loop; this module closes the
*correctness* one. Three planes, all riding the same sampled,
jaxpr-identical-when-off discipline:

1. **Error-feedback residual gauges** — on sampled steps the L2 norms of the
   LoCo / 1-bit residuals carried in ``TrainState.comm_error``, a group of
   leaves a gauge (``numerics/ef_residual_norm``): a norm that trends up
   means the wire drops more than the feedback loop re-captures.

2. **Cross-replica divergence sentinel** (:class:`DivergenceSentinel`) —
   a cheap per-leaf-group digest (sum-of-squares + bit-level xor checksum)
   computed *inside* the jitted train step on sampled steps, carried in
   ``TrainState.numerics`` exactly like the PR-2 ``health`` field. Each
   leaf's digest is compared across the mesh axes the leaf is *replicated*
   over via ``pmin``/``pmax``: physically divergent dp/fsdp replicas make
   min != max and latch a ``numerics/divergence_events`` counter in the
   carried state (host sampling can therefore never miss a detection).
   The xor checksum folds across sharded axes with ``all_gather``+xor —
   order-independent and exact, so the whole-tree checksum is bit-stable
   across mesh shapes and rides the PR-13 fleet heartbeats as the
   cross-process comparator. Policy ``log`` | ``abort`` (the abort raises
   ``diagnostics.manager.TrainingHealthError`` from the host hook).

3. **Serving fidelity** — sampled KV dequant-error and WOQ matmul-error
   probes for the v2 inference engine plus a spec-decode acceptance-rate
   :class:`TrendAlarm` (PR-2 median+MAD discipline, low side).

Disabled (the default) every hook is an attribute check and the sentinel is
absent from the train step — the program is jaxpr-identical to a build
without this module (pinned by ``tests/unit/test_numerics.py``).

See docs/telemetry.md "Numerics observatory".
"""

from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass, replace as dc_replace
from statistics import median
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.utils.compat import shard_map
from deepspeed_tpu.utils.logging import logger


# --------------------------------------------------------------------- config
@dataclass
class NumericsConfig:
    """Tunables (the engine's ``numerics`` config block mirrors these)."""

    enabled: bool = False
    sample_every: int = 16           # 1-in-N steps runs EF-residual/serving probes
    sentinel: bool = True            # in-jit divergence sentinel (when enabled)
    sentinel_sample_every: int = 16  # 1-in-N train steps digests the params
    divergence_policy: str = "log"   # "log" | "abort"
    spec_accept_window: int = 64     # acceptance-rate trend window
    spec_accept_mads: float = 6.0    # PR-2 discipline width
    spec_accept_min_n: int = 8       # min history before the alarm can fire


# ----------------------------------------------------------- digest primitives
def leaf_checksum(x: jax.Array) -> jax.Array:
    """Order-independent bit-level checksum of a float leaf (uint32 scalar).

    xor over the float32 bit patterns: exact, commutative, associative —
    the xor of per-shard checksums equals the whole-tensor checksum, so the
    folded value is bit-stable across mesh shapes (pinned by test).
    """
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    if bits.ndim == 0:
        return bits
    return lax.reduce(bits, np.uint32(0), lax.bitwise_xor,
                      tuple(range(bits.ndim)))


def leaf_sumsq(x: jax.Array) -> jax.Array:
    """Sum of squares in fp32 (magnitude digest; NOT bit-stable across mesh
    shapes — used only for the replica min/max gap, never cross-process)."""
    xf = x.astype(jnp.float32)
    return jnp.sum(xf * xf)


def _spec_axes(spec) -> frozenset:
    """Mesh axis names a PartitionSpec shards over."""
    if spec is None:
        return frozenset()
    names: set = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            names.update(str(e) for e in entry)
        else:
            names.add(str(entry))
    return frozenset(names)


def _group_key(path) -> str:
    """Top-level tree key for a leaf path (mirrors diagnostics/health.py)."""
    if not path:
        return "params"
    entry = path[0]
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry).strip("[].'\"")


class NumericsState(NamedTuple):
    """Sentinel state carried in ``TrainState.numerics`` (distinct arrays —
    shared zeros would alias buffers under step donation)."""

    checked: jax.Array    # i32: digest probes run
    events: jax.Array     # i32: cumulative divergence events (latched)
    checksum: jax.Array   # u32: latest whole-tree xor digest
    gap: jax.Array        # f32: latest max replica sum-of-squares gap


class DivergenceSentinel:
    """In-jit cross-replica digest comparator (see module doc, plane 2).

    Construction captures the mesh and the params' PartitionSpec tree so
    each leaf knows which axes it is replicated over: divergence is defined
    per-leaf as ``pmin != pmax`` of the local digest across exactly those
    axes (a sharded axis holds *different* data by construction and is
    folded into the global checksum instead, via all_gather + xor).
    """

    def __init__(self, mesh, param_specs, sample_every: int = 16):
        self.mesh = mesh
        self.param_specs = param_specs
        self.sample_every = int(sample_every)

    @staticmethod
    def init_state() -> NumericsState:
        return NumericsState(
            checked=jnp.zeros((), jnp.int32),
            events=jnp.zeros((), jnp.int32),
            checksum=jnp.zeros((), jnp.uint32),
            gap=jnp.zeros((), jnp.float32),
        )

    # ------------------------------------------------------------ internals
    def _flat(self, params):
        """Float leaves with (path, spec, group) alignment."""
        leaves = jax.tree_util.tree_leaves_with_path(params)
        spec_leaves = jax.tree_util.tree_leaves(self.param_specs)
        if len(spec_leaves) != len(leaves):
            # spec tree shape drifted from params (custom containers):
            # fall back to fully-replicated specs — digesting a sharded
            # leaf as replicated can false-positive, so be loud about it
            logger.warning(
                "numerics sentinel: param spec tree does not match params "
                f"({len(spec_leaves)} specs vs {len(leaves)} leaves); "
                "assuming replicated leaves")
            spec_leaves = [P()] * len(leaves)
        out = []
        for (path, leaf), spec in zip(leaves, spec_leaves):
            if hasattr(spec, "spec"):  # NamedSharding passed instead of spec
                spec = spec.spec
            if not jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
                continue
            out.append((leaf, spec, _group_key(path)))
        return out

    def _digest(self, flat):
        """shard_map program producing (per-group diverged i32[G], max gap
        f32, whole-tree xor checksum u32), all replicated."""
        mesh = self.mesh
        axis_names = tuple(mesh.axis_names)
        groups: List[str] = []
        for _leaf, _spec, g in flat:
            if g not in groups:
                groups.append(g)
        gidx = {g: i for i, g in enumerate(groups)}
        specs = [spec for _leaf, spec, _g in flat]
        gis = [gidx[g] for _leaf, _spec, g in flat]
        n_groups = len(groups)

        def fn(*locals_):
            div_acc = [jnp.zeros((), jnp.int32) for _ in range(n_groups)]
            gap_all = jnp.zeros((), jnp.float32)
            ck_all = jnp.zeros((), jnp.uint32)
            for x, spec, gi in zip(locals_, specs, gis):
                sharded = _spec_axes(spec) & set(axis_names)
                rep = tuple(a for a in axis_names if a not in sharded)
                ss = leaf_sumsq(x)
                ck = leaf_checksum(x)
                if rep:
                    ck_min, ck_max = lax.pmin(ck, rep), lax.pmax(ck, rep)
                    ss_min, ss_max = lax.pmin(ss, rep), lax.pmax(ss, rep)
                    d = ((ck_min != ck_max) | (ss_min != ss_max)
                         ).astype(jnp.int32)
                    g = ss_max - ss_min
                else:
                    d = jnp.zeros((), jnp.int32)
                    g = jnp.zeros((), jnp.float32)
                # whole-tensor checksum: xor-fold the per-shard checksums
                # across each sharded axis (exact, order-independent)
                for ax in axis_names:
                    if ax not in sharded:
                        continue
                    gathered = lax.all_gather(ck, ax)
                    ck = lax.reduce(gathered, np.uint32(0), lax.bitwise_xor,
                                    (0,))
                    # a sharded axis also means the per-position divergence
                    # verdicts differ: fold to "any position diverged"
                    d = lax.pmax(d, ax)
                    g = lax.pmax(g, ax)
                if rep:
                    # deterministic output when replicas DISAGREE (the
                    # checksum itself is then ill-defined; take the min)
                    ck = lax.pmin(ck, rep)
                div_acc[gi] = jnp.maximum(div_acc[gi], d)
                gap_all = jnp.maximum(gap_all, g)
                ck_all = lax.bitwise_xor(ck_all, ck)
            div = (jnp.stack(div_acc) if div_acc
                   else jnp.zeros((0,), jnp.int32))
            return div, gap_all, ck_all

        in_specs = tuple(spec if spec is not None else P() for spec in specs)
        # fresh closure per trace (shard_map caches on function identity)
        mapped = shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=(P(), P(), P()), check_vma=False)
        return mapped(*[leaf for leaf, _spec, _g in flat]), groups

    # ---------------------------------------------------------------- probe
    def probe(self, nstate: Optional[NumericsState], params, step,
              ) -> Tuple[Optional[NumericsState], Dict[str, Any]]:
        """Traced into the train step. On sampled steps digests ``params``
        and latches divergence into the carried state; other steps run the
        zero branch of a ``lax.cond`` (no digest work dispatched)."""
        if nstate is None:
            return nstate, {}
        flat = self._flat(params)
        if not flat:
            return nstate, {}
        every = self.sample_every

        def run(leaves):
            flat_now = [(leaf, spec, g)
                        for leaf, (_old, spec, g) in zip(leaves, flat)]
            (div, gap, ck), _groups = self._digest(flat_now)
            return div, gap, ck

        def skip(leaves):
            n_groups = len({g for _l, _s, g in flat})
            return (jnp.zeros((n_groups,), jnp.int32),
                    jnp.zeros((), jnp.float32), jnp.zeros((), jnp.uint32))

        do = ((step % every) == 0) if every > 0 else jnp.asarray(False)
        do = jnp.asarray(do)
        leaves = [leaf for leaf, _s, _g in flat]
        div, gap, ck = lax.cond(do, run, skip, leaves)
        diverged = (jnp.max(div) if div.shape[0] else
                    jnp.zeros((), jnp.int32))
        new_state = NumericsState(
            checked=nstate.checked + do.astype(jnp.int32),
            events=nstate.events + diverged,
            checksum=jnp.where(do, ck, nstate.checksum),
            gap=jnp.where(do, gap, nstate.gap),
        )
        groups = []
        for _l, _s, g in flat:
            if g not in groups:
                groups.append(g)
        metrics: Dict[str, Any] = {
            "numerics/checked": new_state.checked,
            "numerics/diverged": diverged,
            "numerics/divergence_events": new_state.events,
            "numerics/digest_gap": new_state.gap,
            "numerics/digest_checksum": lax.bitcast_convert_type(
                new_state.checksum, jnp.int32),
        }
        for i, g in enumerate(groups):
            metrics[f"numerics/diverged/{g}"] = div[i]
        return new_state, metrics


# ------------------------------------------------------------------ trend alarm
class TrendAlarm:
    """Low-side median+MAD trend alarm (PR-2 straggler discipline) over a
    bounded observation window — fires when a fresh value falls below
    ``median - mads·MAD`` of the PRIOR window (the fresh value never vouches
    for itself)."""

    def __init__(self, window: int = 64, mads: float = 6.0, min_n: int = 8,
                 mad_floor_rel: float = 0.01):
        self.window = int(window)
        self.mads = float(mads)
        self.min_n = int(min_n)
        self.mad_floor_rel = float(mad_floor_rel)
        self._vals: deque = deque(maxlen=self.window)
        self.alarms = 0

    def observe(self, value: float) -> bool:
        hist = list(self._vals)
        self._vals.append(float(value))
        if len(hist) < self.min_n:
            return False
        med = median(hist)
        mad = median(abs(v - med) for v in hist)
        mad = max(mad, self.mad_floor_rel * abs(med), 1e-9)
        fired = value < med - self.mads * mad
        if fired:
            self.alarms += 1
        return fired


# ------------------------------------------------------------------ observatory
def _registry():
    from deepspeed_tpu.telemetry import get_tracer

    return get_tracer().registry


class NumericsObservatory:
    """Process-global fidelity observer (same lifecycle discipline as
    ``collectives.observatory``: ``configure()`` resets, ``install()``
    attaches the live engine's profiler arm)."""

    def __init__(self):
        self.config = NumericsConfig()
        self._lock = threading.Lock()
        from deepspeed_tpu.telemetry.events import WarnOnceSet

        self._warn_once_set = WarnOnceSet(subsystem="numerics",
                                          default_kind="fidelity_warning")
        self.profiler_arm: Optional[Callable[..., None]] = None
        self.divergence_events_seen = 0  # host-side last-seen cumulative
        self.spec_accept_alarm = TrendAlarm()

    # ----------------------------------------------------------- configure
    @property
    def enabled(self) -> bool:
        return self.config.enabled

    def configure(self, config: Optional[NumericsConfig] = None,
                  **kwargs) -> "NumericsObservatory":
        with self._lock:
            cfg = (dc_replace(config, **kwargs) if config is not None
                   else NumericsConfig(**kwargs))
            self.config = cfg
            self._warn_once_set.reset()
            self.divergence_events_seen = 0
            self.spec_accept_alarm = TrendAlarm(
                window=cfg.spec_accept_window, mads=cfg.spec_accept_mads,
                min_n=cfg.spec_accept_min_n)
            # install() targets belong to the engine that configured us
            self.profiler_arm = None
        return self

    def install(self, profiler_arm: Optional[Callable] = None) -> None:
        if profiler_arm is not None:
            self.profiler_arm = profiler_arm

    def warn_once(self, key: str, msg: str) -> bool:
        """Log ``msg`` once per ``key`` per configure() epoch (shared
        warn-once helper: the first occurrence also lands on the typed
        event stream)."""
        return self._warn_once_set(key, msg, log=logger)

    # ----------------------------------------------------- EF residual gauges
    def note_ef_residuals(self, err_tree) -> Dict[str, float]:
        """Per-top-level-group L2 norms of the LoCo/1-bit error-feedback
        residuals (called on sampled steps with ``TrainState.comm_error``).
        A residual norm trending up means the wire is dropping more than
        the feedback loop is re-capturing."""
        if err_tree is None or not self.config.enabled:
            return {}
        sums: Dict[str, Any] = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(err_tree):
            if not jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
                continue
            g = _group_key(path)
            ss = leaf_sumsq(leaf)
            sums[g] = sums[g] + ss if g in sums else ss
        if not sums:
            return {}
        vals = jax.device_get({g: jnp.sqrt(s) for g, s in sums.items()})
        reg = _registry()
        out = {}
        for g, v in vals.items():
            out[g] = float(v)
            reg.gauge("numerics/ef_residual_norm", group=g).set(float(v))
        return out

    # ------------------------------------------------ divergence host plane
    def note_divergence_events(self, step: int, events_cum: int,
                               checksum: Optional[int] = None) -> int:
        """Fold the sentinel's carried cumulative event count into the host
        plane: publishes new events (counter + warning + profiler arm) and
        the fleet-visible digest checksum gauge. Returns the number of NEW
        events since the last call (0 = quiet)."""
        events_cum = int(events_cum)
        new = max(0, events_cum - self.divergence_events_seen)
        self.divergence_events_seen = max(self.divergence_events_seen,
                                          events_cum)
        reg = _registry()
        if checksum is not None:
            # exact in f64 for any uint32, so the heartbeat comparator is
            # bit-faithful cross-process
            reg.gauge("numerics/digest_checksum").set(
                float(int(checksum) & 0xFFFFFFFF))
        if new > 0:
            reg.counter("numerics/divergence_events").add(new)
            msg = (f"NUMERICS DIVERGENCE: cross-replica digest mismatch at "
                   f"step {step} ({new} new event(s), {events_cum} total) — "
                   f"dp/fsdp replicas no longer hold identical parameters")
            logger.warning(msg)
            from deepspeed_tpu.telemetry.events import emit_event

            emit_event("numerics", "divergence", msg, severity="critical",
                       labels={"new_events": new, "total": events_cum},
                       step=step,
                       dedup_key="numerics:divergence")
            if self.profiler_arm is not None:
                try:
                    self.profiler_arm(reason=f"numerics_divergence:{step}")
                except Exception:
                    pass
        return new

    # ------------------------------------------------------- serving probes
    def kv_dequant_probe(self, kv_quant: str, head_dim: int = 128,
                         vectors: int = 64, seed: int = 0) -> float:
        """Round-trip relative error of the paged-KV block quantizer on a
        gaussian payload shaped like ``vectors`` per-head KV rows."""
        from deepspeed_tpu.ops.quant import (
            fp8_block_dequant, fp8_block_math, int8_block_math)

        x = jnp.asarray(
            np.random.RandomState(seed).standard_normal((vectors, head_dim)),
            jnp.float32)
        if kv_quant == "int8":
            q, s = int8_block_math(x)
            y = q.astype(jnp.float32) * s
        elif kv_quant == "fp8":
            q, s = fp8_block_math(x)
            y = fp8_block_dequant(q, s)
        else:
            return 0.0
        rel = float(jax.device_get(
            jnp.sqrt(jnp.sum((x - y) ** 2)) /
            jnp.maximum(jnp.sqrt(jnp.sum(x * x)), 1e-12)))
        _registry().gauge("numerics/kv_dequant_rel_err",
                          dtype=kv_quant).set(rel)
        return rel

    def woq_matmul_probe(self, fmt: str, m: int = 8, k: int = 256,
                         n: int = 256, seed: int = 0) -> float:
        """Relative matmul error of a weight-only-quantized gaussian weight
        vs the fp32 reference (the number WOQ serving accuracy rides on)."""
        from deepspeed_tpu.inference import woq as woq_mod

        rs = np.random.RandomState(seed)
        w = jnp.asarray(rs.standard_normal((k, n)), jnp.float32)
        x = jnp.asarray(rs.standard_normal((m, k)), jnp.float32)
        qt = woq_mod._quantize_leaf(w, fmt)
        wq = qt.astype(jnp.float32) if hasattr(qt, "astype") else qt
        ref = x @ w
        got = x @ wq
        rel = float(jax.device_get(
            jnp.sqrt(jnp.sum((ref - got) ** 2)) /
            jnp.maximum(jnp.sqrt(jnp.sum(ref * ref)), 1e-12)))
        _registry().gauge("numerics/woq_matmul_rel_err", fmt=fmt).set(rel)
        return rel

    def note_spec_accept(self, rate: float) -> bool:
        """Feed one spec-decode acceptance-rate observation to the trend
        alarm; fires (returns True, counts, warns once per epoch) when the
        rate collapses below the PR-2 median−MADs band."""
        if not self.config.enabled:
            return False
        fired = self.spec_accept_alarm.observe(float(rate))
        if fired:
            _registry().counter("numerics/spec_accept_alarm").add(1)
            self.warn_once(
                "spec_accept",
                f"numerics: spec-decode acceptance rate {rate:.3f} fell "
                f"below the trailing median-MAD band "
                f"({self.spec_accept_alarm.alarms} alarm(s))")
        return fired


# ------------------------------------------------------------------- singleton
_observatory = NumericsObservatory()


def get_observatory() -> NumericsObservatory:
    return _observatory


def configure(config: Optional[NumericsConfig] = None,
              **kwargs) -> NumericsObservatory:
    return _observatory.configure(config, **kwargs)


def enabled() -> bool:
    return _observatory.enabled
