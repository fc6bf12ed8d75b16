"""Collective performance observatory: live hop timing, online cost-model
calibration, and selector drift detection.

The selector (``selector.py``) routes every facade collective from an
alpha-beta cost model or an offline ``comm/benchmark.py --sweep`` table —
and until now nothing ever checked whether the algorithm it picked is
actually the fastest one on the mesh it is running on. This module closes
that loop, GC3-style (PAPERS.md): schedules should be derived from
*measured* per-link costs, so the selector must observe its own decisions
in production runs and calibrate itself from what it sees.

Three legs, all host-side:

**Live hop timing.** Every routed facade collective registers its signature
at trace time (``note_route`` — op, algorithm, codec, backend, payload
bytes, world, plus a hop/wire census collected by ``algorithms._hop_span``
and the facade's ppermute/remote-DMA records inside ``trace_scope``). On
sampled steps (1-in-N, ``sample_every``) the observatory dispatches the
routed hop-scope program STANDALONE — the same ``jit(shard_map(...))``
probe shape and scalar-fetch sync fencing as ``benchmark._time_collective``,
host-clocked per dispatch — and feeds per-``(op, algorithm, codec,
backend, bytes-bucket, world)`` ``coll/hop_ms`` histograms and
``coll/achieved_gbps`` gauges. Because probes are their own dispatches,
the steady-state step program is untouched in EVERY mode: hop programs are
jaxpr-identical with the observatory on, off, or absent (pinned by test).
Works for ppermute, pallas remote-DMA, and fused-codec hops alike — the
probe runs whatever the signature routed.

**Online calibration.** Observed samples accumulate into the same versioned
row schema ``--sweep`` emits (``table.py``), EMA-merged so one noisy probe
cannot flip a decision, and persist to ``telemetry_out/coll_table.json`` —
which warm-starts the selector's measured mode on the next run (the engine
passes it as the decision table when no explicit one is configured). A
least-squares fit over the accumulated samples refits the per-backend
alpha/beta constants (``selector.calibrate``; ``coll/alpha_us`` /
``coll/beta_gbps`` gauges) so model mode improves even without a sweep.

**Drift detection.** Each probed routed signature reconciles its observed
latency against the selector's predicted cost: ``coll/model_ratio`` gauge,
a LOUD warning past ``drift_ratio`` (either direction), a ``coll:drift``
trace instant, and — when the engine wired one — arming the PR-7
anomaly-profiler capture so the next steps leave a device trace. The
trace-time wire census additionally feeds the ProgramRegistry: every
captured program reconciles the wire bytes the selector's routing traced
against the collective bytes extracted from its compiled HLO
(``coll/wire_bytes_ratio``; ``telemetry/programs.py``).

Process-global like the selector and the tracer; engines configure it from
the ``collectives.observe`` config block. Disabled (the default) every hook
is one attribute check and nothing is allocated.
"""

from __future__ import annotations

import contextlib
import math
import os
import threading
from collections import deque
from dataclasses import dataclass, replace as dc_replace
from typing import Callable, Dict, List, Optional, Tuple

from deepspeed_tpu.utils.logging import logger

# probe signatures larger than this are registered but never timed (a
# multi-GB all-gather probe would stall the run it is observing)
_MAX_SIGNATURES = 64


@dataclass
class ObservatoryConfig:
    """Tunables for the observatory (the ``collectives.observe`` config
    block mirrors these)."""

    enabled: bool = False
    sample_every: int = 16          # 1-in-N steps runs probe work; <=0 never
    probes_per_sample: int = 1      # timed probes per sampled step
    iters: int = 1                  # timed iterations per probe
    warmup: int = 1                 # warmup iterations (first pays compile)
    probe_alternatives: bool = True  # also time candidate algorithms
    # compile new probe programs on a background thread and only TIME them
    # once warm: a multi-second XLA compile must never stall train_batch
    # (the <2% overhead bound covers steady state, not compiles). False =
    # synchronous compile inside the sampled step — deterministic, for
    # tests and explicit tooling; sample_now() always compiles in line.
    async_compile: bool = True
    table_path: Optional[str] = None  # default: <telemetry dir>/coll_table.json
    persist: bool = True
    ema: float = 0.25               # online EMA weight for table merges
    drift_ratio: float = 3.0        # observed/predicted past this ⇒ drift
    refit_every: int = 8            # refit alpha/beta every N merged samples
    # per-refit forgetting factor on the fit statistics (1.0 = never
    # forget): without decay a long run's history outweighs a regime
    # change — an interconnect slowdown would take O(history) samples to
    # show in the calibrated constants
    fit_decay: float = 0.5
    max_probe_mb: float = 64.0      # skip timing payloads above this
    max_programs: int = 32          # probe program cache bound


@dataclass
class RouteInfo:
    """One routed facade signature, as registered at trace time."""

    op: str
    algorithm: str
    codec: str
    backend: str
    axis: str
    nbytes: int        # per-device payload bytes (the selector's query)
    itemsize: int
    world: int
    dtype: str
    block_size: Optional[int] = None
    hops: int = 0        # trace-time hop census (0 until a trace completes)
    wire_bytes: int = 0  # per-trace hop wire bytes (census)
    routes: int = 0      # how many traces registered this signature
    probes: int = 0      # how many timed probes ran for it


class _ScopeState:
    __slots__ = ("key", "hops", "wire")

    def __init__(self, key):
        self.key = key
        self.hops = 0
        self.wire = 0


def _backend_of(algorithm: str) -> str:
    from deepspeed_tpu.collectives.pallas_backend import hop_backend

    return hop_backend(algorithm)


def _bus_factor(op: str, n: int) -> float:
    from deepspeed_tpu.comm.comm import CommsLogger

    return CommsLogger._bus_factor(op, n)


def model_terms(op: str, algorithm: str, codec: str, nbytes: int,
                world: int, itemsize: int = 4,
                block_size: Optional[int] = None) -> Tuple[int, float]:
    """(hops, wire_mb) regressors the alpha/beta refit fits observed
    latencies against — delegates to ``selector.model_terms`` so they are
    BY CONSTRUCTION the same terms ``estimate_us`` charges."""
    from deepspeed_tpu.collectives import selector

    return selector.model_terms(op, algorithm, codec, nbytes, world,
                                itemsize, block_size)


class CollectiveObservatory:
    """Process-global observer of routed collectives (see module doc)."""

    def __init__(self):
        self.config = ObservatoryConfig()
        self._lock = threading.Lock()
        # shared warn-once helper (telemetry/events.py): its OWN lock —
        # callers (note_route's capacity branch) may already hold the
        # non-reentrant self._lock — and every first warning also lands on
        # the typed event stream
        from deepspeed_tpu.telemetry.events import WarnOnceSet

        self._warn_once_set = WarnOnceSet(subsystem="coll",
                                          default_kind="observatory_warning")
        self._tls = threading.local()
        self._routes: Dict[Tuple, RouteInfo] = {}
        self._mesh = None
        self.profiler_arm: Optional[Callable[..., None]] = None
        self._steps = 0
        self._merged_samples = 0
        self._pending_program_wire = 0
        self._probe_queue: deque = deque()
        # (op, alg, codec, axis, elems, dtype, block) -> [f, state]; state
        # is "cold" (never dispatched), "warming" (background compile in
        # flight), "warm" (timable), or "failed". Entries hold the jitted
        # fn only — payloads live solely in _payload_cache so its byte-cap
        # eviction actually frees device memory
        self._probe_cache: Dict[Tuple, List] = {}
        # device payloads shared ACROSS probe programs: every candidate of
        # a signature times the same (elems, dtype, axis) array — caching
        # per program would pin up to max_programs full-size duplicates
        self._payload_cache: Dict[Tuple, object] = {}
        self._warm_queue: deque = deque()
        self._warm_thread = None
        self._table_rows: List[dict] = []
        # per-backend running sufficient statistics of the alpha/beta fit:
        # [sum h*h, sum h*w, sum w*w, sum h*t, sum w*t, n] — O(1) memory
        # and refit cost no matter how long the run observes
        self._fit_stats: Dict[str, List[float]] = {}
        self.calibration: Dict[str, Tuple[float, float]] = {}
        self.drift_events = 0
        # the ONE timing idiom (bench + sweep + probes), resolved lazily at
        # first probe; monkeypatchable in tests to inject a slow hop
        # without slowing the suite
        self._timer: Optional[Callable] = None

    # ----------------------------------------------------------- configure
    @property
    def enabled(self) -> bool:
        return self.config.enabled

    def configure(self, config: Optional[ObservatoryConfig] = None,
                  **kwargs) -> "CollectiveObservatory":
        """Install tunables and reset accumulated state (process-global,
        same lifecycle as ``selector.configure``)."""
        with self._lock:
            cfg = (dc_replace(config, **kwargs) if config is not None
                   else ObservatoryConfig(**kwargs))
            self.config = cfg
            self._routes.clear()
            self._probe_queue.clear()
            self._probe_cache.clear()
            self._payload_cache.clear()
            self._warm_queue.clear()
            self._table_rows = []
            self._fit_stats = {}
            self.calibration = {}
            self._steps = 0
            self._merged_samples = 0
            self._pending_program_wire = 0
            self.drift_events = 0
            self._warn_once_set.reset()
            self._timer = None  # drop any injected test timer with the state
            # install() targets belong to the engine that configured us:
            # keeping a torn-down engine's mesh or diagnostics arm callable
            # would probe a dead mesh / arm a dead profiler (and pin its
            # object graph) from the next engine's drift events
            self._mesh = None
            self.profiler_arm = None
        if cfg.enabled and (cfg.persist or cfg.table_path):
            # warm-load the RESOLVED path (explicit or the default): the
            # first persist() must merge into prior runs' rows, not clobber
            # signatures this run happens not to re-probe (persist=False
            # with no explicit path observes in-memory only — nothing to
            # carry over)
            self._load_existing_table(self.table_path())
        return self

    def install(self, mesh=None, profiler_arm: Optional[Callable] = None) -> None:
        """Attach the live mesh probes run on (and, optionally, the
        diagnostics profiler-capture ``arm`` callable drift fires)."""
        if mesh is not None:
            self._mesh = mesh
        if profiler_arm is not None:
            self.profiler_arm = profiler_arm

    def table_path(self) -> str:
        return self.config.table_path or default_table_path()

    def _load_existing_table(self, path: str) -> None:
        """Warm-start the online table from a previous run's persisted rows
        (EMA continuity — a restart must not forget what it measured)."""
        from deepspeed_tpu.collectives import table as table_mod

        try:
            rows = table_mod.load_table(path)
        except (OSError, ValueError):
            return
        with self._lock:
            self._table_rows = rows

    # -------------------------------------------------- trace-time hooks
    def note_route(self, op: str, algorithm: str, codec: str, nbytes: int,
                   itemsize: int, world: int, axis, dtype: str,
                   block_size: Optional[int] = None):
        """Register one routed facade collective (called at trace time by
        ``comm.py``'s routed branches). Returns a scope context collecting
        the hop/wire census of this trace; the no-op context when disabled,
        unprobeable (tuple axis), or at capacity."""
        if not self.config.enabled:
            return contextlib.nullcontext()
        if getattr(self._tls, "probing", False):
            # the probe programs route through the same facade: observing
            # them would register phantom signatures (and feed back into the
            # probe queue forever)
            return contextlib.nullcontext()
        if isinstance(axis, (tuple, list)):
            if len(axis) != 1:
                return contextlib.nullcontext()  # hierarchical: unprobeable
            axis = axis[0]
        backend = _backend_of(algorithm)
        if backend == "pallas":
            from deepspeed_tpu.collectives import pallas_backend

            if not pallas_backend.available():
                # interpret-mode pallas hops: timings would poison the
                # table (same rule as the sweep) — observe nothing
                return contextlib.nullcontext()
        key = (op, algorithm, codec, backend, _bytes_bucket(nbytes),
               int(world), str(axis))
        with self._lock:
            info = self._routes.get(key)
            if info is None:
                if len(self._routes) >= _MAX_SIGNATURES:
                    self._warn_once(
                        "routes",
                        f"collectives observatory: signature capacity "
                        f"({_MAX_SIGNATURES}) reached; further routed "
                        "signatures are not observed")
                    return contextlib.nullcontext()
                info = self._routes[key] = RouteInfo(
                    op=op, algorithm=algorithm, codec=codec, backend=backend,
                    axis=str(axis), nbytes=int(nbytes), itemsize=int(itemsize),
                    world=int(world), dtype=str(dtype), block_size=block_size)
                self._probe_queue.extend(
                    (key, a, c) for a, c in self._candidates(info))
            info.routes += 1
        return self._scope(key)

    @contextlib.contextmanager
    def _scope(self, key):
        prev = getattr(self._tls, "scope", None)
        state = _ScopeState(key)
        self._tls.scope = state
        try:
            yield
        finally:
            self._tls.scope = prev
            with self._lock:
                info = self._routes.get(key)
                if info is not None:
                    # census SETS (idempotent across retraces), never adds
                    info.hops = state.hops or info.hops
                    info.wire_bytes = state.wire or info.wire_bytes
                self._pending_program_wire += state.wire

    def on_hop(self) -> None:
        """One hop traced inside an active scope (``algorithms._hop_span``)."""
        s = getattr(self._tls, "scope", None)
        if s is not None:
            s.hops += 1

    def on_wire(self, nbytes: int) -> None:
        """Wire bytes of one traced hop transfer (the facade's ppermute /
        remote-DMA records)."""
        s = getattr(self._tls, "scope", None)
        if s is not None:
            s.wire += int(nbytes)

    def drain_program_wire(self) -> int:
        """Routed-collective wire bytes traced since the last captured
        program — the ProgramRegistry attributes them to the program it
        just captured (sequential trace→compile makes this exact for the
        engines' build order; concurrent tracers would smear, documented)."""
        with self._lock:
            n = self._pending_program_wire
            self._pending_program_wire = 0
            return n

    # -------------------------------------------------------- step sampling
    def on_step(self, step: Optional[int] = None) -> int:
        """Per-step hook (engine ``train_batch``): on sampled steps, run up
        to ``probes_per_sample`` timed probes. Returns probes run."""
        if not self.config.enabled:
            return 0
        self._steps += 1
        n = self.config.sample_every
        if n <= 0:
            # sampling off (registration/census stay live) — a zero must
            # not read as "probe every step and blow the overhead bound"
            return 0
        if n > 1 and (self._steps % n):
            return 0
        ran = 0
        for _ in range(max(self.config.probes_per_sample, 1)):
            item = self._next_probe()
            if item is None:
                break
            if self._run_probe(*item):
                ran += 1
        if ran and self.config.persist:
            self.persist()
        return ran

    def sample_now(self) -> int:
        """Force one full probe round regardless of cadence (bench warmup,
        tools) — compiles in line: an explicit call IS the warmup."""
        if not self.config.enabled:
            return 0
        ran = 0
        while True:
            item = self._next_probe(refill=False)
            if item is None:
                break
            if self._run_probe(*item, sync_compile=True):
                ran += 1
        if ran and self.config.persist:
            self.persist()
        return ran

    def _candidates(self, info: RouteInfo) -> List[Tuple[str, str]]:
        """(algorithm, codec) pairs worth timing for one signature: the
        routed pair first (drift detection), then — when
        ``probe_alternatives`` — the sweep's candidate enumeration
        (``benchmark.candidate_pairs``, THE shared gate logic so online
        rows stay comparable with sweep rows), so the online table
        accumulates enough coverage for measured mode to CHANGE a
        decision, not just confirm one."""
        out = [(info.algorithm, info.codec)]
        if not self.config.probe_alternatives:
            return out
        from deepspeed_tpu.comm.benchmark import candidate_pairs

        for pair in candidate_pairs(info.world,
                                    tuple(dict.fromkeys((info.codec, "none"))),
                                    op=info.op, axis=info.axis):
            if pair not in out:
                out.append(pair)
        return out

    def _next_probe(self, refill: bool = True):
        with self._lock:
            if not self._probe_queue:
                if not refill:
                    return None
                # every pending probe ran: start a fresh round so steady
                # state keeps re-measuring (EMA tracks slow drift)
                for key, info in self._routes.items():
                    self._probe_queue.extend(
                        (key, a, c) for a, c in self._candidates(info))
                if not self._probe_queue:
                    return None
            key, alg, cd = self._probe_queue.popleft()
            info = self._routes.get(key)
        if info is None:
            return None
        return key, info, alg, cd

    # ------------------------------------------------------------- probing
    def _probe_payload(self, mesh, axis: str, elems: int, dtype):
        """The device payload probes time against — ONE array per
        (elems, dtype, axis), shared by every candidate program of a
        signature (per-program copies would pin max_programs full-size
        duplicates in device memory). The cache is BYTE-capped (~2x
        ``max_probe_mb`` per device, FIFO eviction): an observer must not
        pin GBs of resident payloads next to model state — an evicted
        shape just pays one host->device transfer on its next probe."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        pkey = (axis, elems, str(dtype))
        with self._lock:
            x = self._payload_cache.get(pkey)
        if x is not None:
            return x
        x = jax.device_put(jnp.ones((elems,), dtype),
                           NamedSharding(mesh, P(axis)))
        nbytes = elems * jnp.dtype(dtype).itemsize
        budget = 2 * self.config.max_probe_mb * 1e6 * max(
            int(mesh.shape[axis]), 1)
        # cache mutation under the lock: the train thread and the warm
        # worker both come through here, and an unguarded evict/iterate
        # would race an insert ("dict changed size during iteration")
        with self._lock:
            cur = self._payload_cache.get(pkey)
            if cur is not None:
                return cur  # the other thread won the transfer
            held = sum(k[1] * jnp.dtype(k[2]).itemsize
                       for k in self._payload_cache)
            while self._payload_cache and held + nbytes > budget:
                k = next(iter(self._payload_cache))
                self._payload_cache.pop(k)
                held -= k[1] * jnp.dtype(k[2]).itemsize
            self._payload_cache[pkey] = x
        return x

    def _probe_program(self, info: RouteInfo, algorithm: str, codec: str):
        """The cache entry ``[f, x, elems, state]`` for one probe — the
        standalone hop-scope program: the same ``jit(shard_map(facade
        call))`` shape the sweep measures, on the live mesh."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.comm.benchmark import (_algorithmic_fn,
                                                  _collective_fn, probe_elems)
        from deepspeed_tpu.utils.compat import shard_map as _shard_map

        mesh = self._mesh
        if mesh is None or info.axis not in mesh.axis_names:
            return None
        n = int(mesh.shape[info.axis])
        if n != info.world:
            return None  # stale signature from a previous mesh
        dtype = jnp.dtype(info.dtype) if info.dtype != "unknown" else jnp.float32
        itemsize = dtype.itemsize
        # per-device payload -> global elements, rounded to the sweep's
        # shared base so reduce_scatter shards stay divisible+lane-aligned
        # and probe rows land on the same shapes a sweep would measure
        elems = probe_elems(n, max(int(info.nbytes // itemsize), 1) * n)
        if elems * itemsize / n > self.config.max_probe_mb * 1e6:
            return None
        key = (info.op, algorithm, codec, info.axis, elems, str(dtype),
               info.block_size)
        with self._lock:
            cached = self._probe_cache.get(key)
            if cached is not None:
                return key, cached
            if len(self._probe_cache) >= self.config.max_programs:
                full = True
            else:
                full = False
        if full:
            self._warn_once(
                "programs",
                f"collectives observatory: probe program cache full "
                f"({self.config.max_programs}); new signatures are not timed")
            return None
        fn = (_collective_fn(info.op, info.axis) if algorithm == "lax" else
              _algorithmic_fn(info.op, info.axis, algorithm, codec,
                              info.block_size or 2048))
        out_spec = P() if info.op == "all_reduce" else P(info.axis)
        f = jax.jit(_shard_map(fn, mesh=mesh, in_specs=P(info.axis),
                               out_specs=out_spec, check_vma=False))
        entry = [f, "cold"]
        with self._lock:
            entry = self._probe_cache.setdefault(key, entry)
        return key, entry

    # ----------------------------------------------- background compile
    def _schedule_warm(self, key) -> None:
        """Queue a cold probe program for background compile + first
        dispatch; a daemon worker pays the (multi-second) XLA compile OFF
        the train loop, and the probe is only TIMED once warm."""
        with self._lock:
            entry = self._probe_cache.get(key)
            if entry is None or entry[1] != "cold":
                return
            entry[1] = "warming"
            self._warm_queue.append(key)
            # handshake against the worker's exit: the worker nulls
            # _warm_thread (under this lock) BEFORE returning on an empty
            # queue, so either it sees this append or we see None and spawn
            # — an is_alive() check would race thread teardown and strand
            # the entry in "warming" forever
            if self._warm_thread is None:
                self._warm_thread = threading.Thread(
                    target=self._warm_worker, name="coll-observatory-warm",
                    daemon=True)
                self._warm_thread.start()

    def _warm_worker(self) -> None:
        import numpy as np
        import jax

        while True:
            with self._lock:
                if not self._warm_queue:
                    self._warm_thread = None  # exit handshake (see above)
                    return
                key = self._warm_queue.popleft()
                entry = self._probe_cache.get(key)
            if entry is None:
                continue
            f = entry[0]
            try:
                mesh = self._mesh
                if mesh is None:
                    continue  # configure() tore the install down mid-warm
                # key = (op, alg, codec, axis, elems, dtype, block)
                x = self._probe_payload(mesh, key[3], key[4], key[5])
                self._tls.probing = True  # this thread's traces too
                try:
                    r = f(x)
                finally:
                    self._tls.probing = False
                jax.block_until_ready(r)
                entry[1] = "warm"
            except Exception as e:  # noqa: BLE001 — must not kill the worker
                entry[1] = "failed"
                self._warn_once(("warm", key[:3]),
                                f"collectives observatory: probe compile "
                                f"failed for {key[0]}/{key[1]}/{key[2]}: {e}")

    def _run_probe(self, key, info: RouteInfo, algorithm: str, codec: str,
                   sync_compile: bool = False) -> bool:
        cfg = self.config
        self._tls.probing = True  # probe traces must not self-register
        try:
            try:
                prog = self._probe_program(info, algorithm, codec)
            except Exception as e:  # noqa: BLE001 — observing must not break the run
                self._warn_once(("build", algorithm, codec),
                                f"collectives observatory: probe build failed "
                                f"for {info.op}/{algorithm}/{codec}: {e}")
                return False
            if prog is None:
                return False
            pkey, entry = prog
            f, state = entry
            if state == "failed" or state == "warming":
                return False
            if state == "cold" and cfg.async_compile and not sync_compile:
                # never pay an XLA compile inside train_batch: warm on the
                # background worker; the re-arming queue brings this pair
                # back once it is timable
                self._schedule_warm(pkey)
                return False
            try:
                # payload fetch + timing in ONE guard: a RESOURCE_EXHAUSTED
                # device_put (or a configure() tearing the mesh down
                # between checks) must degrade to a warning, never abort
                # the train step that sampled this probe
                mesh = self._mesh
                if mesh is None:
                    return False
                # key = (op, alg, codec, axis, elems, dtype, block)
                elems = pkey[4]
                x = self._probe_payload(mesh, pkey[3], elems, pkey[5])
                if self._timer is None:
                    from deepspeed_tpu.comm.benchmark import _time_collective

                    self._timer = _time_collective
                dt = self._timer(f, x, cfg.iters, cfg.warmup)
                entry[1] = "warm"
            except Exception as e:  # noqa: BLE001
                self._warn_once(("time", algorithm, codec),
                                f"collectives observatory: probe failed for "
                                f"{info.op}/{algorithm}/{codec}: {e}")
                return False
        finally:
            self._tls.probing = False
        try:
            itemsize = max(int(x.dtype.itemsize), 1)
            size_mb = elems * itemsize / info.world / 1e6
            routed = (algorithm == info.algorithm and codec == info.codec)
            # the routed signature's own hop census beats the model's count
            hops = info.hops if (routed and info.hops) else None
            info.probes += 1
            self.record_sample(
                op=info.op, algorithm=algorithm, codec=codec,
                backend=_backend_of(algorithm), world=info.world,
                size_mb=size_mb, latency_ms=dt * 1e3, itemsize=itemsize,
                bucket=_bytes_bucket(info.nbytes), hops=hops,
                check_drift=routed, block_size=info.block_size)
        except Exception as e:  # noqa: BLE001 — same contract as above
            self._warn_once(("record", algorithm, codec),
                            f"collectives observatory: sample recording "
                            f"failed for {info.op}/{algorithm}/{codec}: {e}")
            return False
        return True

    # ------------------------------------------------------------- samples
    def record_sample(self, *, op: str, algorithm: str, codec: str,
                      backend: str, world: int, size_mb: float,
                      latency_ms: float, itemsize: int = 4,
                      bucket: Optional[int] = None, hops: Optional[int] = None,
                      check_drift: bool = False,
                      block_size: Optional[int] = None,
                      merge: bool = True) -> dict:
        """Fold one observed latency into the observatory: metrics, online
        table EMA merge, refit accumulation, and (for routed signatures)
        drift reconciliation. The probe path lands here; tests and external
        timers may call it directly (``merge=False`` observes without
        touching the table — the report tool's injected-drift check)."""
        from deepspeed_tpu.collectives import table as table_mod

        nbytes = size_mb * 1e6
        bucket = bucket if bucket is not None else _bytes_bucket(int(nbytes))
        payload_global = nbytes * world
        dt = latency_ms / 1e3
        busbw = (payload_global / dt) * _bus_factor(op, world) if dt > 0 else 0.0
        if hops is None:
            try:
                hops, _ = model_terms(op, algorithm, codec, int(nbytes),
                                      world, itemsize, block_size)
            except ValueError:
                hops = max(world - 1, 1)
        from deepspeed_tpu.telemetry.fleet import get_identity

        row = {
            "op": op, "world": int(world), "size_mb": round(size_mb, 4),
            "algorithm": algorithm, "codec": codec, "backend": backend,
            "latency_ms": round(latency_ms, 4),
            "busbw_gbps": round(busbw / 1e9, 3),
            "itemsize": int(itemsize), "samples": 1,
            # process identity stamp (fleet federation provenance; not part
            # of row_key — the same signature measured on two processes
            # still EMA-merges into one row at the collector)
            "proc": get_identity().key(),
        }
        self._publish_sample(row, hops, bucket)
        if check_drift:
            # BEFORE the merge: the prediction must come from what the
            # table/calibration said prior to this observation, not from a
            # row this very sample just dragged toward itself
            self._check_drift(op, algorithm, codec, backend, int(nbytes),
                              world, itemsize, latency_ms, bucket)
        if merge:
            with self._lock:
                self._table_rows = table_mod.merge_rows(
                    self._table_rows, [row], ema=self.config.ema)
                self._merged_samples += 1
                refit_due = (self.config.refit_every > 0 and
                             self._merged_samples % self.config.refit_every == 0)
            self._note_fit_sample(op, algorithm, codec, backend, int(nbytes),
                                  world, itemsize, latency_ms, block_size)
            if refit_due:
                self.refit()
        return row

    def _publish_sample(self, row: dict, hops: int, bucket: int) -> None:
        from deepspeed_tpu import telemetry

        tracer = telemetry.get_tracer()
        if not tracer.enabled:
            return
        labels = dict(op=row["op"], algorithm=row["algorithm"],
                      codec=row["codec"], backend=row["backend"],
                      bucket=bucket, world=row["world"])
        reg = tracer.registry
        reg.histogram("coll/hop_ms", **labels).observe(
            row["latency_ms"] / max(hops, 1))
        reg.gauge("coll/achieved_gbps", **labels).set(row["busbw_gbps"])
        reg.counter("coll/probes").add(1.0)
        with self._lock:
            reg.gauge("coll/table_rows").set(float(len(self._table_rows)))

    # --------------------------------------------------------------- drift
    def _predicted_us(self, op: str, algorithm: str, codec: str, backend: str,
                      nbytes: int, world: int, itemsize: int
                      ) -> Optional[float]:
        """The trusted cost for this signature, or ``None`` when no
        TRUSTWORTHY prediction exists yet. A drift alarm against the static
        (hand-set) alpha/beta would fire on every mesh whose constants were
        never tuned — noise, not drift — so predictions count only once
        they are measured or calibrated. A measured row counts only at a
        COMPARABLE size (within 2x of the query): the selector's
        nearest-by-log-distance routing may legitimately answer a 32 MB
        query from a 0.25 MB row, but that row's raw latency is no
        prediction for the 32 MB payload and would alarm forever."""
        size_mb = nbytes / 1e6
        with self._lock:
            rows = [r for r in self._table_rows
                    if r.get("op") == op and r.get("algorithm") == algorithm
                    and r.get("codec", "none") == codec
                    and (r.get("backend") or backend) == backend
                    and int(r.get("world", 0)) == world
                    and float(r.get("size_mb", 0.0)) > 0]
            calibrated = backend in self.calibration
        if rows:
            best = min(rows, key=lambda r: abs(math.log(
                float(r["size_mb"]) / size_mb)) if size_mb > 0 else 0.0)
            ratio = float(best["size_mb"]) / size_mb if size_mb > 0 else 0.0
            if 0.5 <= ratio <= 2.0:
                return float(best["latency_ms"]) * 1e3
        if not calibrated:
            return None
        from deepspeed_tpu.collectives import selector

        try:
            return selector.estimate_us(op, algorithm, codec, nbytes, world,
                                        itemsize=itemsize)
        except ValueError:
            return None

    def _check_drift(self, op, algorithm, codec, backend, nbytes, world,
                     itemsize, latency_ms, bucket) -> None:
        predicted = self._predicted_us(op, algorithm, codec, backend, nbytes,
                                       world, itemsize)
        if not predicted or predicted <= 0:
            return
        ratio = (latency_ms * 1e3) / predicted
        from deepspeed_tpu import telemetry

        tracer = telemetry.get_tracer()
        if tracer.enabled:
            tracer.registry.gauge(
                "coll/model_ratio", op=op, algorithm=algorithm, codec=codec,
                backend=backend, bucket=bucket, world=world).set(ratio)
        thresh = self.config.drift_ratio
        if thresh <= 0 or (1.0 / thresh) <= ratio <= thresh:
            return
        self.drift_events += 1
        direction = "slower" if ratio > 1 else "faster"
        msg = (
            f"COLLECTIVE DRIFT: {op} routed {algorithm}/{codec} "
            f"({backend}, {nbytes}B x{world}) measured {latency_ms:.3f} ms "
            f"vs predicted {predicted / 1e3:.3f} ms — {ratio:.1f}x "
            f"{direction} than the cost model (threshold {thresh}x). The "
            "selector may be mis-routing this mesh; re-sweep or let the "
            "observatory's refit converge. Arming profiler capture.")
        logger.warning(msg)
        from deepspeed_tpu.telemetry.events import emit_event

        emit_event("coll", "drift", msg, severity="warn",
                   labels={"op": op, "algorithm": algorithm, "codec": codec,
                           "backend": backend},
                   dedup_key=f"coll:drift:{op}/{algorithm}/{codec}/{backend}")
        if tracer.enabled:
            tracer.registry.counter("coll/drift_events").add(1.0)
            tracer.instant("coll:drift", cat="coll", op=op,
                           algorithm=algorithm, codec=codec, backend=backend,
                           bytes=int(nbytes), world=int(world),
                           observed_ms=round(latency_ms, 4),
                           predicted_ms=round(predicted / 1e3, 4),
                           ratio=round(ratio, 2))
        if self.profiler_arm is not None:
            try:
                self.profiler_arm(reason=f"coll_drift:{op}/{algorithm}")
            except Exception as e:  # noqa: BLE001
                logger.warning(f"collectives observatory: profiler arm "
                               f"failed: {e}")

    # --------------------------------------------------------------- refit
    def _note_fit_sample(self, op, algorithm, codec, backend, nbytes, world,
                         itemsize, latency_ms, block_size) -> None:
        try:
            hops, wire_mb = model_terms(op, algorithm, codec, nbytes, world,
                                        itemsize, block_size)
        except ValueError:
            return
        h, w, t = float(hops), float(wire_mb), latency_ms * 1e3
        with self._lock:
            s = self._fit_stats.setdefault(backend, [0.0] * 6)
            s[0] += h * h
            s[1] += h * w
            s[2] += w * w
            s[3] += h * t
            s[4] += w * t
            s[5] += 1.0

    def refit(self) -> Dict[str, Tuple[float, float]]:
        """Least-squares (alpha, beta) per backend over the accumulated
        samples: ``latency_us ~= hops * alpha + wire_mb * beta``; pushed
        into the selector (``selector.calibrate``) so model mode re-costs
        future decisions from what this mesh actually measured."""
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.collectives import selector

        with self._lock:
            groups = {b: list(s) for b, s in self._fit_stats.items()}
        out: Dict[str, Tuple[float, float]] = {}
        for backend, stats in groups.items():
            fit = _fit_alpha_beta(stats)
            if fit is None:
                continue
            alpha, beta = fit
            out[backend] = (alpha, beta)
            selector.calibrate(backend, alpha_us=alpha, beta_us_per_mb=beta)
            tracer = telemetry.get_tracer()
            if tracer.enabled:
                tracer.registry.gauge("coll/alpha_us", backend=backend).set(alpha)
                # effective link bandwidth the beta term implies
                tracer.registry.gauge("coll/beta_gbps", backend=backend).set(
                    1e3 / beta if beta > 0 else 0.0)
        if out:
            with self._lock:
                self.calibration.update(out)
        d = self.config.fit_decay
        if 0.0 < d < 1.0:
            # exponential forgetting so calibration tracks regime changes:
            # history halves (at the default) every refit instead of
            # outweighing fresh samples forever
            with self._lock:
                for s in self._fit_stats.values():
                    for i in range(len(s)):
                        s[i] *= d
        return out

    # ------------------------------------------------------------- persist
    def table_rows(self) -> List[dict]:
        with self._lock:
            return list(self._table_rows)

    def persist(self, path: Optional[str] = None) -> Optional[str]:
        """Write the online table (versioned envelope) so the NEXT run's
        selector warm-starts measured mode from what this run observed."""
        from deepspeed_tpu.collectives import table as table_mod

        with self._lock:
            rows = list(self._table_rows)
            calib = {b: {"alpha_us": round(a, 4), "beta_us_per_mb": round(bt, 4)}
                     for b, (a, bt) in self.calibration.items()}
        if not rows:
            return None
        path = path or self.table_path()
        from deepspeed_tpu.telemetry.fleet import get_identity

        try:
            return table_mod.write_table(
                path, rows, source="online",
                extra={"calibration": calib,
                       "identity": get_identity().to_dict()})
        except OSError as e:
            self._warn_once("persist",
                            f"collectives observatory: cannot persist table "
                            f"to {path!r}: {e}")
            return None

    # -------------------------------------------------------------- report
    def summary(self) -> dict:
        with self._lock:
            return {
                "routes": len(self._routes),
                "table_rows": len(self._table_rows),
                "merged_samples": self._merged_samples,
                "drift_events": self.drift_events,
                "calibration": {b: list(v) for b, v in self.calibration.items()},
                "steps": self._steps,
            }

    def routes(self) -> List[RouteInfo]:
        with self._lock:
            return list(self._routes.values())

    def _warn_once(self, key, msg: str) -> None:
        self._warn_once_set(str(key), msg, log=logger)


def _fit_alpha_beta(stats: List[float]) -> Optional[Tuple[float, float]]:
    """Closed-form 2-parameter least squares of ``t = h*a + w*b`` from the
    running sufficient statistics ``[sum h², sum hw, sum w², sum ht,
    sum wt, n]``, with non-negativity clamps; ``None`` when the design is
    degenerate (fewer than 2 samples, or no spread in either regressor)."""
    shh, shw, sww, sht, swt, n = stats
    if n < 2:
        return None
    if shh == 0.0:
        # hop-free samples (the lax baseline): only beta is identifiable
        if sww == 0.0:
            return None
        return 0.0, max(swt / sww, 1e-9)
    det = shh * sww - shw * shw
    if abs(det) < 1e-12 * max(shh * sww, 1.0):
        # collinear design: fit alpha alone against the hop count
        return max(sht / shh, 1e-9), 0.0
    alpha = (sht * sww - swt * shw) / det
    beta = (swt * shh - sht * shw) / det
    if alpha < 0.0:
        # clamp and refit the other term unconstrained
        alpha = 0.0
        beta = max(swt / sww, 1e-9) if sww else 0.0
    elif beta < 0.0:
        beta = 0.0
        alpha = max(sht / shh, 1e-9)
    return float(alpha), float(beta)


def _bytes_bucket(nbytes: int) -> int:
    from deepspeed_tpu.collectives import selector

    return selector._bytes_bucket(nbytes)


def default_table_path() -> str:
    """Where the online table lives when no explicit path is configured —
    a function of the telemetry output dir only, never of the (process-
    global, possibly another engine's) observatory config. On a multi-
    process mesh each process gets its OWN file (``coll_table.p<N>.json``
    for process_index > 0): N observatory instances sharing one path would
    clobber each other's atomic writes; the fleet collector
    (``telemetry/collector.py``) is the one place per-process tables merge
    (``table.merge_rows``) into a mesh-wide view."""
    from deepspeed_tpu.telemetry import default_output_dir
    from deepspeed_tpu.telemetry.fleet import get_identity

    idx = get_identity().process_index
    name = "coll_table.json" if idx == 0 else f"coll_table.p{idx}.json"
    return os.path.join(default_output_dir(), name)


# ------------------------------------------------------------- module API

_observatory = CollectiveObservatory()


def get_observatory() -> CollectiveObservatory:
    return _observatory


def configure(config: Optional[ObservatoryConfig] = None,
              **kwargs) -> CollectiveObservatory:
    return _observatory.configure(config, **kwargs)


def enabled() -> bool:
    return _observatory.config.enabled


def note_route(op: str, algorithm: str, codec: str, nbytes: int,
               itemsize: int, world: int, axis, dtype: str,
               block_size: Optional[int] = None):
    return _observatory.note_route(op, algorithm, codec, nbytes, itemsize,
                                   world, axis, dtype, block_size)


def on_hop() -> None:
    _observatory.on_hop()


def on_wire(nbytes: int) -> None:
    _observatory.on_wire(nbytes)


def drain_program_wire() -> int:
    return _observatory.drain_program_wire()
