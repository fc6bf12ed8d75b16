"""Algorithm + codec selection: alpha-beta cost model and measured mode.

Reference analog: NCCL's tuner (latency/bandwidth tables per algorithm and
protocol picking tree vs ring per message size) and DeepSpeed's autotuner.
Here the model is the classic alpha-beta point-to-point model::

    T(alg) = hops * alpha  +  wire_bytes_on_link * beta

with per-algorithm hop counts and busiest-link byte volumes (ring moves
2(n-1)/n * S for all-reduce in n-1+n-1 serial hops; recursive
halving/doubling moves the same bytes in 2*log2(n) hops; ring2d's a x b
factorization trades hop count for two link tiers). Codecs scale the beta
term by their wire ratio (int8 ~ S/4 + scales vs fp32).

``measured`` mode replaces the model with timings: ``comm/benchmark.py
--sweep`` emits a JSON decision table (rows of op/world/size/algorithm/codec/
latency) and the selector picks the nearest-size winner. Either way every
(op, bytes-bucket, axis-size) query is answered once and cached — the cache
IS the decision table the facade consults per traced collective, and each
fresh decision emits a ``telemetry`` instant event so choices land in the
same Perfetto trace as the step.
"""

from __future__ import annotations

import math
import threading
from dataclasses import dataclass, field, replace as dc_replace
from typing import Dict, List, Optional, Tuple

from deepspeed_tpu import telemetry
from deepspeed_tpu.collectives.algorithms import ALGORITHMS, _factor_near_square
from deepspeed_tpu.collectives.codecs import get_codec
from deepspeed_tpu.collectives.costmodel import CostModel
from deepspeed_tpu.collectives import pallas_backend
from deepspeed_tpu.collectives.pallas_backend import PALLAS_ALGORITHMS
from deepspeed_tpu.utils.logging import logger

OPS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all")

# AxesSig: ((axis_name, axis_size), ...) — the mesh-axis factorization a
# query runs over. Part of the decision-cache key (two meshes with equal
# world size but different axis splits must not share entries) and the
# schedule compiler's search domain.
AxesSig = Tuple[Tuple[str, int], ...]


def _is_compiled(algorithm: str) -> bool:
    return algorithm == "compiled" or algorithm.startswith("compiled:")


@dataclass(frozen=True)
class Decision:
    """One cached (op, bytes-bucket, world) routing decision."""

    op: str
    algorithm: str
    codec: str
    est_us: float
    source: str  # "model" | "measured" | "config"


@dataclass
class SelectorConfig:
    """Tunables for the cost model + measured table (see the ``collectives``
    config block in ``config/config.py``)."""

    # "auto": measured when a decision table is loaded, the alpha-beta model
    # otherwise; "model"/"measured" pin one source explicitly.
    mode: str = "auto"  # auto | model | measured
    alpha_us: float = 1.0  # per-hop latency
    beta_us_per_mb: float = 10.0  # inverse link bandwidth (~100 GB/s)
    codecs: Tuple[str, ...] = ("none",)  # candidate wire codecs
    block_size: int = 2048
    decision_table: Optional[str] = None  # JSON path from benchmark --sweep
    # payloads below this skip quantization entirely (scales overhead + host
    # side compute dominate); matches ZeRO++'s "quantize the big tensors"
    min_quant_bytes: int = 1 << 16
    # payloads below this stay on the native lax lowering in model mode: a
    # tiny psum as 2(n-1) serial ppermute hops loses to XLA's built-in
    # collective at any alpha; the "lax" verdict is the model's analog of
    # measured mode's don't-bother rows
    min_algorithmic_bytes: int = 1 << 12
    # Alpha discount for the pallas remote-DMA hop primitive: a fused hop is
    # one kernel where the ppermute path dispatches encode + permute +
    # decode programs, so its per-hop launch overhead is lower. Candidates
    # only enter the model when pallas_backend.available() (a real TPU).
    pallas_alpha_scale: float = 0.5
    # Facade defaults (the `collectives` config block's algorithm/codec):
    # applied by comm.all_reduce/all_gather/reduce_scatter when the call
    # passes no explicit algorithm/codec. None = plain jax.lax lowering.
    facade_algorithm: Optional[str] = None  # "auto" | concrete name | None
    facade_codec: Optional[str] = None
    # Per-backend (alpha_us, beta_us_per_mb) overrides fitted from OBSERVED
    # hop timings (collectives/observatory.py refit -> calibrate()); keys
    # "ppermute" / "pallas" / "xla". When present they replace the static
    # alpha/beta (and the pallas_alpha_scale discount) for that backend's
    # candidates, so model mode re-costs from what this mesh measured.
    backend_ab: Dict[str, Tuple[float, float]] = field(default_factory=dict)
    # Let model mode SYNTHESIZE hierarchical schedules (collectives/
    # schedule.py) as candidates next to the hand-written menu. Off by
    # default: under a flat alpha-beta model a multi-level schedule
    # strictly dominates ring on hops at equal wire, so enabling it shifts
    # routing everywhere — an explicit opt-in (config `compiled_search`).
    compiled_search: bool = False


_lock = threading.Lock()
_config = SelectorConfig()
# THE shared alpha-beta object: selector estimates, observatory refits
# (calibrate below) and the schedule compiler's search objective all read
# this one instance. backend_ab is the SAME dict as _config.backend_ab, so
# existing get_config().backend_ab consumers see calibrations unchanged.
_cost_model = CostModel(backend_ab=_config.backend_ab)
_cache: Dict[tuple, Decision] = {}
_measured: List[dict] = []
_stats = {"hits": 0, "misses": 0}


def configure(config: Optional[SelectorConfig] = None, **kwargs) -> SelectorConfig:
    """Install selector tunables (process-global, like the telemetry tracer);
    clears the decision cache. Accepts a ``SelectorConfig`` or field kwargs."""
    global _config, _cost_model
    with _lock:
        # copy, never mutate the caller's template instance
        cfg = dc_replace(config, **kwargs) if config is not None else SelectorConfig(**kwargs)
        cfg.backend_ab = dict(cfg.backend_ab)  # calibrate() mutates in place
        _config = cfg
        # rebuild the shared cost model around the NEW config's constants,
        # handing it the same backend_ab dict so calibrate() keeps writing
        # through both handles
        _cost_model = CostModel(
            alpha_us=cfg.alpha_us, beta_us_per_mb=cfg.beta_us_per_mb,
            pallas_alpha_scale=cfg.pallas_alpha_scale,
            backend_ab=cfg.backend_ab)
        _cache.clear()
        _measured.clear()
        _stats["hits"] = _stats["misses"] = 0
    from deepspeed_tpu.collectives import schedule as _schedule

    # a fresh model instance orphans every cached compile (the cache keys
    # on model identity + version) — drop them eagerly
    _schedule.invalidate_cache()
    with _lock:
        if cfg.decision_table and cfg.mode != "model":
            from deepspeed_tpu.collectives.table import load_table

            try:
                # versioned envelope or legacy bare list; a schema-version
                # mismatch is rejected (with its own warning) inside
                # load_table and leaves _measured empty -> model fallback
                _measured.extend(load_table(cfg.decision_table))
            except (OSError, ValueError) as e:
                logger.warning(
                    f"collectives: decision table {cfg.decision_table!r} unreadable "
                    f"({e}); falling back to the alpha-beta model")
    return _config


def calibrate(backend: str, alpha_us: float, beta_us_per_mb: float) -> None:
    """Install OBSERVED per-backend alpha/beta constants (the observatory's
    least-squares refit lands here); clears the decision cache so future
    picks re-cost under the calibrated model. Survives until the next
    :func:`configure` (a fresh engine re-installs its config — persistent
    calibration rides the observatory's on-disk table instead)."""
    with _lock:
        # writes through the SHARED dict (_config.backend_ab is
        # _cost_model.backend_ab) and bumps the model's version, so cached
        # schedule compiles re-search under the refit constants
        _cost_model.calibrate(backend, alpha_us, beta_us_per_mb)
        _cache.clear()


def get_config() -> SelectorConfig:
    return _config


def cost_model() -> CostModel:
    """THE alpha-beta object: what ``estimate_us`` charges, ``calibrate``
    refits, and the schedule compiler searches under — one instance, by
    identity (the measured-vs-predicted loop tunes the search objective)."""
    return _cost_model


def cache_info() -> Dict[str, int]:
    with _lock:
        return {"entries": len(_cache), **_stats}


# ----------------------------------------------------------------- the model


def _hops_and_volume(op: str, algorithm: str, nbytes: int, n: int) -> Tuple[int, float]:
    """(serial hop count, bytes crossing the busiest link) for one op.

    ``nbytes`` is what the facade queries with: the LOCAL payload. For
    all_reduce / reduce_scatter that is the full pre-reduction array (link
    volume ``2(n-1)/n * S`` / ``(n-1)/n * S``); for all_gather it is the
    SHARD, of which every link relays n-1 peers' worth: ``(n-1) * s``.
    """
    # pallas algorithms run the SAME schedules as their base (identical hop
    # counts and link volumes) — only the hop primitive and the per-hop
    # alpha differ (applied in estimate_us)
    algorithm = pallas_backend.base_algorithm(algorithm)
    ring_steps = n - 1
    log_steps = max(int(math.ceil(math.log2(n))), 1) if n > 1 else 0
    frac = (n - 1) / n if n > 1 else 0.0
    if op == "all_reduce":
        base = 2 * frac * nbytes
    elif op == "all_gather":
        base = ring_steps * nbytes
    else:  # reduce_scatter / all_to_all: each rank ships (n-1)/n of S
        base = frac * nbytes
    if op == "all_to_all":
        # shift schedule: n-1 direct distance-k permutes of one destination
        # row each; bidir pairs mirror distances on full-duplex links;
        # ring2d is the Big-Send-off a x b sub-ring factorization —
        # (a-1)+(b-1) hops at S*((b-1)/b + (a-1)/a) wire volume. rhd has no
        # all-to-all form (every block has exactly one destination).
        if algorithm == "lax":
            return 0, base / 2
        if algorithm == "ring":
            return ring_steps, base
        if algorithm == "bidir":
            return max(-(-ring_steps // 2), 0), base / 2
        if algorithm == "ring2d":
            a, b = _factor_near_square(n)
            hops = (a - 1) + (b - 1)
            vol = nbytes * ((b - 1) / b + (a - 1) / a)
            return hops, vol
        raise ValueError(f"no cost model for op={op!r} algorithm={algorithm!r}")
    if algorithm == "lax":
        # the native XLA lowering: assume the best exact schedule the
        # hardware offers (bidirectional, so half the per-link volume) with
        # no per-hop dispatch penalty — the conservative baseline every
        # algorithmic candidate must beat, so exact-wire rerouting never
        # wins and quantized routing must earn its keep
        return 0, base / 2
    if op == "all_reduce":
        vol = base
        if algorithm == "ring":
            return 2 * ring_steps, vol
        if algorithm == "bidir":
            # two counter-rotating rings each carry half the payload
            return 2 * ring_steps, vol / 2
        if algorithm == "rhd":
            return 2 * log_steps, vol
        if algorithm == "ring2d":
            # the SAME factorization the execution path uses
            a, b = _factor_near_square(n)
            hops = (b - 1) + 2 * (a - 1) + (b - 1)
            vol = nbytes * ((b - 1) / b + 2 * (a - 1) / (a * b) + (b - 1) / b)
            return hops, vol
    else:  # all_gather / reduce_scatter
        vol = base
        if algorithm in ("ring", "ring2d"):
            return ring_steps, vol
        if algorithm == "bidir":
            return ring_steps, vol / 2
        if algorithm == "rhd":
            return log_steps, vol
    raise ValueError(f"no cost model for op={op!r} algorithm={algorithm!r}")


def model_terms(op: str, algorithm: str, codec: str, nbytes: int, n: int,
                itemsize: int = 4, block_size: Optional[int] = None,
                cfg: Optional[SelectorConfig] = None) -> Tuple[int, float]:
    """(hops, wire_mb) — THE regressors of the alpha-beta model.
    ``estimate_us`` charges exactly ``hops*alpha + wire_mb*beta`` from
    these, and the observatory's refit fits observed latencies against the
    SAME terms — one formula, or fitted constants would be applied to
    different regressors than they were fit against."""
    cfg = cfg or _config
    if _is_compiled(algorithm):
        # synthesized schedules carry per-level codecs in the signature;
        # the codec argument is the row's stamped (lossiest) codec and the
        # terms come from the schedule IR under the shared cost model
        from deepspeed_tpu.collectives import schedule as _schedule

        sig = algorithm.split(":", 1)[1]
        if not sig:
            raise ValueError("model_terms needs a concrete compiled:<sig>")
        return _schedule.signature_terms(
            op, sig, nbytes, itemsize,
            block_size if block_size is not None else cfg.block_size,
            cm=_cost_model)
    hops, vol = _hops_and_volume(op, algorithm, nbytes, n)
    c = get_codec(codec, block_size if block_size is not None else cfg.block_size)
    wire = c.wire_bytes(max(int(vol // itemsize), 1), itemsize)
    return hops, wire / 1e6


def estimate_us(op: str, algorithm: str, codec: str, nbytes: int, n: int,
                cfg: Optional[SelectorConfig] = None, itemsize: int = 4) -> float:
    """Alpha-beta time estimate for one (algorithm, codec) pair.

    ``itemsize`` is the payload element width: the link volume converts to
    an element count before the codec's wire-byte model applies, so a bf16
    payload's int8 wire is costed at ~1/2, not the fp32 default's ~1/4."""
    cfg = cfg or _config
    hops, wire_mb = model_terms(op, algorithm, codec, nbytes, n, itemsize,
                                cfg=cfg)
    fitted = cfg.backend_ab.get(pallas_backend.hop_backend(algorithm))
    if fitted is not None:
        # observed constants (observatory refit) replace the static model —
        # including the pallas alpha discount, which the fit subsumes
        alpha, beta = fitted
    else:
        alpha = cfg.alpha_us * (cfg.pallas_alpha_scale
                                if pallas_backend.is_pallas(algorithm) else 1.0)
        beta = cfg.beta_us_per_mb
    return hops * alpha + wire_mb * beta


def _model_pick(op: str, nbytes: int, n: int, codec: Optional[str],
                cfg: SelectorConfig, itemsize: int = 4,
                axes_sig: Optional[AxesSig] = None) -> Decision:
    if nbytes < cfg.min_algorithmic_bytes and codec in (None, "none"):
        # the native lowering cannot apply a wire codec, so the lax floor
        # only covers queries that didn't force one
        return Decision(op, "lax", "none", 0.0, "model")
    codecs = (codec,) if codec else tuple(cfg.codecs) or ("none",)
    if codec is None and nbytes < cfg.min_quant_bytes:
        # small payloads never auto-quantize (scale overhead dominates); the
        # exact wire is always a legal candidate even when the configured
        # candidate list is all-lossy (e.g. codecs=["int8"])
        codecs = tuple(c for c in codecs if c == "none") or ("none",)
    pow2 = n > 0 and not (n & (n - 1))
    # the native lowering is a candidate whenever no lossy codec is forced:
    # an exact-wire algorithmic collective moves the same bytes as XLA's
    # fused native one plus hop latency, so it can only win by shrinking
    # the wire — but a FORCED lossy codec needs an algorithmic carrier
    best: Optional[Decision] = None
    if codec in (None, "none"):
        best = Decision(op, "lax", "none",
                        estimate_us(op, "lax", "none", nbytes, n, cfg, itemsize),
                        "model")
    candidates = ALGORITHMS + (PALLAS_ALGORITHMS if pallas_backend.available() else ())
    for alg in candidates:
        if alg == "rhd" and (not pow2 or op == "all_to_all"):
            continue
        for cd in codecs:
            if not pallas_backend.compiled_ok(alg, cd):
                continue  # the chip's compiler refuses the fused hop
            est = estimate_us(op, alg, cd, nbytes, n, cfg, itemsize)
            if best is None or est < best.est_us:
                best = Decision(op, alg, cd, est, "model")
    if cfg.compiled_search and axes_sig:
        from deepspeed_tpu.collectives import schedule as _schedule

        if op in _schedule.SCHEDULED_OPS:
            for cd in codecs:
                sched = _schedule.compile_schedule(
                    op, axes_sig, nbytes, cd, itemsize=itemsize,
                    block_size=cfg.block_size, cm=_cost_model)
                if sched is None:
                    continue
                # the decision's codec is the schedule's LOSSIEST level
                # (what actually hits a wire), not the search input — a
                # mixed placement may keep cd off the inner rings entirely
                stamped = _schedule.signature_codec(sched.signature)
                if best is None or sched.est_us < best.est_us:
                    best = Decision(op, f"compiled:{sched.signature}",
                                    stamped, sched.est_us, "model")
    assert best is not None
    return best


def _row_mesh_ok(r: dict, op: str, axes_sig: Optional[AxesSig]) -> bool:
    """A ``compiled:<sig>`` row names mesh axes and their factor sizes: it
    may only route onto a query whose axis tuple the signature actually
    factors (and, for rank-ordered ops, in executable order). Hand-written
    algorithm rows are mesh-shape-agnostic — the world-size match the
    caller already did is all they claim."""
    alg = str(r.get("algorithm", ""))
    if not _is_compiled(alg):
        return True
    if ":" not in alg or axes_sig is None:
        return False
    from deepspeed_tpu.collectives import schedule as _schedule

    try:
        levels = _schedule.parse_signature(alg.split(":", 1)[1])
        _schedule._validate_levels(levels, axes_sig, op)
    except ValueError:
        return False
    return True


def _measured_pick(op: str, nbytes: int, n: int, codec: Optional[str],
                   cfg: SelectorConfig, itemsize: int = 4,
                   axes_sig: Optional[AxesSig] = None) -> Optional[Decision]:
    if codec is not None:
        allowed = {codec}
    else:
        # same guardrails as the model path: only configured codec
        # candidates, and never a lossy wire under min_quant_bytes —
        # measured rows for a bigger bucket must not smuggle one in
        allowed = set(cfg.codecs) | {"none"}
        if nbytes < cfg.min_quant_bytes:
            allowed = {"none"}
    rows = [r for r in _measured
            if r.get("op") == op and int(r.get("world", 0)) == n
            and r.get("codec", "none") in allowed and _row_backend_ok(r)
            and _row_mesh_ok(r, op, axes_sig)]
    # a mixed-itemsize table (online rows + sweeps at different dtypes)
    # keeps separate rows per element width because a lossy wire costs per
    # ELEMENT: answer from rows measured at the querying payload's width
    # when any exist; tables without itemsize coverage keep the legacy
    # any-row behavior rather than starving measured mode
    # legacy rows default to the historical sweep width (bf16, 2) — the
    # same default table.row_key uses, so they stay visible to bf16 queries
    same_width = [r for r in rows if int(r.get("itemsize", 2)) == int(itemsize)]
    rows = same_width or rows
    if not rows:
        return None
    size_mb = nbytes / 1e6

    def closeness(r):
        return abs(math.log((float(r["size_mb"]) + 1e-9) / (size_mb + 1e-9)))

    nearest = min(closeness(r) for r in rows)
    bucket = [r for r in rows if closeness(r) <= nearest + 1e-12]
    win = min(bucket, key=lambda r: float(r["latency_ms"]))
    return Decision(op, win["algorithm"], win.get("codec", "none"),
                    float(win["latency_ms"]) * 1e3, "measured")


def _row_backend_ok(r: dict) -> bool:
    """A decision-table row may only route algorithms of the hop backend it
    was MEASURED with (``--sweep`` stamps ``backend``): ppermute timings say
    nothing about remote-DMA hop counts and vice versa. Un-stamped legacy
    rows are ppermute-era sweeps; a pallas algorithm in one is a schema
    mismatch and never routes. ``lax`` rows (stamped ``xla``) are
    backend-neutral don't-bother verdicts. Pallas rows additionally need
    the backend to be usable in THIS process."""
    alg = str(r.get("algorithm", ""))
    stamp = r.get("backend", "ppermute")
    if alg == "lax":
        return True
    implied = "pallas" if pallas_backend.is_pallas(alg) else "ppermute"
    if stamp != implied or not pallas_backend.compiled_ok(alg, r.get("codec")):
        return False
    return implied != "pallas" or pallas_backend.available()


def pick_codec(op: str, nbytes: int, axis_size: int, algorithm: str,
               itemsize: int = 4) -> str:
    """Best wire codec from the configured candidates for a FORCED
    algorithm (the config block's concrete ``algorithm`` + ``codec: auto``
    combination) — same guardrails as the joint model pick."""
    cfg = _config
    if nbytes < cfg.min_quant_bytes:
        return "none"
    if algorithm not in ALGORITHMS + PALLAS_ALGORITHMS:
        algorithm = "ring"
    alg = algorithm
    candidates = tuple(cd for cd in cfg.codecs
                       if pallas_backend.compiled_ok(alg, cd)) or ("none",)
    return min(candidates,
               key=lambda cd: estimate_us(op, alg, cd, nbytes, axis_size, cfg, itemsize))


def _bytes_bucket(nbytes: int) -> int:
    """Power-of-two size bucket so near-identical payloads share a cache
    entry (and one telemetry decision event)."""
    return max(int(nbytes), 1).bit_length()


def select(op: str, nbytes: int, axis_size: int, codec: Optional[str] = None,
           itemsize: int = 4, axes_sig: Optional[AxesSig] = None) -> Decision:
    """Pick (algorithm, codec) for one collective; cached per
    (op, bytes-bucket, axis-size, mesh factorization, payload itemsize
    [, forced codec])."""
    if op not in OPS:
        raise ValueError(f"unknown op {op!r} (one of {OPS})")
    # the hop backend is part of the decision's identity: a cache warmed
    # while pallas hops were unavailable must not answer for a process (or
    # restored table) where they are, and vice versa. So is the mesh-axis
    # FACTORIZATION (axes_sig): two meshes with equal world size but
    # different axis splits — (("dp", 8),) vs (("dp", 4), ("ep", 2)) — take
    # different schedules, so they must not share a cache entry (and a
    # legacy axes_sig-less query must not answer a factorized one).
    key = (op, _bytes_bucket(nbytes), int(axis_size), axes_sig, codec,
           int(itemsize), pallas_backend.backend_token())
    with _lock:
        hit = _cache.get(key)
        if hit is not None:
            _stats["hits"] += 1
            return hit
        _stats["misses"] += 1
        cfg = _config
    decision = None
    if nbytes < cfg.min_algorithmic_bytes and codec in (None, "none"):
        # the lax floor applies in EVERY mode: a measured table's smallest
        # swept size must not extrapolate onto tiny step-critical psums.
        # A FORCED lossy codec needs an algorithmic path, so it bypasses it.
        decision = Decision(op, "lax", "none", 0.0, "model")
    elif cfg.mode == "measured" or (cfg.mode == "auto" and _measured):
        decision = _measured_pick(op, nbytes, axis_size, codec, cfg, itemsize,
                                  axes_sig)
    if decision is None:
        decision = _model_pick(op, nbytes, axis_size, codec, cfg, itemsize,
                               axes_sig)
    with _lock:
        decision = _cache.setdefault(key, decision)
    tracer = telemetry.get_tracer()
    if tracer.enabled:
        tracer.instant("coll:select", cat="coll", op=op, bytes=int(nbytes),
                       world=int(axis_size), algorithm=decision.algorithm,
                       codec=decision.codec, est_us=round(decision.est_us, 3),
                       source=decision.source)
    return decision
