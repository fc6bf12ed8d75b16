"""Pre-flight HBM-fit guard.

A plain out-of-memory refusal: this module checks a byte estimate against
the device's memory BEFORE anything is materialized on chip, and either
warns (default) or refuses with the estimate in the error — so an
over-budget config fails at once with the terms that do not fit, not with
an allocator error partway through placement.

Device memory discovery: ``jax.devices()[0].memory_stats()['bytes_limit']``
where the backend reports it; the ``DSTPU_DEVICE_MEMORY_GB`` env var or an
explicit ``device_memory`` argument overrides (and is the only way to make
the guard bite on CPU backends, which report host RAM or nothing — that is
also what the unit tests use). With no budget discoverable the check is a
no-op: the guard must never block CPU smoke runs.
"""

from __future__ import annotations

import os
from typing import Optional

from deepspeed_tpu.utils.logging import logger


class HBMBudgetError(RuntimeError):
    """Raised (mode='refuse') when an estimate exceeds the device budget."""


# ------------------------------------------------------ quantized-serving math
# The serving-capacity byte formulas (ISSUE 10): KV bytes/token is the
# admission bottleneck the guard protects, so the guard, the engine's pool
# sizing, and the capacity benchmark must all agree on ONE definition of what
# a quantized block costs. Quantized storage (int8/fp8) holds 1 byte/element
# plus one fp32 scale per (layer, slot, kv-head) hd-vector block — the
# ``ops.quant`` block-math layout ``inference/paged.py`` writes.

KV_SCALE_BYTES = 4  # fp32 scale per (slot, head) quantization block


def kv_slot_bytes(num_layers: int, kv_heads: int, head_dim: int,
                  dtype_bytes: int = 2, kv_quant: Optional[str] = None) -> int:
    """Bytes ONE token slot occupies in the paged KV pool (k + v)."""
    if kv_quant is None:
        per_head = head_dim * dtype_bytes
    else:
        per_head = head_dim * 1 + KV_SCALE_BYTES
    return 2 * num_layers * kv_heads * per_head


def kv_pool_bytes(num_layers: int, num_slots: int, kv_heads: int, head_dim: int,
                  dtype_bytes: int = 2, kv_quant: Optional[str] = None) -> int:
    """Bytes of a paged pool holding ``num_slots`` token slots
    (``num_blocks * block_size``: the pool is whole pages, no trash slot)."""
    return num_slots * kv_slot_bytes(num_layers, kv_heads, head_dim,
                                     dtype_bytes, kv_quant)


def kv_blocks_for_bytes(pool_bytes: int, num_layers: int, block_size: int,
                        kv_heads: int, head_dim: int, dtype_bytes: int = 2,
                        kv_quant: Optional[str] = None) -> int:
    """How many KV blocks fit a byte budget — the admission-capacity lever:
    at identical ``pool_bytes`` an int8 pool yields ~2x the blocks of a bf16
    pool (head_dim ≥ 64: ≥1.88x after the per-block scale), which is what the
    ``BlockedAllocator`` sizing then admits."""
    per_block = block_size * kv_slot_bytes(num_layers, kv_heads, head_dim,
                                           dtype_bytes, kv_quant)
    return max(int(pool_bytes) // per_block, 1)


def disagg_pool_bytes(total_bytes: int, roles, prefill_share: float = 0.25):
    """Split one serving tier's KV byte budget across phase-specialized
    replica pools (ISSUE 14 capacity math).

    Prefill pools hold a request's KV only TRANSIENTLY — from the prefill
    dispatch until its migration commits, bounded by ``migration_depth``
    concurrent exports times the longest prompt — while the decode pool
    holds EVERY in-flight request's full context for its whole generation.
    So the decode side gets the bulk: the prefill replicas share
    ``prefill_share`` of the budget evenly, decode (and mixed, which also
    decode) replicas share the rest. A roster with no specialized role
    splits evenly — the mixed baseline at equal hardware.

    Returns one byte budget per entry of ``roles``, summing to
    ``total_bytes`` (modulo integer division).
    """
    roles = list(roles)
    if not roles:
        raise ValueError("disagg_pool_bytes needs at least one role")
    if not 0.0 < prefill_share < 1.0:
        raise ValueError(f"prefill_share must be in (0, 1), got {prefill_share}")
    n_pre = sum(1 for r in roles if r == "prefill")
    n_rest = len(roles) - n_pre
    if n_pre == 0 or n_rest == 0:
        return [int(total_bytes) // len(roles)] * len(roles)
    pre_each = int(total_bytes * prefill_share) // n_pre
    rest_each = int(total_bytes - pre_each * n_pre) // n_rest
    return [pre_each if r == "prefill" else rest_each for r in roles]


def prefix_cache_capacity_blocks(num_blocks: int, fraction: float) -> int:
    """Cache-aware pool sizing (ISSUE 12): how many pool blocks the prefix
    cache may hold references to. The cap guarantees live sequences always
    have at least ``(1 - fraction)`` of the pool available after LRU
    eviction, and because cached blocks store QUANTIZED bytes, the same
    ``fraction`` of an int8 pool indexes ~1.9x the prefix tokens of a bf16
    pool at fixed HBM (the PR-10 byte shrink compounding with reuse)."""
    if not 0.0 <= fraction <= 1.0:
        raise ValueError(f"prefix-cache fraction must be in [0, 1], got {fraction}")
    return int(num_blocks * fraction)


def record_calibration(
    estimate_bytes: int,
    actual_peak_bytes: Optional[int],
    *,
    what: str,
    warn_factor: float = 1.2,
    registry=None,
) -> Optional[float]:
    """Reconcile a pre-flight estimate with XLA's own ``memory_analysis()``.

    The guard's whole value is refusing BEFORE placement — which it can only do
    if its byte math tracks reality. Every captured program's XLA peak
    (argument + output − alias + temp) is compared against the estimate the
    engine registered; the ratio lands as ``hbm/estimate_ratio`` (labelled
    per program, plus an unlabelled last-program gauge), and an
    under-estimate beyond ``warn_factor`` (default: actual >20% over the
    estimate) warns loudly — that is the guard flying blind. Ratios well
    below 1 are normal: the estimate covers the whole engine state while a
    single program's peak covers only its live set.

    Returns the ratio, or None when either side is unusable.
    """
    if not estimate_bytes or estimate_bytes <= 0 or not actual_peak_bytes:
        return None
    ratio = float(actual_peak_bytes) / float(estimate_bytes)
    if registry is None:
        from deepspeed_tpu.telemetry import get_tracer

        tracer = get_tracer()
        registry = tracer.registry if tracer.enabled else None
    if registry is not None:
        registry.gauge("hbm/estimate_ratio", program=what).set(ratio)
        registry.gauge("hbm/estimate_ratio").set(ratio)
    if ratio > warn_factor:

        def fmt(b: float) -> str:
            return (f"{b / (1 << 30):.2f} GiB" if b >= (1 << 28)
                    else f"{b / (1 << 20):.2f} MiB")

        logger.warning(
            f"HBM calibration: program {what!r} peaks at "
            f"{fmt(actual_peak_bytes)} per XLA memory_analysis but the "
            f"pre-flight guard estimated {fmt(estimate_bytes)} "
            f"({ratio:.2f}x) — the refuse-mode guard is under-estimating "
            "and may admit a run that runs out of device memory; revisit "
            "estimate_state_memory terms for this config.")
    return ratio


def device_memory_bytes(device=None) -> Optional[int]:
    """Best-effort per-device memory budget in bytes, or None if unknown.

    ``DSTPU_DEVICE_MEMORY_GB`` overrides backend discovery (set it to make
    the guard authoritative on backends with unreliable ``memory_stats``).
    CPU backends are treated as unknown — host RAM is not the budget the
    guard protects.
    """
    env = os.environ.get("DSTPU_DEVICE_MEMORY_GB")
    if env:
        return int(float(env) * (1 << 30))
    try:
        import jax

        dev = device if device is not None else jax.devices()[0]
        if dev.platform == "cpu":
            return None
        stats = dev.memory_stats()
        if stats and stats.get("bytes_limit"):
            return int(stats["bytes_limit"])
    except Exception:  # noqa: BLE001 — discovery must never break init
        pass
    return None


def check_hbm_fit(
    need_bytes: int,
    *,
    what: str,
    mode: str = "warn",
    device_memory: Optional[int] = None,
    headroom: float = 0.92,
) -> bool:
    """Check ``need_bytes`` against the device budget BEFORE materializing.

    mode: 'warn' logs and proceeds; 'refuse' raises :class:`HBMBudgetError`;
    'off' is a no-op. Returns True when the estimate fits (or no budget is
    discoverable), False when it does not and mode permitted proceeding.
    """
    if mode not in ("warn", "refuse", "off"):
        raise ValueError(f"hbm guard mode must be warn|refuse|off, got {mode!r}")
    if mode == "off":
        return True
    budget = device_memory if device_memory is not None else device_memory_bytes()
    if budget is None:
        return True
    usable = int(budget * headroom)
    if need_bytes <= usable:
        return True

    def fmt(b: float) -> str:
        return (f"{b / (1 << 30):.2f} GiB" if b >= (1 << 28)
                else f"{b / (1 << 20):.2f} MiB")

    msg = (
        f"HBM pre-flight: {what} needs an estimated {fmt(need_bytes)} "
        f"but the device budget is {fmt(budget)} "
        f"({headroom:.0%} usable = {fmt(usable)}). "
        "Materializing anyway would run out of device memory. Shrink the "
        "model/batch, raise ZeRO stage, or enable offload."
    )
    if mode == "refuse":
        raise HBMBudgetError(msg)
    logger.warning(msg)
    return False
