"""Kernel registry: named ops with per-backend implementations.

TPU-native analog of the reference's op_builder system (``op_builder/builder.py``
— 30 JIT-compiled CUDA extensions selected per accelerator). Here an "op" is a
named function with one or more implementations ('xla' — plain jnp the compiler
fuses; 'pallas' — a hand-written TPU kernel). ``auto`` picks pallas on TPU
when the op has one and 'xla' elsewhere. An implementation asked for BY NAME
that the op does not have is an error: on the chip a silent hand-over to XLA
would be measured under the kernel's name.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax

_REGISTRY: Dict[str, Dict[str, Callable]] = {}


def register(op_name: str, impl: str) -> Callable:
    def deco(fn: Callable) -> Callable:
        _REGISTRY.setdefault(op_name, {})[impl] = fn
        return fn

    return deco


@functools.lru_cache(None)
def _default_backend() -> str:
    return jax.default_backend()


def available_impls(op_name: str) -> Dict[str, Callable]:
    return dict(_REGISTRY.get(op_name, {}))


def dispatch(op_name: str, impl: str = "auto") -> Callable:
    """Resolve an op implementation. 'auto' => pallas on TPU else xla."""
    impls = _REGISTRY.get(op_name)
    if not impls:
        raise KeyError(f"No implementations registered for op {op_name!r}")
    if impl == "auto":
        if _default_backend() == "tpu" and "pallas" in impls:
            return impls["pallas"]
        return impls.get("xla") or next(iter(impls.values()))
    if impl == "flash":  # model-config alias for the pallas attention path
        impl = "pallas"
    if impl not in impls:
        raise KeyError(
            f"op {op_name!r} has no {impl!r} implementation (registered: {sorted(impls)})")
    return impls[impl]


def op_report() -> Dict[str, list]:
    """ds_report analog: which impls exist per op."""
    return {name: sorted(impls) for name, impls in sorted(_REGISTRY.items())}
