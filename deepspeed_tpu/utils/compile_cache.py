"""Where JAX's persistent compilation cache lives.

The cache directory is part of every entry's key, so a directory that moves
(a ``mkdtemp``, a pid, a clock) never hits twice. One rule, used by every
entry point that compiles (``chip_smoke.py``, ``benchmarks/run.py``, the
replica daemon, ``tools/fabric_smoke.py``): ``JAX_COMPILATION_CACHE_DIR``
wins when the environment sets it — jax reads that variable itself, nothing
here touches the config — and otherwise the cache sits at the fixed
``<checkout>/.jax_cache`` (git-ignored).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only those past jax's one-second threshold:
    # a second run of the same command should compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
