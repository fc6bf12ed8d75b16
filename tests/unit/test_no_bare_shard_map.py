"""Tier-1 lint: no bare ``jax.shard_map`` / ``from jax import shard_map``.

``shard_map`` and ``axis_size`` have both moved between jax releases, once
taking out dozens of tests at import time. The package is written for the
one installed jax, and every call site imports the two names from
``deepspeed_tpu.utils.compat`` — so the next move is a one-line change there,
and ``compat.axis_size`` keeps its tuple/``default=`` semantics in one place.
This grep keeps call sites from reaching past it.
"""

import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the shim itself (resolves the native symbol) and this lint are exempt
EXEMPT = {
    os.path.join("deepspeed_tpu", "utils", "compat.py"),
    os.path.join("tests", "unit", "test_no_bare_shard_map.py"),
}

BARE_PATTERNS = [
    (re.compile(r"\bjax\.shard_map\b"), "jax.shard_map"),
    (re.compile(r"^\s*from\s+jax\s+import\s+.*\bshard_map\b", re.M),
     "from jax import shard_map"),
    (re.compile(r"^\s*from\s+jax\.experimental\.shard_map\s+import", re.M),
     "from jax.experimental.shard_map import"),
    (re.compile(r"\bjax\.lax\.axis_size\b|\blax\.axis_size\b"), "lax.axis_size"),
]

SCAN_DIRS = ("deepspeed_tpu", "tests", "tools")
SCAN_FILES = ("bench.py",)


def _python_files():
    for d in SCAN_DIRS:
        for root, _dirs, files in os.walk(os.path.join(REPO_ROOT, d)):
            if ".jax_cache" in root:
                continue
            for f in files:
                if f.endswith(".py"):
                    yield os.path.join(root, f)
    for f in SCAN_FILES:
        p = os.path.join(REPO_ROOT, f)
        if os.path.exists(p):
            yield p


def test_no_bare_shard_map_or_axis_size():
    offenders = []
    for path in _python_files():
        rel = os.path.relpath(path, REPO_ROOT)
        if rel in EXEMPT:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                src = f.read()
        except OSError:
            continue
        for pat, label in BARE_PATTERNS:
            for m in pat.finditer(src):
                line = src.count("\n", 0, m.start()) + 1
                offenders.append(f"{rel}:{line}: {label}")
    assert not offenders, (
        "bare shard_map/axis_size usage (import from "
        "deepspeed_tpu.utils.compat instead):\n  " + "\n  ".join(offenders))


def test_compat_shard_map_resolves():
    """The import point resolves on the installed jax, and the retired
    ``check_rep`` spelling is refused rather than ignored."""
    from deepspeed_tpu.utils.compat import shard_map

    assert callable(shard_map)
    with pytest.raises(TypeError):
        shard_map(lambda x: x, mesh=None, in_specs=None, out_specs=None,
                  check_vma=False, check_rep=False)
