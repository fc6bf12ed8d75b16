"""Test harness: run every test on a virtual 8-device CPU mesh.

TPU-native analog of the reference's distributed-in-one-box harness
(``tests/unit/common.py`` — DistributedTest spawning N processes): JAX SPMD
needs no process-per-rank, so we instead force the host CPU platform to expose
8 virtual devices and run real multi-device sharding/collectives in-process.
Pallas kernels run in interpret mode here; what the chip's compiler accepts
is checked by ``tests/unit/ops/test_chip_compile.py`` and ``chip_smoke.py``.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_backend_optimization_level" not in flags:
    # The suite is COMPILE-dominated on the single-core driver lane and the
    # tests assert math, not codegen quality: level 1 compiles the
    # compile-heavy tests ~2-3x faster (round-5 measurement: heaviest test
    # 88 s -> ~31 s cold) WITHOUT level 0's interpreter-slow codegen, which
    # regressed runtime-heavy tests (LoCo EF test 69 s -> 98 s at O0). Perf
    # numbers never come from tests (benchmarks/run.py runs without this
    # conftest).
    flags = flags + " --xla_backend_optimization_level=1"
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

# the platform is pinned in code as well as by the driver's JAX_PLATFORMS=cpu:
# a bare ``pytest tests/`` must not reach for an accelerator
jax.config.update("jax_platforms", "cpu")
# Persistent compilation cache: the suite is dominated by XLA compiles of
# near-identical tiny programs (round-2 verdict: 186 tests no longer fit one
# 550 s run). Cache survives across pytest invocations in the repo tree.
#
# Keyed per HEAD sha (ISSUE 18): jax's entry keys hash the traced program,
# not the python that built it, so a source change that alters runtime
# behavior without changing the HLO (donation tweaks, compile options read
# from the environment, jax version-adjacent serialization drift) can serve
# a stale executable across commits. One subdir per HEAD commit makes the
# cache's validity domain explicit; stale sibling dirs (and pre-keying flat
# entries) are pruned so the tree holds at most one commit's cache.
_CACHE_ROOT = os.path.join(os.path.dirname(__file__), ".jax_cache")


def _head_sha():
    """Short HEAD sha of the repo this conftest sits in, or None when git
    is unavailable / not a checkout (then the cache keys to 'nogit')."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10.0)
    except (OSError, subprocess.TimeoutExpired):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


def jax_cache_dir(root=None, sha=None):
    """The compilation-cache dir for one commit: ``<root>/<short-sha>``."""
    return os.path.join(root or _CACHE_ROOT, sha or _head_sha() or "nogit")


def _prune_stale_cache(keep, root=None):
    """Remove sibling cache dirs from other commits and legacy flat cache
    files from the pre-keyed layout. Returns the entry names removed."""
    import shutil

    root = root or _CACHE_ROOT
    if not os.path.isdir(root):
        return []
    removed = []
    for entry in os.listdir(root):
        path = os.path.join(root, entry)
        if os.path.abspath(path) == os.path.abspath(keep):
            continue
        try:
            if os.path.isdir(path):
                shutil.rmtree(path)
            else:
                os.remove(path)
            removed.append(entry)
        except OSError:
            pass  # racing a parallel pytest: its key is the same sha anyway
    return removed


# JAX_COMPILATION_CACHE_DIR, when the environment sets it, wins here as it
# does everywhere (utils/compile_cache.py): jax reads it itself, and a
# directory placed from outside is neither re-pointed nor pruned.
_PLACED = os.environ.get("JAX_COMPILATION_CACHE_DIR")
_CACHE_DIR = _PLACED or jax_cache_dir()
if not _PLACED:
    _prune_stale_cache(keep=_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
# Placed the same way for what runs downstream: enable_compile_cache() yields
# to it, in this worker and in every subprocess a test starts, so no test can
# re-point the cache at the flat <checkout>/.jax_cache.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _clear_mesh_state():
    yield
    from deepspeed_tpu.topology import mesh as mesh_mod

    mesh_mod._ACTIVE_MESH = None
