"""Pin the per-commit compilation-cache keying (ISSUE 18).

The suite's persistent XLA cache is keyed by HEAD sha
(``tests/conftest.py``): jax hashes the traced program, not the python that
produced it, so without the key a source change could be served a stale
executable compiled at another commit. These tests pin the contract: the
active cache dir is ``tests/.jax_cache/<short-sha>``, and pruning removes
other commits' dirs plus legacy flat entries while leaving the live dir
alone.
"""

import os

import jax

from tests import conftest


def test_cache_dir_is_keyed_by_head_sha(tmp_path):
    sha = conftest._head_sha()
    # the repo under test IS a git checkout; if this ever runs from an
    # export tarball the 'nogit' fallback keeps the cache functional
    key = sha or "nogit"
    assert conftest.jax_cache_dir() == os.path.join(conftest._CACHE_ROOT, key)
    # explicit args win (what the pruner and this test key off)
    assert conftest.jax_cache_dir(root=str(tmp_path), sha="abc123") == str(
        tmp_path / "abc123")


def test_active_jax_config_points_into_keyed_dir():
    configured = jax.config.jax_compilation_cache_dir
    assert configured == conftest._CACHE_DIR
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:  # a cache placed from outside wins over the per-commit keying
        assert configured == placed
        return
    # the configured dir is a CHILD of the cache root, never the root
    # itself (the root held flat entries before keying landed)
    assert os.path.dirname(os.path.abspath(configured)) == os.path.abspath(
        conftest._CACHE_ROOT)


def test_prune_removes_stale_siblings_and_flat_files(tmp_path):
    root = tmp_path / "cache"
    live = root / "abc123"
    stale = root / "0ldsha"
    live.mkdir(parents=True)
    stale.mkdir()
    (live / "entry-cache").write_bytes(b"keep")
    (stale / "entry-cache").write_bytes(b"drop")
    (root / "jit_fn-deadbeef-cache").write_bytes(b"legacy flat entry")

    removed = conftest._prune_stale_cache(keep=str(live), root=str(root))

    assert sorted(removed) == ["0ldsha", "jit_fn-deadbeef-cache"]
    assert (live / "entry-cache").read_bytes() == b"keep"
    assert not stale.exists()
    assert sorted(os.listdir(root)) == ["abc123"]


def test_prune_handles_missing_root(tmp_path):
    assert conftest._prune_stale_cache(
        keep=str(tmp_path / "x" / "sha"), root=str(tmp_path / "x")) == []
