"""Packaging smoke: ``pip install -e .`` + console entry points.

Reference parity: ``setup.py:152-198`` installs ``deepspeed``/``ds_*`` console
scripts; round-3 verdict item 7 requires the CLIs to be runnable OUTSIDE the
checkout. Strategy: build a venv with --system-site-packages (jax/setuptools
come from the host; the sandbox has no network), editable-install the repo
with --no-deps --no-build-isolation, and drive two entry points from a cwd
outside the repo.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))


@pytest.fixture(scope="module")
def venv_bin(tmp_path_factory):
    venv = tmp_path_factory.mktemp("pkg") / "venv"
    try:
        subprocess.run([sys.executable, "-m", "venv", str(venv)],
                       check=True, capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired) as e:
        pytest.skip(f"venv creation unavailable: {e}")
    # The test interpreter may itself be a venv (sandbox: /opt/venv), in which
    # case --system-site-packages would expose the BASE python's site-packages
    # and miss jax/setuptools. Link the parent's site-packages explicitly.
    import site

    sp_dirs = [p for p in site.getsitepackages() if os.path.isdir(p)]
    venv_sp = venv / "lib" / f"python{sys.version_info.major}.{sys.version_info.minor}" / "site-packages"
    (venv_sp / "_parent_env.pth").write_text("\n".join(sp_dirs) + "\n")
    pip = venv / "bin" / "pip"
    r = subprocess.run(
        [str(pip), "install", "--no-deps", "--no-build-isolation", "-e", REPO],
        capture_output=True, text=True, timeout=300)
    if r.returncode != 0:
        pytest.fail(f"pip install -e . failed:\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
    return venv / "bin"


def _run(venv_bin, exe, *args, cwd="/tmp"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)  # prove the INSTALL resolves, not the checkout
    return subprocess.run([str(venv_bin / exe), *args], capture_output=True,
                          text=True, timeout=180, cwd=cwd, env=env)


def test_editable_install_exposes_all_cli_entry_points(venv_bin):
    expected = ["dstpu", "ds_report", "ds_bench", "ds_elastic", "ds_io",
                "ds_nvme_tune", "ds_ssh", "zero_to_fp32"]
    missing = [e for e in expected if not (venv_bin / e).exists()]
    assert not missing, f"entry points not installed: {missing}"


def test_ds_elastic_runs_outside_checkout(venv_bin, tmp_path):
    cfg = tmp_path / "ds_config.json"
    cfg.write_text(json.dumps({
        "elasticity": {"enabled": True, "max_train_batch_size": 64,
                       "micro_batch_sizes": [2, 4], "min_gpus": 1,
                       "max_gpus": 8, "version": 0.1}}))
    r = _run(venv_bin, "ds_elastic", "-c", str(cfg))
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["train_batch_size"] > 0 and out["valid_world_sizes"]


def test_dstpu_help_runs_outside_checkout(venv_bin):
    r = _run(venv_bin, "dstpu", "--help")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "usage" in r.stdout.lower()


def test_entry_point_targets_importable():
    """Packaging check without a `pip install -e .`: every [project.scripts]
    target in pyproject.toml must resolve to a callable."""
    import importlib

    try:
        import tomllib  # stdlib from 3.11
    except ImportError:  # pragma: no cover - declared floor is 3.10
        tomllib = pytest.importorskip("tomli")

    with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
        scripts = tomllib.load(f)["project"]["scripts"]
    expected = {"dstpu", "ds_report", "ds_bench", "ds_elastic", "ds_io",
                "ds_nvme_tune", "ds_ssh", "zero_to_fp32"}
    assert expected <= set(scripts), f"missing console scripts: {expected - set(scripts)}"
    for name, target in scripts.items():
        mod, _, attr = target.partition(":")
        fn = getattr(importlib.import_module(mod), attr)
        assert callable(fn), f"{name} -> {target} is not callable"
