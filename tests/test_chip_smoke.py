"""The chip smoke script refuses to run without a TPU, and the compile
cache is placed from the environment or at a fixed path in the checkout."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.runtime import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_leaves_a_placed_dir_alone(monkeypatch, tmp_path,
                                                 cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_dir_in_the_checkout(
        monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.use_compile_cache()
    assert got == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.use_compile_cache() == got
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def _run_smoke(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_KERNELS", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _claims_ok(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return json.loads(lines[-1]).get("ok") is True
    except (ValueError, AttributeError):
        return False


def test_chip_smoke_fails_on_the_cpu():
    r = _run_smoke(ROOT)
    assert r.returncode != 0
    assert not _claims_ok(r.stdout)
    assert "no TPU" in r.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run_smoke(tmp_path)
    assert r.returncode != 0
    assert not _claims_ok(r.stdout)
