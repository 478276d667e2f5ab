"""The harness refuses to measure anything but a known TPU under Pallas
dispatch, and then prints no result."""

import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

import smoke
import harness

RUN = Path(__file__).resolve().parents[1] / "run.py"


def fake(kind, platform="tpu"):
    return [SimpleNamespace(platform=platform, device_kind=kind)]


def test_cpu_run_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(RUN), "--workload",
                        "smollm-360m.train-2k", "--seed", "1",
                        "--seconds", "1"], capture_output=True, text=True,
                       env=env, cwd=smoke.ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_kind(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: fake("TPU v9 imaginary"))
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    with pytest.raises(harness.DeviceError, match="no row"):
        harness.check_device(1)


def test_too_few_chips(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: fake("TPU v5 lite"))
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    with pytest.raises(harness.DeviceError, match="4 chips"):
        harness.check_device(4)


def test_non_pallas_dispatch(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: fake("TPU v5 lite"))
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    with pytest.raises(harness.DeviceError, match="not 'pallas'"):
        harness.check_device(1)
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    assert harness.check_device(1) == {"platform": "tpu",
                                       "kind": "TPU v5 lite", "count": 1}
