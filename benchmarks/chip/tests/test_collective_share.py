"""The reader of ``collective_share.train`` on a synthetic reduced
trace: collective ops by name, synchronous and asynchronous halves,
per device over busy time; nothing to read without a trace."""

from pathlib import Path

import pytest

import smoke  # noqa: F401
from harness import load_module

READER = load_module(Path(__file__).resolve().parents[1] / "metrics"
                     / "collective_share.train.py")


def rec(op_s, busy_s=2.0, devices=4, traced_steps=2):
    return {"traced_steps": traced_steps,
            "trace": {"op_s": op_s, "busy_s": busy_s, "window_s": 2.1,
                      "devices": devices}}


def test_sync_and_async_collectives_over_busy_time():
    op_s = {"gemm_aie": 5.0, "fusion": 1.0,
            "all-gather": 0.4, "all-gather-start": 0.1,
            "all-gather-done": 0.1, "all-reduce": 0.2,
            "reduce-scatter": 0.1, "all-to-all": 0.05,
            "collective-permute-start": 0.02,
            "collective-permute-done": 0.03,
            "all-reduce-start": 0.05, "all-reduce-done": 0.05,
            # not collectives: a gather, a reduction, a copy
            "gather": 9.0, "reduce": 9.0, "copy-start": 9.0}
    # 1.1 s of collectives over 4 devices, 2.0 s busy per device
    assert READER.read(rec(op_s)) == pytest.approx(100 * 1.1 / 4 / 2.0)


def test_no_collective_reads_zero():
    assert READER.read(rec({"gemm_aie": 1.0})) == 0.0


@pytest.mark.parametrize("r", [
    {"traced_steps": 0, "trace": None},
    {"traced_steps": 2},
    rec({"all-gather": 1.0}, traced_steps=0),
    rec({"all-gather": 1.0}, busy_s=0.0)])
def test_nothing_to_read(r):
    assert READER.read(r) is None
