"""The trace reduction on a small trace recorded on a TPU v5e
(``testdata/record.py``: three planned GEMMs of 512x1024x768 and one
flash-attention forward)."""

from pathlib import Path

import pytest

import smoke  # noqa: F401
import trace

SMALL = Path(__file__).resolve().parents[1] / "testdata" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(str(SMALL)))


def test_busy_and_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["ops"] == 24
    assert reduced["busy_s"] == pytest.approx(4.7256e-05, abs=1e-12)
    assert reduced["window_s"] == pytest.approx(0.002142194, abs=1e-12)


def test_families(reduced):
    fam = reduced["family_s"]
    # the three gemm_tb custom calls, by hand from the trace
    assert fam["gemm_pallas"] == pytest.approx(2.2546e-05, abs=1e-12)
    assert fam["attention_pallas"] == pytest.approx(8.626e-06, abs=1e-12)
    assert fam["matmul_xla"] == 0.0


def test_breakdown(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert [n for n, _ in ops[:3]] == ["gemm_tb", "flash_attention",
                                       "copy-done"]
    assert len(ops) <= 10 and len(reduced["breakdown"]["idle_gaps"]) <= 10
    gaps = [s for _, s in reduced["breakdown"]["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= reduced["window_s"] - reduced["busy_s"] + 1e-12


def test_op_name():
    assert trace.op_name("%gemm_tb.1 = f32[512,768]{1,0:T(8,128)S(1)} "
                         "custom-call(bf16[512,1024] %x.1)") \
        == ("gemm_tb", "custom-call")
    assert trace.op_name("%copy-start = (bf16[1024,768]{1,0}, u32[]{:S(2)})"
                         " copy-start(bf16[1024,768] %y.1)") \
        == ("copy-start", "copy-start")
    assert trace.op_name("%while.301 = (s32[], bf16[16,2048,960]) "
                         "while((s32[]) %tuple)")[1] == "while"
