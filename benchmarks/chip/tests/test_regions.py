"""Device time by region: the HLO join of ``regions.py`` on hand-made
HLO, on the scoped trace recorded on a TPU v5e with its compiled HLO
(``testdata/record_scoped.py``: one train step of the program's
smoke-size smollm-360m), and ``trace.reduce``'s own output pinned on the
small trace, which the region sums sit beside and do not change."""

import json
from pathlib import Path

import pytest

import smoke  # noqa: F401  (puts the benchmark on sys.path)
import regions
import trace

DATA = Path(__file__).resolve().parents[1] / "testdata"
SMALL = DATA / "small.xplane.pb"
SCOPED = DATA / "scoped.xplane.pb"
SCOPED_HLO = DATA / "scoped.hlo.txt"
SMOKE = Path(__file__).resolve().parent / "data"

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %exponential.1 = f32[4]{0} exponential(%param_0), metadata={op_name="jit(step)/transpose(jvp(layers))/while/body/checkpoint/attention/transpose(jvp())/exp"}
}

%body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %get-tuple-element.1 = f32[4]{0} get-tuple-element(%arg), index=1
  %fusion.7 = f32[4]{0} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation
  %copy.3 = f32[4]{0} copy(%fusion.7)
  %dynamic-update-slice.2 = f32[4]{0} dynamic-update-slice(%copy.3), metadata={op_name="jit(step)/jvp(layers)/while/body/dynamic_update_slice"}
  %get-tuple-element.2 = s32[] get-tuple-element(%arg), index=0
  ROOT %tuple.4 = (s32[], f32[4]{0}) tuple(%get-tuple-element.2, %dynamic-update-slice.2)
}

%cond (arg.1: (s32[], f32[4])) -> pred[] {
  %arg.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %constant.9 = pred[] constant(true)
}

ENTRY %main.5 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0), metadata={op_name="state.params"}
  %broadcast.1 = f32[4]{0} broadcast(%p), dimensions={0}
  %multiply.2 = f32[4]{0} multiply(%broadcast.1, %p), metadata={op_name="jit(step)/grad_clip/mul"}
  %constant.1 = s32[] constant(0)
  %tuple.1 = (s32[], f32[4]{0}) tuple(%constant.1, %multiply.2)
  %while.1 = (s32[], f32[4]{0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(layers)/while"}
  %get-tuple-element.3 = f32[4]{0} get-tuple-element(%while.1), index=1
  ROOT %add.1 = f32[4]{0} add(%get-tuple-element.3, %p), metadata={op_name="jit(step)/optimizer/optimizer/add"}
}
"""


def test_region_of_path_unwraps_transforms():
    assert regions.region_of_path(
        "jit(train_step)/transpose(jvp(layers))/while/body/closed_call/"
        "checkpoint/rematted_computation/attention/exp") == "attention"
    assert regions.region_of_path(
        "jit(train_step)/jvp(layers)/while/body/dynamic_slice") \
        == regions.GLUE
    assert regions.region_of_path("jit(train_step)/jvp(norm)/mul") \
        == "norm"
    assert regions.region_of_path("jit(train_step)/add") is None
    assert regions.region_of_path("jit(layers_x)/embedding") is None


def test_hand_made_hlo():
    hlo = regions.parse(HLO)
    assert hlo.module == "jit_step"
    assert hlo.computations["body"][0] == "tuple.4"          # root first
    assert hlo.caller["body"] == "while.1"
    got = regions.regions(hlo)
    # own op_name; a fusion by its fused root; a copy by its user; a
    # loop body's unnamed ops by the loop; entry bookkeeping unscoped
    assert got["multiply.2"] == "grad_clip"
    assert got["add.1"] == "optimizer"
    assert got["fusion.7"] == "attention"
    assert got["copy.3"] == regions.GLUE
    assert got["get-tuple-element.2"] == regions.GLUE
    assert got["broadcast.1"] == "grad_clip"
    assert got["while.1"] == regions.GLUE
    assert got["constant.1"] == regions.GLUE     # feeds the loop's init
    assert got["exponential.1"] == "attention"
    assert set(regions.executed(hlo)) == set(hlo.instrs) - {
        "param_0", "exponential.1"}


def test_small_trace_reduce_is_pinned():
    """``trace.reduce``'s whole output on the small trace, as recorded
    before region sums existed."""
    r = trace.reduce(trace.load(str(SMALL)))
    assert set(r) == {"busy_s", "window_s", "devices", "ops", "family_s",
                      "op_s", "breakdown"}
    assert (r["devices"], r["ops"]) == (1, 24)
    assert r["busy_s"] == pytest.approx(4.7256e-05, abs=1e-12)
    assert r["window_s"] == pytest.approx(0.002142194, abs=1e-12)
    assert r["family_s"] == pytest.approx(
        {"gemm_pallas": 2.2546e-05, "attention_pallas": 8.626e-06,
         "matmul_xla": 0.0}, abs=1e-12)
    want = [["gemm_tb", 2.2546e-05], ["flash_attention", 8.626e-06],
            ["copy-done", 7.584e-06], ["convert_element_type", 4.217e-06],
            ["copy", 1.907e-06], ["copy_bitcast_fusion", 1.578e-06],
            ["broadcast_in_dim", 7.74e-07], ["copy-start", 2.4e-08]]
    assert [n for n, _ in r["breakdown"]["device_ops"]] == \
        [n for n, _ in want]
    assert [s for _, s in r["breakdown"]["device_ops"]] == \
        pytest.approx([s for _, s in want], abs=1e-12)
    assert r["op_s"] == pytest.approx(dict(want), abs=1e-12)
    assert r["breakdown"]["idle_gaps"] == [
        ["$profiler.py:101 start_trace", pytest.approx(0.000803799)],
        ["no host event", pytest.approx(0.00072441)],
        ["$api.py:3108 try_to_block", pytest.approx(0.000566707)]]


def test_a_foreign_module_does_not_join():
    # the small trace ran jit__lambda modules, not jit_step
    r = regions.reduce(trace.load(str(SMALL)), HLO)
    assert r["region_join_share"] == 0.0
    assert list(r["region_s"]) == [regions.UNJOINED]
    assert r["region_s"][regions.UNJOINED] == pytest.approx(4.7256e-05,
                                                            abs=1e-12)


@pytest.fixture(scope="module")
def scoped():
    prof = trace.load(str(SCOPED))
    hlo = SCOPED_HLO.read_text()
    return prof, hlo, trace.reduce(prof), regions.reduce(prof, hlo)


def test_scoped_regions_sum_to_the_op_sums(scoped):
    prof, hlo, base, got = scoped
    assert got["region_join_share"] == 1.0
    assert set(got["region_s"]) == set(regions.REGIONS) - {"layers"} \
        | {regions.GLUE, regions.UNSCOPED}
    # every op event once: the regions hold the busy time of the one
    # device (no op overlaps another there) and each op name's total
    assert sum(got["region_s"].values()) == pytest.approx(base["busy_s"],
                                                          abs=1e-12)
    by_name = {}
    for name, _, d in regions.events(prof, hlo):
        key = trace.op_name(name + " =")[0]
        by_name[key] = by_name.get(key, 0) + d / 1e9
    assert {k: by_name[k] for k in base["op_s"]} == pytest.approx(
        base["op_s"], abs=1e-12)


def test_scoped_kernels_fall_in_their_regions(scoped):
    prof, hlo, base, got = scoped
    gemm = {}
    for name, region, d in regions.events(prof, hlo):
        if trace.op_name(name + " =")[0].startswith("gemm_"):
            gemm[region] = gemm.get(region, 0) + d / 1e9
    assert set(gemm) == {"qkv", "attn_out", "mlp", "lm_head_xent"}
    assert sum(gemm.values()) == pytest.approx(
        base["family_s"]["gemm_pallas"], abs=1e-12)
    s = got["region_s"]
    assert s["lm_head_xent"] == pytest.approx(4.8368e-05, abs=1e-12)
    assert s[regions.GLUE] == pytest.approx(5.13e-06, abs=1e-12)
    assert s["attention"] == pytest.approx(4.203e-06, abs=1e-12)
    assert s[regions.UNSCOPED] == pytest.approx(1.373e-06, abs=1e-12)


def test_scoped_readers(scoped):
    _, _, base, got = scoped
    c = json.loads((SMOKE / "smollm-360m-smoke.json").read_text())
    p = json.loads((SMOKE / "train-smoke.json").read_text())
    rec = {"trace": dict(base, **got), "traced_steps": 1, "config": c,
           "traffic": p, "peak": {"bf16_flops": 197e12,
                                  "hbm_bytes_per_s": 819e9}}
    # smoke attention, causal over 32 positions (mean context 16.5), 128
    # tokens: 3 x 4 x 2 layers x 3 heads x 20 x 16.5 x 128 = 3.04e6
    # operations (15.4 ns); bytes, f32: 6 x 2 x 128 x 20 x 4 x (3 + 1)
    # = 491,520 (600.1 ns) bound it
    least = 6 * 2 * 128 * 20 * 4 * (3 + 1) / 819e9
    assert regions.attention_least_seconds(c, p, rec["peak"]) \
        == pytest.approx(least)
    assert regions.attn_roofline(rec) == pytest.approx(
        100 * least / 4.203e-06)
    assert regions.scan_glue_share(rec) == pytest.approx(
        100 * 5.13e-06 / 0.000102882)
    # a trace without regions, as the reduction gives it today, reads
    # nothing
    rec["trace"] = base
    assert regions.attn_roofline(rec) is None
    assert regions.scan_glue_share(rec) is None
