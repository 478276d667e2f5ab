"""Mixed-precision GEMM end-to-end: per-operand dtypes through the cost
model (W8A16 halves modeled weight traffic) and fused int8-weight Pallas
kernels (interpret-mode parity vs dequantize-first references)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant
from repro.core import dse
from repro.core.bandwidth import estimate
from repro.core.hardware import TPU_V5E
from repro.core.memory_model import VMEM_HEADROOM, fits_vmem, \
    vmem_footprint
from repro.core.tiling import GemmProblem, TileConfig
from repro.kernels import ops, ref
from repro.kernels.gemm_aie import gemm_aie
from repro.kernels.gemm_tb import gemm_tb

# These suites exercise the deprecated legacy entrypoints on purpose
# (old-vs-new parity is the point); the -W error::DeprecationWarning
# CI invocation must not fail them.
pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")



# --------------------------------------------------- cost-model layer

def test_gemm_problem_per_operand_dtypes_and_compat():
    p = GemmProblem(16, 4096, 4096, "bfloat16", "bfloat16", "float32",
                    "int8")
    assert p.mixed
    assert p.a_dtype == "bfloat16" and p.b_dtype == "int8"
    assert p.in_dtype == "bfloat16"          # compat property = A dtype
    assert p.a_bytes == 16 * 4096 * 2
    assert p.b_bytes == 4096 * 4096          # one byte/element
    # b_dtype=None means uniform precision (old constructor semantics)
    u = GemmProblem(64, 64, 64, "int8", "int8", "int32")
    assert u.b_dtype == "int8" and not u.mixed


def test_vmem_footprint_bills_b_at_its_own_width():
    p16 = GemmProblem(128, 2048, 2048, "bfloat16", "bfloat16")
    p8 = GemmProblem(128, 2048, 2048, "bfloat16", "bfloat16",
                     "float32", "int8")
    for strategy in ("aie", "tb"):
        t = TileConfig(128, 512, 512, strategy)
        f16 = vmem_footprint(t, p16, TPU_V5E)
        f8 = vmem_footprint(t, p8, TPU_V5E)
        assert f8.b_bytes * 2 == f16.b_bytes
        assert f8.a_bytes == f16.a_bytes
        assert f8.scale_bytes > 0            # fused scale-vector block


def test_int8_b_roughly_doubles_feasible_bk():
    """The DSE's capacity constraint admits ~2x deeper k-blocks when B
    streams at one byte/element (the fused-dequant win).  A tight budget
    scoped limit makes the constraint binding at candidate-grid sizes."""
    m, k, n = 16, 8192, 8192
    # working sets up to ~1.3 MiB fit: the B block binds
    tight = dataclasses.replace(
        TPU_V5E, vmem_limit_bytes=round(1.28 * 2**20 * VMEM_HEADROOM))

    def max_bk(b_dtype):
        best = 0
        for bk in (128, 256, 512, 1024, 2048):
            t = TileConfig(16, bk, 512, "aie")
            p = GemmProblem(m, k, n, "bfloat16", "bfloat16", "float32",
                            b_dtype)
            if fits_vmem(t, p, tight):
                best = bk
        return best

    assert max_bk("int8") == 2 * max_bk("bfloat16") > 0


def test_w8a16_decode_traffic_under_60_percent():
    """Acceptance criterion: decode-shaped W8A16 (m=16, k=n=4096) HBM
    traffic <= 60% of the bf16-weights design."""
    t8 = dse.best_tile(16, 4096, 4096, "bfloat16", b_dtype="int8")
    t16 = dse.best_tile(16, 4096, 4096, "bfloat16")
    p8 = GemmProblem(16, 4096, 4096, "bfloat16", "bfloat16", "float32",
                     "int8")
    p16 = GemmProblem(16, 4096, 4096, "bfloat16", "bfloat16")
    hbm8 = estimate(t8, p8, TPU_V5E).hbm_bytes
    hbm16 = estimate(t16, p16, TPU_V5E).hbm_bytes
    assert hbm8 <= 0.6 * hbm16, (hbm8, hbm16)


def test_w8a16_compute_peak_is_bf16_w8a8_is_int8():
    t = TileConfig(128, 512, 512, "aie")
    mixed = estimate(t, GemmProblem(128, 4096, 4096, "bfloat16",
                                    "bfloat16", "float32", "int8"))
    both8 = estimate(t, GemmProblem(128, 4096, 4096, "int8", "int32",
                                    "int32"))
    # same padded flops; int8 x int8 runs at the int8 peak (~2x bf16)
    ratio = TPU_V5E.peak_int8_ops / TPU_V5E.peak_bf16_flops
    assert mixed.t_compute == pytest.approx(ratio * both8.t_compute)


def test_gemm_int8_cost_model_bills_int32_output():
    """Satellite fix: the gemm_int8 DSE query must bill C at 4 bytes
    (the kernel writes the int32 accumulator)."""
    p = GemmProblem(512, 512, 512, "int8", "int32", "int32")
    for d in dse.solve(p, top=3):
        # real footprint of the tile the DSE scored, re-billed at the
        # int32 output the kernel writes, stays within budget
        assert fits_vmem(d.tile, p, TPU_V5E)
        assert d.traffic.hbm_bytes >= p.out_bytes   # 4-byte C counted
    assert p.out_bytes == 512 * 512 * 4


def test_solve_cache_distinguishes_b_dtype():
    a = dse.solve(GemmProblem(64, 1024, 1024, "bfloat16"), top=1)[0]
    b = dse.solve(GemmProblem(64, 1024, 1024, "bfloat16", "bfloat16",
                              "float32", "int8"), top=1)[0]
    assert b.traffic.hbm_bytes < a.traffic.hbm_bytes


# ------------------------------------------------------- kernel layer

@pytest.mark.parametrize("strategy", ["aie", "tb"])
@pytest.mark.parametrize("shape", [(128, 256, 256), (64, 384, 128)],
                         ids=str)
def test_fused_w8a16_matches_dequant_first(strategy, shape):
    m, k, n = shape
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    wq = quant.quantize_weight(w)
    tile = TileConfig(64, 128, 128, strategy)
    fn = gemm_aie if strategy == "aie" else gemm_tb
    got = fn(a, wq["q"], tile=tile, b_scale=wq["scale"], interpret=True)
    want = ref.gemm_ref(a, quant.dequantize_weight(wq, jnp.bfloat16),
                        out_dtype=jnp.float32)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 5e-3, (strategy, rel)      # int8 roundtrip tolerance


@pytest.mark.parametrize("strategy", ["aie", "tb"])
def test_fused_w8a8_matches_int32_reference(strategy):
    m, k, n = 128, 256, 128
    rng = np.random.default_rng(0)
    a_q, _ = ref.quantize_int8(
        jnp.asarray(rng.standard_normal((m, k)), jnp.float32), axis=-1)
    wq = quant.quantize_weight(
        jnp.asarray(rng.standard_normal((k, n)), jnp.float32))
    tile = TileConfig(64, 128, 128, strategy)
    fn = gemm_aie if strategy == "aie" else gemm_tb
    got = fn(a_q, wq["q"], tile=tile, b_scale=wq["scale"],
             interpret=True)
    want = ref.gemm_fused_ref(a_q, wq["q"], wq["scale"])
    # int32 accumulation + one fp32 scale multiply: bitwise equal
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ops_gemm_quant_struct_interpret_matches_ref(monkeypatch):
    a = jax.random.normal(jax.random.PRNGKey(0), (4, 24, 192),
                          jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (192, 320), jnp.float32)
    wq = quant.quantize_weight(w)
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    got = ops.gemm(a, wq)
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    want = ops.gemm(a, wq)
    assert got.shape == (4, 24, 320)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=1e-2)


@pytest.mark.parametrize("strategy", ["aie", "tb"])
def test_ops_gemm_fused_strategies_interpret(monkeypatch, strategy):
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    a = jax.random.normal(jax.random.PRNGKey(0), (16, 256), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.float32)
    wq = quant.quantize_weight(w)
    got = ops.gemm(a, wq, strategy=strategy)
    want = a.astype(jnp.float32) @ quant.dequantize_weight(
        wq, jnp.float32)
    rel = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert rel < 2e-2, (strategy, rel)


def test_ops_gemm_stacked_scan_leaves(monkeypatch):
    """Fused path under jax.lax.scan over a stacked (L, k, n) quantized
    leaf — how scanned model blocks consume per-layer weight slices."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    L, k, n = 3, 192, 128
    w = jax.random.normal(jax.random.PRNGKey(0), (L, k, n), jnp.float32)
    wq = quant.quantize_weight(w)                # (L,k,n) q, (L,1,n) scale
    assert wq["q"].shape == (L, k, n)
    assert wq["scale"].shape == (L, 1, n)
    x0 = jax.random.normal(jax.random.PRNGKey(1), (8, k), jnp.bfloat16)

    def body(x, layer):
        y = ops.gemm(x, layer, out_dtype=jnp.float32)
        return x, y

    _, ys = jax.lax.scan(body, x0, wq)
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    _, want = jax.lax.scan(body, x0, wq)
    np.testing.assert_allclose(np.asarray(ys, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=1e-2)


def test_fused_grad_dequantizes_only_in_backward():
    """d/dA of the fused path == d/dA against the dequantized weight."""
    a = jax.random.normal(jax.random.PRNGKey(0), (16, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    wq = quant.quantize_weight(w)
    wd = quant.dequantize_weight(wq, jnp.float32)
    ga = jax.grad(lambda x: jnp.sum(ops.gemm(x, wq) ** 2))(a)
    want = jax.grad(lambda x: jnp.sum((x @ wd) ** 2))(a)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------- W8A8 mode

def test_w8a8_activation_mode(monkeypatch):
    a = jax.random.normal(jax.random.PRNGKey(0), (32, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 64), jnp.float32)
    wq = quant.quantize_weight(w)
    assert quant.activation_mode() == "none"
    quant.set_activation_mode("w8a8")
    try:
        got = ops.gemm(a, wq)
    finally:
        quant.set_activation_mode("none")
    want = a @ w
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.03                       # W8A8 quantization error
    with pytest.raises(ValueError):
        quant.set_activation_mode("int4")


def test_w8a8_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_W8A8", "1")
    assert quant.activation_mode() == "w8a8"
    monkeypatch.setenv("REPRO_W8A8", "0")
    assert quant.activation_mode() == "none"
    monkeypatch.setenv("REPRO_W8A8", "false")   # strict: not "truthy"
    assert quant.activation_mode() == "none"
    monkeypatch.setenv("REPRO_W8A8", "yes")
    with pytest.raises(ValueError):
        quant.activation_mode()


# --------------------------------------------------- serve reporting

def test_gemm_weight_bytes_halves_under_int8():
    from repro.configs.base import get_smoke_config
    from repro.models import transformer as T
    cfg = get_smoke_config("minitron-8b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    dense = quant.gemm_weight_bytes(params)
    qparams, n = quant.quantize_params(params)
    fused = quant.gemm_weight_bytes(qparams)
    assert n > 0 and dense > 0
    # int8 q + f32 scale vs 2-byte (or wider) dense leaves
    assert fused < 0.6 * dense, (fused, dense)
