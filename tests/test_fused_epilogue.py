"""Fused-epilogue GEMM subsystem: interpret-mode kernel parity vs the
unfused reference compositions for every epilogue variant
(bias/activation/residual x bf16/W8A16/W8A8), dual-B gated-kernel parity
vs the unfused SwiGLU composition (including grads through both custom
VJPs), the traffic-aware DSE extensions, and the tb feasibility
fallback."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant
from repro.core import dse
from repro.core.bandwidth import hbm_traffic_bytes
from repro.core.hardware import TPU_V5E
from repro.core.memory_model import fits_vmem, vmem_footprint
from repro.core.tiling import GemmProblem, TileConfig
from repro.kernels import ops, ref
from repro.kernels.epilogue import ACTIVATIONS, Epilogue, apply_epilogue
from repro.kernels.gemm_aie import gemm_aie
from repro.kernels.gemm_gated import gemm_gated
from repro.kernels.gemm_tb import feasible_bk, gemm_tb

# These suites exercise the deprecated legacy entrypoints on purpose
# (old-vs-new parity is the point); the -W error::DeprecationWarning
# CI invocation must not fail them.
pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")



M, K, N = 64, 256, 128


def _operands(mode: str, key=0):
    """(a, b, b_scale) for one precision mode."""
    ka, kb = jax.random.split(jax.random.PRNGKey(key))
    w = jax.random.normal(kb, (K, N), jnp.float32)
    if mode == "bf16":
        return (jax.random.normal(ka, (M, K), jnp.bfloat16),
                w.astype(jnp.bfloat16), None)
    wq = quant.quantize_weight(w)
    if mode == "w8a16":
        return (jax.random.normal(ka, (M, K), jnp.bfloat16),
                wq["q"], wq["scale"])
    assert mode == "w8a8"
    a_q, _ = ref.quantize_int8(jax.random.normal(ka, (M, K), jnp.float32),
                               axis=-1)
    return a_q, wq["q"], wq["scale"]


EP_VARIANTS = {
    "bias": dict(bias=True),
    "silu": dict(activation="silu"),
    "gelu": dict(activation="gelu"),
    "relu": dict(activation="relu"),
    "res": dict(residual=True),
    "bias+silu+res": dict(bias=True, activation="silu", residual=True),
}


def _ep_operands(flags, key=7):
    bias = res = None
    if flags.get("bias"):
        bias = jax.random.normal(jax.random.PRNGKey(key), (1, N),
                                 jnp.float32)
    if flags.get("residual"):
        res = jax.random.normal(jax.random.PRNGKey(key + 1), (M, N),
                                jnp.float32)
    return bias, res


# ----------------------------------------------------- spec round-trip

def test_epilogue_spec_roundtrip_and_validation():
    for flags in EP_VARIANTS.values():
        ep = Epilogue(bias=flags.get("bias", False),
                      activation=flags.get("activation"),
                      residual=flags.get("residual", False))
        assert Epilogue.parse(ep.key) == ep
        assert bool(ep)
    assert Epilogue.parse("") == Epilogue() and not Epilogue()
    assert Epilogue(out_quant=True).key == "q8"
    with pytest.raises(ValueError):
        Epilogue(activation="tanh")
    with pytest.raises(ValueError):
        Epilogue.parse("bias+nonsense")


# ------------------------------------------- kernel-level parity sweep

@pytest.mark.parametrize("strategy", ["aie", "tb"])
@pytest.mark.parametrize("mode", ["bf16", "w8a16", "w8a8"])
@pytest.mark.parametrize("variant", sorted(EP_VARIANTS), ids=str)
def test_kernel_epilogue_matches_unfused_composition(strategy, mode,
                                                     variant):
    flags = EP_VARIANTS[variant]
    a, b, b_scale = _operands(mode)
    bias, res = _ep_operands(flags)
    tile = TileConfig(32, 128, 128, strategy)
    fn = gemm_aie if strategy == "aie" else gemm_tb
    got = fn(a, b, tile=tile, b_scale=b_scale, bias=bias, residual=res,
             activation=flags.get("activation"), out_dtype=jnp.float32,
             interpret=True)

    # unfused composition: plain GEMM (+ explicit dequant), then the
    # epilogue as separate XLA ops in fp32
    if b_scale is None:
        z = ref.gemm_ref(a, b, out_dtype=jnp.float32)
    else:
        z = ref.gemm_fused_ref(a, b, b_scale, out_dtype=jnp.float32)
    want = apply_epilogue(z, activation=flags.get("activation"),
                          bias=bias, residual=res)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("strategy", ["aie", "tb"])
def test_kernel_out_quant_epilogue(strategy):
    """Optional quantized output: the flush divides by the given scale,
    rounds and clips to int8."""
    a, b, _ = _operands("bf16")
    osc = jnp.asarray([[0.05]], jnp.float32)
    tile = TileConfig(32, 128, 128, strategy)
    fn = gemm_aie if strategy == "aie" else gemm_tb
    got = fn(a, b, tile=tile, activation="relu", out_scale=osc,
             out_dtype=jnp.int8, interpret=True)
    z = ref.gemm_ref(a, b, out_dtype=jnp.float32)
    want = jnp.clip(jnp.round(jax.nn.relu(z) / 0.05), -127, 127) \
        .astype(jnp.int8)
    assert got.dtype == jnp.int8
    # bf16 accumulation noise may flip a borderline rounding by 1 LSB
    diff = np.abs(np.asarray(got, np.int32) - np.asarray(want, np.int32))
    assert diff.max() <= 1


# ------------------------------------------------------- gated kernel

@pytest.mark.parametrize("mode", ["bf16", "w8a16", "w8a8"])
def test_gated_kernel_matches_unfused_swiglu(mode):
    a, bg, sg = _operands(mode, key=0)
    _, bu, su = _operands(mode, key=1)
    tile = TileConfig(32, 128, 128, "aie")
    got = gemm_gated(a, bg, bu, tile=tile, bg_scale=sg, bu_scale=su,
                     out_dtype=jnp.float32, interpret=True)
    # unfused: two separate GEMMs, silu and multiply in XLA
    if sg is None:
        zg = ref.gemm_ref(a, bg, out_dtype=jnp.float32)
        zu = ref.gemm_ref(a, bu, out_dtype=jnp.float32)
    else:
        zg = ref.gemm_fused_ref(a, bg, sg, out_dtype=jnp.float32)
        zu = ref.gemm_fused_ref(a, bu, su, out_dtype=jnp.float32)
    want = jax.nn.silu(zg) * zu
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_ops_gemm_gated_interpret_matches_model_swiglu(monkeypatch):
    """ops-level gated dispatch (interpret) vs the unfused model-layer
    composition it replaced, on a (b, s, d) activation."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 192),
                          jnp.bfloat16)
    wg = jax.random.normal(jax.random.PRNGKey(1), (192, 256),
                           jnp.bfloat16)
    wu = jax.random.normal(jax.random.PRNGKey(2), (192, 256),
                           jnp.bfloat16)
    got = ops.gemm_gated(x, wg, wu)
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    gate = ops.gemm(x, wg)
    up = ops.gemm(x, wu)
    want = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)
    assert got.shape == (2, 8, 256) and got.dtype == x.dtype


def test_ops_gemm_fused_quant_struct_interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    a = jax.random.normal(jax.random.PRNGKey(0), (16, 256), jnp.bfloat16)
    wq = quant.quantize_weight(
        jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.float32))
    bias = jax.random.normal(jax.random.PRNGKey(2), (128,), jnp.float32)
    res = jax.random.normal(jax.random.PRNGKey(3), (16, 128),
                            jnp.bfloat16)
    got = ops.gemm_fused(a, wq, bias=bias, activation="silu",
                         residual=res, out_dtype=jnp.float32)
    w = quant.dequantize_weight(wq, jnp.float32)
    want = jax.nn.silu(a.astype(jnp.float32) @ w + bias) \
        + res.astype(jnp.float32)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 2e-2, rel


# ------------------------------------------------------------- grads

def test_gemm_fused_grads_match_unfused_composition():
    a = jax.random.normal(jax.random.PRNGKey(0), (16, 64), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    bias = jax.random.normal(jax.random.PRNGKey(2), (32,), jnp.float32)
    res = jax.random.normal(jax.random.PRNGKey(3), (16, 32), jnp.float32)

    def fused(a, b, bias, res):
        return jnp.sum(ops.gemm_fused(a, b, bias=bias, activation="silu",
                                      residual=res) ** 2)

    def unfused(a, b, bias, res):
        return jnp.sum((jax.nn.silu(a @ b + bias) + res) ** 2)

    got = jax.grad(fused, argnums=(0, 1, 2, 3))(a, b, bias, res)
    want = jax.grad(unfused, argnums=(0, 1, 2, 3))(a, b, bias, res)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_gemm_gated_grads_match_unfused_composition():
    a = jax.random.normal(jax.random.PRNGKey(0), (16, 64), jnp.float32)
    bg = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    bu = jax.random.normal(jax.random.PRNGKey(2), (64, 32), jnp.float32)

    def fused(a, bg, bu):
        return jnp.sum(ops.gemm_gated(a, bg, bu) ** 2)

    def unfused(a, bg, bu):
        return jnp.sum((jax.nn.silu(a @ bg) * (a @ bu)) ** 2)

    got = jax.grad(fused, argnums=(0, 1, 2))(a, bg, bu)
    want = jax.grad(unfused, argnums=(0, 1, 2))(a, bg, bu)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_gemm_fused_quant_grad_dequantizes_only_in_backward():
    a = jax.random.normal(jax.random.PRNGKey(0), (16, 64), jnp.float32)
    wq = quant.quantize_weight(
        jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32))
    wd = quant.dequantize_weight(wq, jnp.float32)
    bias = jnp.zeros((32,), jnp.float32)
    ga = jax.grad(lambda x: jnp.sum(ops.gemm_fused(
        x, wq, bias=bias, activation="gelu") ** 2))(a)
    want = jax.grad(lambda x: jnp.sum(jax.nn.gelu(x @ wd) ** 2))(a)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_gemm_gated_quant_grad():
    a = jax.random.normal(jax.random.PRNGKey(0), (16, 64), jnp.float32)
    wgq = quant.quantize_weight(
        jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32))
    wuq = quant.quantize_weight(
        jax.random.normal(jax.random.PRNGKey(2), (64, 32), jnp.float32))
    wg = quant.dequantize_weight(wgq, jnp.float32)
    wu = quant.dequantize_weight(wuq, jnp.float32)
    ga = jax.grad(lambda x: jnp.sum(ops.gemm_gated(x, wgq, wuq) ** 2))(a)
    want = jax.grad(
        lambda x: jnp.sum((jax.nn.silu(x @ wg) * (x @ wu)) ** 2))(a)
    # fused-int8 dot vs dequantize-first dot: identical math, different
    # reduction order -> ~1e-3 relative float noise
    np.testing.assert_allclose(np.asarray(ga), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------- model layers

def test_swiglu_residual_fusion_matches_old_composition():
    from repro.models import layers as L
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 96), jnp.float32)
    params = L.init_swiglu(jax.random.PRNGKey(1), 96, 192, jnp.float32)
    got = L.swiglu(params, x, residual=x)
    gate = ops.gemm(x, params["w_gate"])
    up = ops.gemm(x, params["w_up"])
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    want = x + ops.gemm(h, params["w_down"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_attention_block_residual_fusion():
    from repro.models import layers as L
    spec = L.AttnLayerSpec(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16)
    params = L.init_attention(jax.random.PRNGKey(0), spec, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64), jnp.float32)
    got = L.attention_block(params, x, spec, residual=x)
    want = x + L.attention_block(params, x, spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------- cost model / DSE

def test_vmem_footprint_bills_epilogue_and_second_b():
    t = TileConfig(128, 512, 512, "aie")
    base = vmem_footprint(t, GemmProblem(128, 2048, 2048))
    ep = vmem_footprint(
        t, GemmProblem(128, 2048, 2048, epilogue="bias+silu+res"))
    assert ep.bias_bytes > 0 and ep.residual_bytes > 0
    assert ep.total > base.total
    gated = vmem_footprint(
        t, GemmProblem(128, 2048, 2048, epilogue="silu", n_b_operands=2))
    assert gated.b_bytes == 2 * base.b_bytes
    assert gated.acc_bytes == 2 * base.acc_bytes


def test_hbm_traffic_bills_dual_b_and_residual():
    t = TileConfig(16, 512, 512, "aie")
    p1 = GemmProblem(16, 4096, 4096)
    p2 = GemmProblem(16, 4096, 4096, epilogue="silu", n_b_operands=2)
    extra = hbm_traffic_bytes(t, p2) - hbm_traffic_bytes(t, p1)
    assert extra == pytest.approx(p1.b_bytes, rel=1e-6)  # second B once
    pres = GemmProblem(16, 4096, 4096, epilogue="res")
    assert hbm_traffic_bytes(t, pres) - hbm_traffic_bytes(t, p1) \
        == pytest.approx(16 * 4096 * 2)                  # residual read


def test_dse_gated_search_is_aie_only_and_feasible():
    for d in dse.solve(GemmProblem(16, 4096, 14336, epilogue="silu",
                                   n_b_operands=2)):
        assert d.tile.strategy == "aie"
        assert fits_vmem(d.tile,
                         GemmProblem(16, 4096, 14336, epilogue="silu",
                                     n_b_operands=2), TPU_V5E)
    t = dse.best_tile(16, 4096, 14336, epilogue="silu", n_b_operands=2)
    assert t.strategy == "aie"


def test_dse_cache_distinguishes_epilogue():
    a = dse.solve(GemmProblem(64, 1024, 1024), top=1)[0]
    b = dse.solve(GemmProblem(64, 1024, 1024, epilogue="res"), top=1)[0]
    assert b.traffic.hbm_bytes > a.traffic.hbm_bytes


def test_plan_explain_agrees_with_cost_model():
    """GemmPlan carries exactly the DSE/traffic-model numbers: the tile
    is ``dse.best_tile``'s winner and the modeled bytes are
    ``hbm_traffic_bytes`` at that tile, for the decode- and train-shaped
    cases asserted throughout this module."""
    from repro.kernels import api
    # decode-shaped gated SwiGLU up-projection (16 x 4096 x ff 14336)
    pl = api.plan(api.GemmSpec(gated=True, epilogue="silu"),
                  (16, 4096, 14336))
    assert pl.tile == dse.best_tile(16, 4096, 14336, epilogue="silu",
                                    n_b_operands=2)
    assert pl.hbm_bytes == hbm_traffic_bytes(pl.tile, pl.problem)
    assert f"{pl.hbm_bytes / 2**20:.2f} MiB" in pl.explain()
    # train-shaped residual down-projection (8192 x 14336 x 4096)
    pl2 = api.plan(api.GemmSpec(epilogue="res"), (8192, 14336, 4096))
    assert pl2.tile == dse.best_tile(8192, 14336, 4096, epilogue="res")
    assert pl2.hbm_bytes == hbm_traffic_bytes(pl2.tile, pl2.problem)
    assert pl2.flops == pl2.traffic.flops
    assert pl2.vmem_bytes == pl2.vmem.total


def test_decode_swiglu_modeled_hbm_drop():
    """Acceptance criterion: decode-shaped SwiGLU (16x4096, d_ff 14336).
    The weight stream is an irreducible floor both sides share, so the
    fusion credit lands on the activation/intermediate traffic: >= 30%
    modeled drop (measured ~53%)."""
    fused = dse.mlp_traffic(16, 4096, 14336, fused=True)
    unfused = dse.mlp_traffic(16, 4096, 14336, fused=False)
    assert fused["weights"] == unfused["weights"]        # same floor
    assert fused["activations"] <= 0.7 * unfused["activations"], \
        (fused, unfused)
    assert fused["total"] < unfused["total"]


def test_train_swiglu_modeled_hbm_drop_total():
    """At train/prefill shapes the (m, d_ff) intermediates dominate and
    the >= 30% drop holds on TOTAL modeled layer bytes (measured ~35%)."""
    fused = dse.mlp_traffic(8192, 4096, 14336, fused=True, residual=True)
    unfused = dse.mlp_traffic(8192, 4096, 14336, fused=False,
                              residual=True)
    assert fused["total"] <= 0.7 * unfused["total"], (fused, unfused)


# --------------------------------------------- tb feasibility satellite

def test_feasible_bk_shrinks_oversized_k_chunk():
    # (2048, 2048) f32 A resident + B streams + rmw C streams: ~112 MiB,
    # over the 100 MiB scoped limit — the k-chunk must refine
    big = TileConfig(2048, 2048, 2048, "tb")
    p = GemmProblem(2048, 8192, 2048, "float32", "float32")
    assert not fits_vmem(big, p)
    bk = feasible_bk(2048, 8192, 2048, big, jnp.float32, jnp.float32,
                     jnp.float32, jnp.float32)
    assert 0 < bk < 2048
    assert 8192 % bk == 0
    assert fits_vmem(TileConfig(2048, bk, 2048, "tb"), p)


def test_gemm_tb_refines_infeasible_bk_and_stays_correct():
    m, k, n = 256, 1024, 256
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    # tiny budget forces the refinement path deterministically: monkey-
    # patching is avoided by picking a tile that is feasible (so no
    # error) — correctness must be identical whatever bk is used
    got = gemm_tb(a, b, tile=TileConfig(256, 1024, 256, "tb"),
                  interpret=True)
    want = ref.gemm_ref(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_gemm_tb_raises_when_blocks_cannot_fit(monkeypatch):
    from repro.core import memory_model
    monkeypatch.setattr(memory_model, "fits_vmem",
                        lambda *a, **kw: False)
    # shapes unique to this test: gemm_tb is jit-cached on the static
    # (shape, tile) signature, and a hit would skip the trace-time check
    a = jnp.zeros((128, 640), jnp.float32)
    b = jnp.zeros((640, 128), jnp.float32)
    with pytest.raises(ValueError, match="infeasible"):
        gemm_tb(a, b, tile=TileConfig(128, 128, 128, "tb"),
                interpret=True)


def test_explicit_infeasible_tb_tile_raises(monkeypatch):
    """The plan-level gate: an explicit tile= override is honored
    verbatim, and one that can never fit raises at plan time instead of
    being silently replaced by another kernel's tile."""
    from repro.kernels import api
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    monkeypatch.setattr(api, "feasible_bk", lambda *a, **kw: 0)
    api.plan_cache_clear()
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 256), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.bfloat16)
    try:
        with pytest.raises(ValueError, match="infeasible"):
            ops.gemm(a, b, tile=TileConfig(64, 128, 128, "tb"))
    finally:
        api.plan_cache_clear()


def test_dse_tb_winner_falls_back_to_aie_with_reason(monkeypatch):
    """A strategy='tb' *hint* (no explicit tile) whose DSE winner fails
    the post-clamp viability recheck re-routes to the aie winner and the
    plan records why — the old silent fallback, now introspectable."""
    from repro.kernels import api
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    monkeypatch.setattr(api, "feasible_bk", lambda *a, **kw: 0)
    api.plan_cache_clear()
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 256), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.bfloat16)
    try:
        spec = api.GemmSpec.for_operands(a, b, strategy="tb")
        pl = api.plan(spec, api.gemm_shapes(a, b))
        assert pl.tile.strategy == "aie"
        assert pl.fallback_reason and "aie" in pl.fallback_reason
        assert "fallback" in pl.explain()
        got = api.execute(pl, a, b)
        want = ref.gemm_ref(a, b, out_dtype=jnp.bfloat16)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=5e-2)
    finally:
        api.plan_cache_clear()


# ------------------------------------- xent fp32 emission satellite

def test_gemm_ref_keeps_operands_at_storage_dtype():
    """The fp32-upcast-round-trip fix: fp32 logits must come from
    preferred_element_type accumulation, not from pre-cast fp32 copies of
    the bf16 operands (k*V extra HBM bytes on the lm_head hot path)."""
    a = jnp.zeros((8, 64), jnp.bfloat16)
    b = jnp.zeros((64, 32), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda a, b: ref.gemm_ref(a, b, out_dtype=jnp.float32))(a, b)
    prims = [e.primitive.name for e in jaxpr.eqns]
    assert "convert_element_type" not in prims, prims
    dot = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"][0]
    assert dot.params["preferred_element_type"] == jnp.float32


def test_w8a8_mode_keeps_int8_path_for_linear_epilogue():
    """Residual/bias-only epilogues commute with the per-row activation
    scale, so w8a8 mode must keep the int8 x int8 MXU path (epilogue
    applied outside); nonlinear epilogues fall back to fused W8A16."""
    a = jax.random.normal(jax.random.PRNGKey(0), (8, 128), jnp.float32)
    wq = quant.quantize_weight(
        jax.random.normal(jax.random.PRNGKey(1), (128, 64), jnp.float32))
    res = jax.random.normal(jax.random.PRNGKey(2), (8, 64), jnp.float32)
    quant.set_activation_mode("w8a8")
    try:
        lin = ops.gemm_fused(a, wq, residual=res)
        # int8 x int8 GEMM + residual outside == w8a8 gemm + res
        want = ops.gemm(a, wq, out_dtype=jnp.float32) + res
        np.testing.assert_allclose(np.asarray(lin), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        nonlin = ops.gemm_fused(a, wq, activation="silu")
    finally:
        quant.set_activation_mode("none")
    # nonlinear: W8A16 (no activation quant) — matches the plain ref
    want_nl = jax.nn.silu(a @ quant.dequantize_weight(wq, jnp.float32))
    rel = float(jnp.linalg.norm(nonlin - want_nl)
                / jnp.linalg.norm(want_nl))
    assert rel < 2e-2, rel
    # and the w8a8 quantization error is visible in the linear path
    exact = a @ quant.dequantize_weight(wq, jnp.float32) + res
    assert float(jnp.linalg.norm(lin - exact)
                 / jnp.linalg.norm(exact)) < 0.05


def test_activation_table_matches_model_functions():
    z = jnp.linspace(-3, 3, 64)
    np.testing.assert_allclose(np.asarray(ACTIVATIONS["silu"](z)),
                               np.asarray(jax.nn.silu(z)))
    np.testing.assert_allclose(np.asarray(ACTIVATIONS["gelu"](z)),
                               np.asarray(jax.nn.gelu(z)))
