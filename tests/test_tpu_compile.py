"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Each case plans a GEMM or attention call through ``repro.ops`` under
Pallas dispatch and compiles it with the TPU compiler for a described
(not attached) ``v5e:2x2`` topology.  That compiler refuses a kernel
whose VMEM use exceeds its scoped limit, which the CPU suite and the
Pallas interpreter cannot see.  Widths are those of the shipped
configs: smollm-360m (d_model 960, d_ff 2560, vocab 49152, 15/5 heads
of 64), qwen3-moe expert banks (d_model 4096, d_ff 1536) and a
llama-class 4096x14336 MLP.  The h2o-danube-3-4b cases are the blocks
one chip of its (data 2, model 2) training mesh runs at seq 4096 and
global batch 8, forward and backward: 4 rows of the batch, 16 query and
4 kv heads of 120, and each GEMM's rows split over 'data' and columns
over 'model' with the contraction whole.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import ops

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def pallas(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    ops.plan_cache_clear()
    ops.attn_plan_cache_clear()
    yield
    ops.plan_cache_clear()
    ops.attn_plan_cache_clear()


def _lm_head(s):
    """One chunk of the chunked cross-entropy at seq 2048, batch 8."""
    return (lambda h, w: ops.gemm(h, w, out_dtype=F32),
            s((8, 256, 960), BF16), s((960, 49152), BF16))


def _mlp_up(s):
    return (lambda a, b: ops.gemm(a, b),
            s((4096, 14336), BF16), s((14336, 4096), BF16))


def _swiglu(s):
    return (lambda x, g, u: ops.gemm(x, g, b2=u, activation="silu"),
            s((8, 2048, 960), BF16), s((960, 2560), BF16),
            s((960, 2560), BF16))


def _w8a16_decode(s):
    return (lambda a, q, sc: ops.gemm(a, {"q": q, "scale": sc}),
            s((16, 4096), BF16), s((4096, 14336), I8),
            s((1, 14336), F32))


def _grouped(s):
    return (lambda x, bank, sizes: ops.gemm_grouped(
                x, bank, sizes, activation="silu", out_dtype=BF16),
            s((4096, 4096), BF16), s((8, 4096, 1536), BF16), s((8,), I32))


def _flash_prefill(s):
    return (lambda q, k, v: ops.attention(q, k, v, causal=True),
            s((1, 2048, 15, 64), BF16), s((1, 2048, 5, 64), BF16),
            s((1, 2048, 5, 64), BF16))


def _flash_decode(s):
    return (lambda q, k, v, pos: ops.decode_attention(q, k, v, pos),
            s((8, 15, 64), BF16), s((8, 1024, 5, 64), BF16),
            s((8, 1024, 5, 64), BF16), s((8,), I32))


def _paged_decode(s):
    return (lambda q, k, v, tbl, pos: ops.decode_attention_paged(
                q, k, v, tbl, pos),
            s((8, 15, 64), BF16), s((17, 512, 5, 64), BF16),
            s((17, 512, 5, 64), BF16), s((8, 2), I32), s((8,), I32))


CASES = {
    "lm_head_2048x960x49152_f32": _lm_head,
    "mlp_4096x14336x4096_bf16": _mlp_up,
    "swiglu_gated_smollm": _swiglu,
    "w8a16_decode_16x4096x14336": _w8a16_decode,
    "grouped_qwen3_moe_experts": _grouped,
    "flash_prefill_2048": _flash_prefill,
    "flash_decode": _flash_decode,
    "paged_decode": _paged_decode,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, pallas):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, *args = CASES[case](sds)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    plans = ops.plans() + ops.attn_plans()
    assert plans
    for p in plans:
        assert p.fallback_reason is None, p.explain()
    for p in ops.attn_plans():
        assert not p.kernel.startswith("xla"), p.explain()


def test_flash_backward_compiles_for_v5e(one_chip, pallas):
    """The training path of the smollm-360m cell's attention: the
    forward that writes its lse and the two backward kernels, at seq
    2048, 15/5 heads of 64 and the cell's batch of 16 on one chip, each
    under the scoped VMEM limit of its modeled working set."""
    from repro.core.memory_model import vmem_limit_bytes

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)

    def loss(q, k, v):
        return ops.attention(q, k, v, causal=True).astype(F32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sds((16, 2048, 15, 64)), sds((16, 2048, 5, 64)),
        sds((16, 2048, 5, 64))).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 3
    assert sum("flash_attention_bwd" in ln for ln in calls) == 2
    (pl,) = ops.attn_plans()
    assert pl.bwd_kernel == "flash_attention_bwd", pl.explain()
    scoped = {int(m) for ln in calls for m in re.findall(
        r'"scoped_memory_configs":\[\{[^]]*"size":"(\d+)"', ln)}
    assert scoped == {vmem_limit_bytes(v)
                      for v in (pl.vmem_bytes, *pl.bwd.vmem)}


#: one chip's flash forward in each train cell: (shapes, window, the
#: blocks it computes of all)
FLASH_FORWARDS = {
    "smollm_16x2048_h15/5_d64": ((16, 2048, 2048, 15, 5, 64), 0, (10, 16)),
    "danube_4x4096_h16/4_d120": ((4, 4096, 4096, 16, 4, 120), 4096,
                                 (36, 64)),
}


@pytest.mark.parametrize("return_lse", [False, True])
@pytest.mark.parametrize("case", sorted(FLASH_FORWARDS))
def test_flash_forward_compiles_for_v5e(case, return_lse, one_chip, pallas):
    """The forward that skips the blocks its mask empties, at each train
    cell's per-chip shape, with and without its lse, under the scoped
    VMEM limit of its plan's modeled working set."""
    from repro.core.memory_model import vmem_limit_bytes
    from repro.kernels.flash_attention import flash_attention
    (b, sq, skv, hq, hkv, d), window, blocks = FLASH_FORWARDS[case]
    pl = ops.attn_plan(ops.AttnSpec(group=hq // hkv, window=window),
                       (b, sq, skv, hq, hkv, d))
    assert (pl.kernel, pl.bq, pl.bkv, pl.blocks) == (
        "flash_attention", 512, 512, blocks)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)

    hlo = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, window=window, bq=pl.bq, bkv=pl.bkv,
        vmem_bytes=pl.vmem_bytes, return_lse=return_lse)).lower(
        sds((b, sq, hq, d)), sds((b, skv, hkv, d)),
        sds((b, skv, hkv, d))).compile().as_text()
    (call,) = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    scoped = {int(m) for m in re.findall(
        r'"scoped_memory_configs":\[\{[^]]*"size":"(\d+)"', call)}
    assert scoped == {vmem_limit_bytes(pl.vmem_bytes)}


#: (m, k, n) of the GEMMs one chip of the danube mesh runs: q and o
#: projections, k/v, the gated MLP, its down projection, and one chunk
#: of the lm head (seq 4096 / 8 chunks x 4 rows)
DANUBE_GEMMS = {
    "wq_16384x3840x1920": (16384, 3840, 1920, BF16),
    "wkv_16384x3840x480": (16384, 3840, 480, BF16),
    "w_down_16384x10240x1920": (16384, 10240, 1920, BF16),
    "lm_head_2048x3840x16000_f32": (2048, 3840, 16000, F32),
}


@pytest.mark.parametrize("case", sorted(DANUBE_GEMMS))
def test_danube_shard_gemm_compiles_for_v5e(case, one_chip, pallas):
    """Forward and backward of one GEMM of a danube model shard."""
    m, k, n, out = DANUBE_GEMMS[case]

    def loss(a, b):
        return ops.gemm(a, b, out_dtype=out).astype(F32).sum()

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        sds((m, k)), sds((k, n))).compile().as_text()
    assert "tpu_custom_call" in hlo
    for p in ops.plans():
        assert p.fallback_reason is None, p.explain()


def test_danube_gated_mlp_compiles_for_v5e(one_chip, pallas):
    """The SwiGLU of a danube model shard, 3840 -> 5120, forward and
    backward."""
    def loss(x, g, u):
        return ops.gemm(x, g, b2=u, activation="silu").astype(F32).sum()

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sds((4, 4096, 3840)), sds((3840, 5120)),
        sds((3840, 5120))).compile().as_text()
    assert "tpu_custom_call" in hlo
    for p in ops.plans():
        assert p.fallback_reason is None, p.explain()


def test_danube_flash_compiles_for_v5e(one_chip, pallas):
    """The flash forward with its lse and the two backward kernels at
    head_dim 120 (padded to 128 lanes), seq 4096, 16/4 heads (a GQA
    group of 4), 4 rows: one chip's share of the danube cell."""
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)

    def loss(q, k, v):
        return ops.attention(q, k, v, causal=True,
                             window=4096).astype(F32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sds((4, 4096, 16, 120)), sds((4, 4096, 4, 120)),
        sds((4, 4096, 4, 120))).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 3
    assert sum("flash_attention_bwd" in ln for ln in calls) == 2
    (pl,) = ops.attn_plans()
    assert (pl.hq, pl.hkv, pl.d, pl.spec.group) == (16, 4, 120, 4)
    assert pl.bwd_kernel == "flash_attention_bwd", pl.explain()
    assert pl.fallback_reason is None, pl.explain()


def test_danube_gemm_under_the_mesh_compiles_for_v5e(topo, one_chip,
                                                     pallas):
    """The danube down projection at global shapes under a described
    (data 2, model 2) mesh, forward and backward: each chip plans its own
    block of the product, so the tile and its scoped VMEM limit fit the
    block it runs."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist import sharding as shd
    mesh = shd.make_mesh((2, 2), ("data", "model"), devices=topo.devices)

    def loss(a, b):
        return ops.gemm(a, b).astype(F32).sum()

    a = jax.ShapeDtypeStruct((32768, 10240), BF16,
                             sharding=NamedSharding(mesh, P("data", None)))
    b = jax.ShapeDtypeStruct((10240, 3840), BF16,
                             sharding=NamedSharding(mesh, P(None, "model")))
    with shd.use_mesh(mesh):
        hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(a, b) \
            .compile().as_text()
    assert "tpu_custom_call" in hlo
    assert (16384, 10240, 1920) in {(p.m, p.k, p.n) for p in ops.plans()}
