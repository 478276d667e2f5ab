"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Each case plans a GEMM or attention call through ``repro.ops`` under
Pallas dispatch and compiles it with the TPU compiler for a described
(not attached) ``v5e:2x2`` topology.  That compiler refuses a kernel
whose VMEM use exceeds its scoped limit, which the CPU suite and the
Pallas interpreter cannot see.  Widths are those of the shipped
configs: smollm-360m (d_model 960, d_ff 2560, vocab 49152, 15/5 heads
of 64), qwen3-moe expert banks (d_model 4096, d_ff 1536) and a
llama-class 4096x14336 MLP.
"""

from __future__ import annotations

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
