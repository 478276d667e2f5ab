"""``repro.ops`` — the public operator API.

The GEMM family is the declarative planned pipeline from
:mod:`repro.kernels.api`:

    spec = ops.GemmSpec.for_operands(x, w, residual=r)   # or GemmSpec(...)
    pl   = ops.plan(spec, ops.gemm_shapes(x, w))         # cached, once
    y    = ops.execute(pl, x, w, residual=r)
    print(pl.explain())                                  # kernel/tile/bytes

or the one-shot form every model layer uses (identical dispatch — it
builds the spec and goes through the same plan cache):

    y = ops.gemm(x, w, residual=r)

The grouped ragged family member (the MoE expert sweep) is
``ops.gemm_grouped(xs, bank, group_sizes)`` — same spec/plan/execute
pipeline with the extended ``gemm_grouped_shapes`` plan key.

Attention is the same framework applied to the second hot-spot
(:mod:`repro.kernels.attn_api`):

    spec = ops.AttnSpec(mode="decode", group=6)
    pl   = ops.attn_plan(spec, (b, skv, hq, hkv, d))
    o    = ops.attn_execute(pl, q, k_cache, v_cache, pos=pos)

with the one-shots ``ops.attention`` / ``ops.decode_attention`` /
``ops.decode_attention_paged`` building the spec from live operands.
The pre-redesign entrypoints (``gemm_fused``/``gemm_gated``/
``gemm_int8``, the old ``gemm``, and the same-named attention trio)
live on as deprecated shims in :mod:`repro.kernels.ops`.
"""

from repro.kernels.api import (  # noqa: F401
    GemmPlan,
    GemmSpec,
    PlanCacheInfo,
    execute,
    gemm,
    gemm_grouped,
    gemm_grouped_shapes,
    gemm_shapes,
    plan,
    plan_cache_clear,
    plan_cache_info,
    plans,
    use_pallas,
)
from repro.kernels.attn_api import (  # noqa: F401
    BLOCKED_ATTN_THRESHOLD,
    AttnPlan,
    AttnProblem,
    AttnSpec,
    attention,
    attn_execute,
    attn_plan,
    attn_plan_cache_clear,
    attn_plan_cache_info,
    attn_plans,
    attn_solve_topk,
    decode_attention,
    decode_attention_paged,
)
from repro.kernels.epilogue import ACTIVATIONS, Epilogue  # noqa: F401
from repro.kernels.ops import dequantize, quantize_int8  # noqa: F401
from repro.core.tiling import TileConfig  # noqa: F401
