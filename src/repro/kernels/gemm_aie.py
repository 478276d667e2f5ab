"""Output-stationary Pallas GEMM — the Versal AIE dataflow on TPU.

Paper mapping (SS IV-A): on Versal, each AIE core computes an MxKxN block
and adder trees reduce partial products across the Y (reduction) axis
*before* anything leaves the array, so each C element is written once.
The TPU analogue is an output-stationary kernel: grid (m, n, k) with k
innermost, partial sums held in a VMEM scratch accumulator (fp32 for
float operands, int32 for int8 — the paper's 8-bit operand / 32-bit
accumulation scheme), and the C block written on the last k step.

That last-k flush is also where the *epilogue* fuses: because the
accumulator is already resident on-chip, a per-output-channel bias, an
activation (silu/gelu/relu), a residual add and an optional int8 output
quantization run on the VMEM block before the single C write — the
unfused ``gemm -> XLA elementwise`` composition would instead round-trip
the full (m, n) intermediate through HBM.  The fused weight-dequant
``b_scale`` path composes: scale first, then the epilogue.

Block shapes come from the reuse-maximizing DSE (:mod:`repro.core.dse`),
the way the paper's U,V,W come from its IP solver.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import TileConfig
from repro.kernels import _compiler_params, acc_dtype
from repro.kernels.epilogue import apply_epilogue


def _gemm_aie_kernel(activation, has_scale, has_bias, has_res, has_oscale,
                     *refs):
    """One kernel body for every aie variant.  ``refs`` order follows the
    in_specs: a, b, [scale], [bias], [residual], [out_scale], then the
    output ref and the accumulator scratch."""
    it = iter(refs)
    a_ref, b_ref = next(it), next(it)
    s_ref = next(it) if has_scale else None
    bias_ref = next(it) if has_bias else None
    res_ref = next(it) if has_res else None
    osc_ref = next(it) if has_oscale else None
    o_ref, acc_ref = next(it), next(it)
    fused = (has_scale or has_bias or has_res or has_oscale
             or activation is not None)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    # W8A16 only: widen an int8 B in-register to A's dtype.  Any other
    # mismatch must not silently narrow (e.g. float B with int8 A).
    if b.dtype == jnp.int8 and a.dtype != jnp.int8:
        b = b.astype(a.dtype)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=acc_ref.dtype)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        x = acc_ref[...]
        if fused:
            x = x.astype(jnp.float32)
            if s_ref is not None:
                x = x * s_ref[...]
            x = apply_epilogue(
                x, activation=activation,
                bias=bias_ref[...] if bias_ref is not None else None,
                residual=res_ref[...] if res_ref is not None else None,
                out_scale=osc_ref[...] if osc_ref is not None else None)
        o_ref[...] = x.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype",
                                             "activation", "interpret",
                                             "vmem_bytes"))
def gemm_aie(a: jax.Array, b: jax.Array, *, tile: TileConfig,
             out_dtype=None, b_scale: Optional[jax.Array] = None,
             bias: Optional[jax.Array] = None,
             residual: Optional[jax.Array] = None,
             out_scale: Optional[jax.Array] = None,
             activation: Optional[str] = None,
             interpret: bool = False, vmem_bytes: int = 0) -> jax.Array:
    """C[m,n] = epilogue(sum_k A[m,k] B[k,n]), output-stationary.

    Dims must be multiples of the tile (ops.py pads — the paper's
    zero-padding alignment, SS V-C2).

    ``b_scale`` (1, n) fp32 turns on the fused weight-dequant path: ``b``
    must then be int8, streamed into VMEM at one byte/element, and
    ``C[m,n] = b_scale[n] * sum_k A[m,k] Bq[k,n]`` with the scale applied
    on the last-k flush (int32 accumulation when A is int8 too).

    Epilogue operands, all applied on the flush (after ``b_scale``), in
    order: ``bias`` (1, n) add, ``activation`` in fp32, ``residual``
    (m, n) add, ``out_scale`` (1, 1) fp32 output quantization (divide,
    round, clip to [-127, 127]; pair with ``out_dtype=jnp.int8``).

    ``vmem_bytes`` is the plan's modeled VMEM working set; it sets the
    kernel's scoped VMEM limit.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bk, bn = tile.bm, tile.bk, tile.bn
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, \
        (a.shape, b.shape, tile)
    acc = acc_dtype(a.dtype)
    fused = (b_scale is not None or bias is not None or residual is not None
             or out_scale is not None or activation is not None)
    out_dtype = out_dtype or (jnp.float32 if fused else acc)
    grid = (m // bm, n // bn, k // bk)

    operands = [a, b]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
        pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
    ]
    if b_scale is not None:
        assert b.dtype == jnp.int8, b.dtype
        assert b_scale.shape == (1, n), (b_scale.shape, n)
        operands.append(b_scale.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, l: (0, j)))
    if bias is not None:
        assert bias.shape == (1, n), (bias.shape, n)
        operands.append(bias.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, l: (0, j)))
    if residual is not None:
        assert residual.shape == (m, n), (residual.shape, (m, n))
        operands.append(residual)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)))
    if out_scale is not None:
        assert out_scale.shape == (1, 1), out_scale.shape
        operands.append(out_scale.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j, l: (0, 0)))

    kernel = functools.partial(
        _gemm_aie_kernel, activation, b_scale is not None,
        bias is not None, residual is not None, out_scale is not None)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"), vmem_bytes),
        interpret=interpret,
    )(*operands)
