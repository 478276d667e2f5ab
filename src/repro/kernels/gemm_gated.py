"""Dual-B gated GEMM — one Pallas call for ``act(A W_gate) * (A W_up)``.

The SwiGLU/GeGLU block is two GEMMs that share the same activation
operand A and whose outputs meet in one elementwise gate.  Run unfused,
A streams from HBM twice and both (m, d_ff) intermediates round-trip
through HBM before the multiply.  This kernel is the paper's
keep-it-in-the-array discipline (SS IV-A) applied across *two* reductions:
the grid is (m, n, k) with k innermost, ONE A block is fetched per grid
step and multiplied against both B streams, two VMEM scratch accumulators
hold the partial gate/up sums, and the last-k flush computes
``act(acc_gate) * acc_up`` (per-output-channel dequant scales first, for
int8 B operands) — so A is read once and the gate/up intermediates never
exist outside VMEM.

Output-stationary ('aie' dataflow) only: the DSE bills the second B
stream and the second accumulator via ``GemmProblem(n_b_operands=2)``.
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
from repro.kernels.epilogue import ACTIVATIONS


def _gated_kernel(activation, has_scale, *refs):
    it = iter(refs)
    a_ref, bg_ref, bu_ref = next(it), next(it), next(it)
    sg_ref = next(it) if has_scale else None
    su_ref = next(it) if has_scale else None
    o_ref, accg_ref, accu_ref = next(it), next(it), next(it)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    a = a_ref[...]                   # fetched once, used against both Bs
    bg = bg_ref[...]
    bu = bu_ref[...]
    if bg.dtype == jnp.int8 and a.dtype != jnp.int8:
        bg = bg.astype(a.dtype)      # W8A16: in-register int8 -> a-dtype
        bu = bu.astype(a.dtype)
    accg_ref[...] += jnp.dot(a, bg, preferred_element_type=accg_ref.dtype)
    accu_ref[...] += jnp.dot(a, bu, preferred_element_type=accu_ref.dtype)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        xg = accg_ref[...].astype(jnp.float32)
        xu = accu_ref[...].astype(jnp.float32)
        if sg_ref is not None:
            xg = xg * sg_ref[...]
            xu = xu * su_ref[...]
        o_ref[...] = (ACTIVATIONS[activation](xg) * xu).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype",
                                             "activation", "interpret",
                                             "vmem_bytes"))
def gemm_gated(a: jax.Array, b_gate: jax.Array, b_up: jax.Array, *,
               tile: TileConfig, activation: str = "silu",
               out_dtype=None,
               bg_scale: Optional[jax.Array] = None,
               bu_scale: Optional[jax.Array] = None,
               interpret: bool = False, vmem_bytes: int = 0) -> jax.Array:
    """C[m,n] = act(A @ B_gate) * (A @ B_up), single resident A stream.

    Dims must be multiples of the tile (ops.py pads).  ``bg_scale`` /
    ``bu_scale`` (1, n) fp32 turn on the fused weight-dequant path (both
    B operands must then be int8); scales apply to their accumulators on
    the flush, before the gate.  ``vmem_bytes`` (the plan's modeled
    working set) sets the scoped VMEM limit.
    """
    m, k = a.shape
    k2, n = b_gate.shape
    assert k == k2 and b_up.shape == (k, n), \
        (a.shape, b_gate.shape, b_up.shape)
    assert tile.strategy == "aie", \
        f"gemm_gated is output-stationary only (got {tile.strategy!r})"
    assert activation in ACTIVATIONS, activation
    assert (bg_scale is None) == (bu_scale is None), \
        "quantize both B operands or neither"
    bm, bk, bn = tile.bm, tile.bk, tile.bn
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, \
        (a.shape, b_gate.shape, tile)
    acc = acc_dtype(a.dtype)
    out_dtype = out_dtype or (a.dtype if a.dtype != jnp.int8
                              else jnp.float32)
    grid = (m // bm, n // bn, k // bk)

    operands = [a, b_gate, b_up]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
        pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
    ]
    if bg_scale is not None:
        assert b_gate.dtype == jnp.int8 and b_up.dtype == jnp.int8
        assert bg_scale.shape == (1, n) and bu_scale.shape == (1, n)
        operands += [bg_scale.astype(jnp.float32),
                     bu_scale.astype(jnp.float32)]
        in_specs += [pl.BlockSpec((1, bn), lambda i, j, l: (0, j)),
                     pl.BlockSpec((1, bn), lambda i, j, l: (0, j))]

    kernel = functools.partial(_gated_kernel, activation,
                               bg_scale is not None)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc),
                        pltpu.VMEM((bm, bn), acc)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"), vmem_bytes),
        interpret=interpret,
    )(*operands)
