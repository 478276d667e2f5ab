"""A-stationary Pallas GEMM — the Stratix Tensor-Block dataflow on TPU.

Paper mapping (SS IV-B): on Stratix, a 3x10 A block is pinned in each
TB's ping-pong registers while a stream of B blocks is broadcast past it;
partial dot products cascade outward and are accumulated *into the C
buffer by PL soft-logic adders* (read-modify-write, II=1).  The TPU
analogue:

* within one ``pallas_call`` the grid is (m, n) with n innermost — the A
  block is fetched once per m row and stays VMEM-resident while the B
  stream (all n blocks) passes it: weight-stationary, like the TB
  registers;
* the reduction (K) dimension is chunked *outside* the kernel; each
  k-chunk re-reads and updates C in place via ``input_output_aliasing``
  — exactly the paper's PL-accumulator pattern (and its V*Y*K-dimension
  tile reduction).

This has a genuinely different traffic signature from the output-
stationary 'aie' kernel (C is rmw-ed gk times but A is read once), which
is why the DSE searches both.

The *final* k-chunk is special: it is the one visit that knows the full
accumulator, so the fused epilogue (b_scale dequant, bias, activation,
residual, optional int8 output quantization) runs inside that last
kernel body before the single out-dtype C write — the tb analogue of the
aie kernel's last-k flush.

Feasibility: the requested ``bk`` k-chunk must keep the resident
(bm, bk) A block plus the streaming B/C blocks inside VMEM.  The DSE
only emits tiles it has already checked, but explicit/legacy tiles can
bust for large K — :func:`gemm_tb` re-checks against
:func:`repro.core.memory_model.fits_vmem` and transparently refines the
k-chunking (smaller ``bk``; the result is identical, only the chunk loop
gets longer) rather than over-subscribing VMEM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import memory_model
from repro.core.tiling import GemmProblem, TileConfig
from repro.kernels import _compiler_params, acc_dtype
from repro.kernels.epilogue import apply_epilogue


def _gemm_tb_kernel(a_ref, b_ref, c_ref, o_ref):
    # One (m,n) visit: accumulate this k-chunk's contribution onto C.
    # A quantized B stream arrives as int8 (one byte/element in VMEM) and
    # is dequantized in-register to A's dtype; per-output-channel scales
    # commute with the k-sum, so they are applied once after the cascade
    # (gemm_tb), like the paper's outward-cascaded TB accumulation.
    b = b_ref[...]
    if b.dtype == jnp.int8 and a_ref.dtype != jnp.int8:    # W8A16 only
        b = b.astype(a_ref.dtype)
    o_ref[...] = c_ref[...] + jnp.dot(a_ref[...], b,
                                      preferred_element_type=o_ref.dtype)


def _gemm_tb_final_kernel(activation, has_scale, has_bias, has_res,
                          has_oscale, *refs):
    """Last k-chunk: finish the accumulation AND apply the fused epilogue
    before the single out-dtype C write (the tb flush)."""
    it = iter(refs)
    a_ref, b_ref, c_ref = next(it), next(it), next(it)
    s_ref = next(it) if has_scale else None
    bias_ref = next(it) if has_bias else None
    res_ref = next(it) if has_res else None
    osc_ref = next(it) if has_oscale else None
    o_ref = next(it)
    b = b_ref[...]
    if b.dtype == jnp.int8 and a_ref.dtype != jnp.int8:    # W8A16 only
        b = b.astype(a_ref.dtype)
    acc = c_ref[...] + jnp.dot(a_ref[...], b,
                               preferred_element_type=c_ref.dtype)
    x = acc.astype(jnp.float32)
    if s_ref is not None:
        x = x * s_ref[...]
    x = apply_epilogue(
        x, activation=activation,
        bias=bias_ref[...] if bias_ref is not None else None,
        residual=res_ref[...] if res_ref is not None else None,
        out_scale=osc_ref[...] if osc_ref is not None else None)
    o_ref[...] = x.astype(o_ref.dtype)


def _tb_call(a, b, c, *, bm: int, bn: int, interpret: bool,
             vmem_bytes: int):
    m, k = a.shape
    _, n = b.shape
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        _gemm_tb_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),   # A row resident
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),   # B stream
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),  # C rmw in
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), c.dtype),
        input_output_aliases={2: 0},                      # C updated in place
        compiler_params=_compiler_params(("parallel", "arbitrary"),
                                         vmem_bytes),
        interpret=interpret,
    )(a, b, c)


def _tb_call_final(a, b, c, *, bm: int, bn: int, out_dtype, b_scale,
                   bias, residual, out_scale, activation, interpret: bool,
                   vmem_bytes: int):
    m, k = a.shape
    _, n = b.shape
    grid = (m // bm, n // bn)
    operands = [a, b, c]
    in_specs = [
        pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        pl.BlockSpec((k, bn), lambda i, j: (0, j)),
        pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
    ]
    if b_scale is not None:
        operands.append(b_scale.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
    if bias is not None:
        operands.append(bias.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
    if residual is not None:
        operands.append(residual)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j: (i, j)))
    if out_scale is not None:
        operands.append(out_scale.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j: (0, 0)))
    kernel = functools.partial(
        _gemm_tb_final_kernel, activation, b_scale is not None,
        bias is not None, residual is not None, out_scale is not None)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=_compiler_params(("parallel", "arbitrary"),
                                         vmem_bytes),
        interpret=interpret,
    )(*operands)


def feasible_bk(m: int, k: int, n: int, tile: TileConfig, a_dtype,
                b_dtype, out_dtype, acc_dtype, epilogue: str = "") -> int:
    """Largest k-chunk <= tile.bk that divides K, is lane-aligned, and
    keeps the tb working set (resident (bm, bk) A + streamed B/C blocks
    + any fused bias/residual blocks, via ``epilogue``) inside the VMEM
    budget.  Returns 0 when even bk=128 busts (then the (bm, bn) blocks
    themselves are infeasible — the caller should use a different tile
    or the 'aie' strategy)."""
    def fits(bk: int) -> bool:
        p = GemmProblem(m, k, n, str(jnp.dtype(a_dtype)),
                        str(jnp.dtype(out_dtype)),
                        str(jnp.dtype(acc_dtype)), str(jnp.dtype(b_dtype)),
                        epilogue)
        return memory_model.fits_vmem(
            TileConfig(tile.bm, bk, tile.bn, "tb"), p)

    for bk in range(min(tile.bk, k), 0, -128):
        if k % bk == 0 and fits(bk):
            return bk
    return 0


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype",
                                             "activation", "interpret",
                                             "vmem_bytes"))
def gemm_tb(a: jax.Array, b: jax.Array, *, tile: TileConfig,
            out_dtype=None, b_scale: Optional[jax.Array] = None,
            bias: Optional[jax.Array] = None,
            residual: Optional[jax.Array] = None,
            out_scale: Optional[jax.Array] = None,
            activation: Optional[str] = None,
            interpret: bool = False, vmem_bytes: int = 0) -> jax.Array:
    """C[m,n] = epilogue(sum_k A[m,k] B[k,n]), A-stationary with k-chunked
    PL-style accumulation.  Dims must be tile multiples (ops.py pads).

    ``b_scale`` (1, n) fp32 turns on the fused weight-dequant path:
    ``b`` must then be int8 (streamed at one byte/element, dequantized
    in-register inside the kernel body for W8A16; int32 accumulation
    when A is int8 too) and the per-output-channel scale is applied once
    after the last k-chunk cascade.

    Epilogue operands (``bias`` (1, n), ``activation``, ``residual``
    (m, n), ``out_scale`` (1, 1) int8 output quantization) fuse into the
    final k-chunk's kernel body — the accumulator is completed and
    post-processed in VMEM, written once at ``out_dtype``.

    ``vmem_bytes`` (the plan's modeled working set) sets the scoped VMEM
    limit of every k-chunk call.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bk, bn = tile.bm, tile.bk, tile.bn
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, \
        (a.shape, b.shape, tile)
    if b_scale is not None:
        assert b.dtype == jnp.int8, b.dtype
        assert b_scale.shape == (1, n), (b_scale.shape, n)
    if bias is not None:
        assert bias.shape == (1, n), (bias.shape, n)
    if residual is not None:
        assert residual.shape == (m, n), (residual.shape, (m, n))
    if out_scale is not None:
        assert out_scale.shape == (1, 1), out_scale.shape
    acc = acc_dtype(a.dtype)
    fused = (b_scale is not None or bias is not None or residual is not None
             or out_scale is not None or activation is not None)
    out_dtype = out_dtype or (jnp.float32 if fused else acc)

    # Feasibility (satellite): the (bm, bk) A block is VMEM-resident for
    # a whole n sweep — refine the k-chunking when the requested bk would
    # over-subscribe VMEM (identical result, longer chunk loop).  The
    # fused final-chunk operands (bias/residual blocks) count too.
    from repro.kernels.epilogue import Epilogue
    ep_key = Epilogue.from_args(bias, activation, residual, out_scale).key
    bk_fit = feasible_bk(m, k, n, tile, a.dtype, b.dtype, out_dtype, acc,
                         epilogue=ep_key)
    if bk_fit == 0:
        raise ValueError(
            f"tb tile {tile} infeasible for ({m},{k},{n}) even at bk=128:"
            " (bm, bn) blocks bust VMEM — shrink the tile or use 'aie'")
    bk = min(bk, bk_fit)

    gk = k // bk
    c = jnp.zeros((m, n), acc)
    for kk in range(gk - 1):        # k-chunk loop = the paper's V loop
        a_k = jax.lax.slice(a, (0, kk * bk), (m, (kk + 1) * bk))
        b_k = jax.lax.slice(b, (kk * bk, 0), ((kk + 1) * bk, n))
        c = _tb_call(a_k, b_k, c, bm=bm, bn=bn, interpret=interpret,
                     vmem_bytes=vmem_bytes)
    a_k = jax.lax.slice(a, (0, (gk - 1) * bk), (m, k))
    b_k = jax.lax.slice(b, ((gk - 1) * bk, 0), (k, n))
    if not fused:
        c = _tb_call(a_k, b_k, c, bm=bm, bn=bn, interpret=interpret,
                     vmem_bytes=vmem_bytes)
        return c.astype(out_dtype)
    return _tb_call_final(a_k, b_k, c, bm=bm, bn=bn, out_dtype=out_dtype,
                          b_scale=b_scale, bias=bias, residual=residual,
                          out_scale=out_scale, activation=activation,
                          interpret=interpret, vmem_bytes=vmem_bytes)
