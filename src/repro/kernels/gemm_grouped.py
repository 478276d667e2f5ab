"""Grouped ragged GEMM — one output-stationary sweep over concatenated
expert token groups (the MoE expert compute, megablocks-style).

Problem: ``A`` is ``(m, k)`` tokens *sorted by expert* so each expert's
rows are contiguous (``group_sizes[e]`` rows for expert ``e``, groups
packed from row 0, zero tail); ``B`` is the ``(E, k, n)`` expert weight
bank.  A dense formulation pads every group to capacity and multiplies
the padding at full price; this kernel visits only the m-tiles a group
actually covers.

Paper mapping: this is the GotoBLAS2-on-Versal move (PAPERS.md) — one
hierarchically tiled micro-kernel sweeping irregular panels, instead of
per-panel (per-expert) dispatch.  The steering trick is the same scalar
prefetch PR 8 used for KV page tables: three CSR-style tables ride
``PrefetchScalarGridSpec`` scalar memory and the ``index_map``s read
them to pick each grid step's A row-tile and B expert slice:

    group_offsets : (E+1,)  row offset of each group (cumsum, leading 0)
    group_ids     : (I,)    expert id of grid instance i
    m_tile_ids    : (I,)    A/C m-tile of grid instance i

with ``I = tiles_m + E - 1`` static (a tile straddling a group boundary
is visited once per group it hosts).  The actual instance count is
dynamic — the grid's middle dimension is a traced scalar, so tile visits
scale with the *real* routed token counts, not the static worst case.

A straddling tile masks the foreign rows on the flush: consecutive
instances of the same output tile blend via ``where(mask, x, out)``, so
each C element is written by exactly the instance that owns its row and
the accumulation per tile is exact.  Rows beyond ``sum(group_sizes)``
(dropped-token tail) are zeroed outside the kernel.

The W8A16 ``{q, scale}`` dequant path and the bias/activation
``Epilogue`` fuse on the last-k flush exactly like ``gemm_aie`` —
per-expert ``(E, 1, n)`` scale/bias vectors are steered by the same
``group_ids`` table.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import TileConfig
from repro.kernels import _compiler_params, acc_dtype
from repro.kernels.epilogue import apply_epilogue


def group_metadata(group_sizes: jax.Array, m: int, bm: int
                   ) -> Tuple[Tuple[jax.Array, jax.Array, jax.Array],
                              jax.Array]:
    """CSR-style steering tables for the grouped sweep.

    Returns ``((group_offsets, group_ids, m_tile_ids), num_instances)``.
    The tables have static length ``tiles_m + E - 1`` (the worst case:
    every group boundary lands mid-tile); ``num_instances`` is the traced
    number of live entries — empty groups contribute none, and a group
    contributes one instance per m-tile it overlaps.  Entries past
    ``num_instances`` are repeat-padding and must never be executed.
    """
    e = group_sizes.shape[0]
    tiles_m = m // bm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), ends]).astype(jnp.int32)
    starts = offsets[:-1]
    # m-tiles each group overlaps: [floor(start/bm), ceil(end/bm))
    tiles_per_group = jnp.where(
        sizes == 0, 0, (ends + bm - 1) // bm - starts // bm)
    n_inst = tiles_m + e - 1
    group_ids = jnp.repeat(jnp.arange(e, dtype=jnp.int32), tiles_per_group,
                           total_repeat_length=n_inst)
    # visits per m-tile: 1 + number of (non-empty) groups starting mid-tile
    mid_start = (starts % bm != 0) & (sizes > 0)
    start_tile = jnp.where(mid_start, starts // bm, tiles_m)
    visits = jnp.ones((tiles_m,), jnp.int32).at[start_tile].add(
        1, mode="drop")
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), visits,
                            total_repeat_length=n_inst)
    num_instances = tiles_per_group.sum()
    return (offsets, group_ids, m_tile_ids), num_instances


def _grouped_kernel(activation, has_scale, has_bias, bm, bn, *refs):
    """Body for every grouped variant.  ``refs``: the three prefetched
    tables, then a, b, [scale], [bias], the output ref and the
    accumulator scratch."""
    it = iter(refs)
    offs_ref, gids_ref, tids_ref = next(it), next(it), next(it)
    a_ref, b_ref = next(it), next(it)
    s_ref = next(it) if has_scale else None
    bias_ref = next(it) if has_bias else None
    o_ref, acc_ref = next(it), next(it)
    gi = pl.program_id(1)
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    # W8A16: widen an int8 B bank in-register to A's dtype (gemm_aie rule)
    if b.dtype == jnp.int8 and a.dtype != jnp.int8:
        b = b.astype(a.dtype)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=acc_ref.dtype)

    @pl.when(k_i == pl.num_programs(2) - 1)
    def _flush():
        g = gids_ref[gi]
        x = acc_ref[...]
        if has_scale or has_bias or activation is not None:
            x = x.astype(jnp.float32)
            if s_ref is not None:
                x = x * s_ref[...]
            x = apply_epilogue(
                x, activation=activation,
                bias=bias_ref[...] if bias_ref is not None else None)
        x = x.astype(o_ref.dtype)
        # blend: only the rows this instance's group owns are written,
        # so a straddling tile's other visitor(s) keep their rows intact
        rows = tids_ref[gi] * bm \
            + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        mask = (rows >= offs_ref[g]) & (rows < offs_ref[g + 1])
        o_ref[...] = jnp.where(mask, x, o_ref[...])


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype",
                                             "activation", "interpret",
                                             "vmem_bytes"))
def gemm_grouped(a: jax.Array, b: jax.Array, group_sizes: jax.Array, *,
                 tile: TileConfig, out_dtype=None,
                 b_scale: Optional[jax.Array] = None,
                 bias: Optional[jax.Array] = None,
                 activation: Optional[str] = None,
                 interpret: bool = False, vmem_bytes: int = 0) -> jax.Array:
    """``C[r, n] = epilogue(sum_k A[r, k] B[g(r), k, n])`` where ``g(r)``
    is the group owning row ``r`` under ``group_sizes``.

    ``a``: (m, k) group-sorted rows; ``b``: (E, k, n) bank.  Dims must be
    tile multiples (api.py pads).  Rows at and beyond
    ``sum(group_sizes)`` come back zero.  ``b_scale`` (E, 1, n) fp32
    turns on the fused W8A16 dequant (``b`` int8); ``bias`` (E, 1, n) is
    a per-expert bias, applied with ``activation`` on the flush.
    ``vmem_bytes`` (the plan's modeled working set) sets the scoped VMEM
    limit.
    """
    m, k = a.shape
    e, k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert group_sizes.shape == (e,), (group_sizes.shape, e)
    bm, bk, bn = tile.bm, tile.bk, tile.bn
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, \
        (a.shape, b.shape, tile)
    acc = acc_dtype(a.dtype)
    fused = b_scale is not None or bias is not None or activation is not None
    out_dtype = out_dtype or (jnp.float32 if fused else acc)
    (offsets, group_ids, m_tile_ids), num_instances = \
        group_metadata(group_sizes, m, bm)
    grid = (n // bn, num_instances, k // bk)

    operands = [a, b]
    in_specs = [
        pl.BlockSpec((bm, bk),
                     lambda ni, gi, ki, offs, gids, tids: (tids[gi], ki)),
        pl.BlockSpec((None, bk, bn),
                     lambda ni, gi, ki, offs, gids, tids:
                     (gids[gi], ki, ni)),
    ]
    vec_map = (lambda ni, gi, ki, offs, gids, tids: (gids[gi], 0, ni))
    if b_scale is not None:
        assert b.dtype == jnp.int8, b.dtype
        assert b_scale.shape == (e, 1, n), (b_scale.shape, (e, 1, n))
        operands.append(b_scale.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((None, 1, bn), vec_map))
    if bias is not None:
        assert bias.shape == (e, 1, n), (bias.shape, (e, 1, n))
        operands.append(bias.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((None, 1, bn), vec_map))

    kernel = functools.partial(_grouped_kernel, activation,
                               b_scale is not None, bias is not None,
                               bm, bn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (bm, bn),
            lambda ni, gi, ki, offs, gids, tids: (tids[gi], ni)),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=_compiler_params(
            ("parallel", "arbitrary", "arbitrary"), vmem_bytes),
        interpret=interpret,
    )(offsets, group_ids, m_tile_ids, *operands)
    # unvisited tail tiles (and straddle rows past the last group) hold
    # whatever the out buffer held — zero everything past the live rows
    live = jnp.arange(m, dtype=jnp.int32)[:, None] < offsets[-1]
    return jnp.where(live, out, jnp.zeros((), out.dtype))


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype",
                                             "activation"))
def gemm_grouped_blocked_ref(a: jax.Array, b: jax.Array,
                             group_sizes: jax.Array, *, tile: TileConfig,
                             out_dtype=None,
                             b_scale: Optional[jax.Array] = None,
                             bias: Optional[jax.Array] = None,
                             activation: Optional[str] = None
                             ) -> jax.Array:
    """XLA gather oracle at the kernel's exact tile/accumulation order.

    Replays the grouped sweep instance by instance with dynamic-slice
    gathers — same (bm, bk)x(bk, bn) dots in the same k order, same
    flush, same blend — so interpret-mode kernel output must match
    *bitwise*.  O(instances) sequential; test-sized problems only (the
    fast dispatch oracle is ``ref.gemm_grouped_ref``).
    """
    m, k = a.shape
    e, _, n = b.shape
    bm, bk, bn = tile.bm, tile.bk, tile.bn
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, \
        (a.shape, b.shape, tile)
    acc_d = acc_dtype(a.dtype)
    fused = b_scale is not None or bias is not None or activation is not None
    out_dtype = out_dtype or (jnp.float32 if fused else acc_d)
    (offsets, group_ids, m_tile_ids), num_instances = \
        group_metadata(group_sizes, m, bm)
    gk, gn = k // bk, n // bn
    rows_iota = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)

    def instance(i, out):
        g, t = group_ids[i], m_tile_ids[i]
        a_row = jax.lax.dynamic_slice(a, (t * bm, 0), (bm, k))
        w = jax.lax.dynamic_index_in_dim(b, g, 0, keepdims=False)

        def column(ni, out):
            def kstep(ki, acc):
                ab = jax.lax.dynamic_slice(a_row, (0, ki * bk), (bm, bk))
                wb = jax.lax.dynamic_slice(w, (ki * bk, ni * bn), (bk, bn))
                if wb.dtype == jnp.int8 and ab.dtype != jnp.int8:
                    wb = wb.astype(ab.dtype)
                return acc + jnp.dot(ab, wb,
                                     preferred_element_type=acc.dtype)
            x = jax.lax.fori_loop(0, gk, kstep,
                                  jnp.zeros((bm, bn), acc_d))
            if fused:
                x = x.astype(jnp.float32)
                if b_scale is not None:
                    x = x * jax.lax.dynamic_slice(
                        b_scale, (g, 0, ni * bn), (1, 1, bn))[0]
                x = apply_epilogue(
                    x, activation=activation,
                    bias=jax.lax.dynamic_slice(
                        bias, (g, 0, ni * bn), (1, 1, bn))[0]
                    if bias is not None else None)
            x = x.astype(out.dtype)
            rows = t * bm + rows_iota
            mask = (rows >= offsets[g]) & (rows < offsets[g + 1])
            cur = jax.lax.dynamic_slice(out, (t * bm, ni * bn), (bm, bn))
            return jax.lax.dynamic_update_slice(
                out, jnp.where(mask, x, cur), (t * bm, ni * bn))

        return jax.lax.fori_loop(0, gn, column, out)

    def guarded(i, out):
        return jax.lax.cond(i < num_instances,
                            lambda o: instance(i, o), lambda o: o, out)

    out = jax.lax.fori_loop(0, group_ids.shape[0], guarded,
                            jnp.zeros((m, n), out_dtype))
    live = jnp.arange(m, dtype=jnp.int32)[:, None] < offsets[-1]
    return jnp.where(live, out, jnp.zeros((), out.dtype))
