"""Flash-decoding: single-token attention over a long KV cache (Pallas).

The decode_32k / long_500k serving shapes are pure memory-roofline: one
query token must attend over a 32k–524k cache, so the kernel's job is to
stream k/v through VMEM exactly once at their storage dtype with the
online-softmax state held in VMEM scratch.  The XLA reference path
materializes (b, h, S) logits and (on CPU) fp32 cache copies; this kernel
reads k/v blocks once and writes (groups, d) per kv head.

Grid: (b·hkv, S/bkv) with the kv-block dimension 'arbitrary' (sequential
accumulation).  GQA is handled by shaping the query block as
(groups, d) — the group dim rides the sublane axis, so MQA
(recurrentgemma, groups=16) and GQA (deepseek, groups=8) tile the MXU
without materializing repeated kv heads.  The per-slot positions enter
as a prefetched (b,) vector (`PrefetchScalarGridSpec`) indexed by the
grid's batch coordinate and used only for masking, so one compiled
kernel serves every decode step of a continuous batch — each row
attends at its own length.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INF
from repro.kernels import _compiler_params

LANES = 128
SUBLANES = 8


def _flash_decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *,
                         scale: float, window: int, bkv: int,
                         kv_len: int, hkv: int):
    kvi = pl.program_id(1)

    @pl.when(kvi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # per-slot position: the prefetched (b,) vector indexed by this
    # program's batch coordinate — each row masks at its own length
    pos = pos_ref[pl.program_id(0) // hkv]
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (gp, dp)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, dp)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (gp, bkv)

    gp = q.shape[0]
    k_pos = kvi * bkv + jax.lax.broadcasted_iota(jnp.int32, (gp, bkv), 1)
    mask = (k_pos <= pos) & (k_pos < kv_len)
    if window > 0:
        mask &= k_pos > pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]                                # (gp, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)

    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha \
        + jnp.dot(p, v_ref[0, 0].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kvi == pl.num_programs(1) - 1)
    def _flush():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)) \
            .astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "bkv", "scale", "interpret", "vmem_bytes"))
def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 pos: jax.Array, *, window: int = 0, bkv: int = 512,
                 scale: float | None = None,
                 interpret: bool = False,
                 vmem_bytes: int = 0) -> jax.Array:
    """q: (b, hq, d) one token per slot; caches: (b, S, hkv, d);
    pos: (b,) int32 per-slot positions (a scalar broadcasts — the
    lockstep special case).

    Returns (b, hq, d).  Row i masks cache slots > pos[i] (and a sliding
    window when ``window`` > 0 — positions <= pos[i] - window are
    excluded).  ``vmem_bytes`` (the plan's modeled working set) sets
    the scoped VMEM limit.
    """
    b, hq, d = q.shape
    _, skv, hkv, _ = k_cache.shape
    assert hq % hkv == 0
    groups = hq // hkv
    scale = float(scale if scale is not None else d ** -0.5)

    dp = max(LANES, ((d + LANES - 1) // LANES) * LANES)
    gp = max(SUBLANES, ((groups + SUBLANES - 1) // SUBLANES) * SUBLANES)
    bkv = min(bkv, max(128, 1 << (skv - 1).bit_length()))
    skv_p = ((skv + bkv - 1) // bkv) * bkv

    qt = q.reshape(b, hkv, groups, d)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, gp - groups), (0, dp - d)))
    kt = jnp.pad(k_cache, ((0, 0), (0, skv_p - skv), (0, 0),
                           (0, dp - d))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v_cache, ((0, 0), (0, skv_p - skv), (0, 0),
                           (0, dp - d))).transpose(0, 2, 1, 3)

    grid = (b * hkv, skv_p // bkv)

    def q_map(bh, kvi, pos_ref):
        return (bh // hkv, bh % hkv, 0, 0)

    def kv_map(bh, kvi, pos_ref):
        return (bh // hkv, bh % hkv, kvi, 0)

    kernel = functools.partial(
        _flash_decode_kernel, scale=scale, window=window, bkv=bkv,
        kv_len=skv, hkv=hkv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, gp, dp), q_map),
            pl.BlockSpec((1, 1, bkv, dp), kv_map),
            pl.BlockSpec((1, 1, bkv, dp), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, dp), q_map),
        scratch_shapes=[
            pltpu.VMEM((gp, LANES), jnp.float32),    # running max
            pltpu.VMEM((gp, LANES), jnp.float32),    # running denom
            pltpu.VMEM((gp, dp), jnp.float32),       # accumulator
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, dp), q.dtype),
        compiler_params=_compiler_params(("parallel", "arbitrary"),
                                         vmem_bytes),
        interpret=interpret,
    )(jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)), qt, kt, vt)

    return out[:, :, :groups, :d].reshape(b, hq, d)


def _flash_decode_paged_kernel(pos_ref, tbl_ref, q_ref, k_ref, v_ref,
                               o_ref, m_ref, l_ref, acc_ref, *,
                               scale: float, window: int, ps: int,
                               ps_p: int, hkv: int):
    """Same online softmax as `_flash_decode_kernel`, but the kv block
    for grid step `pi` is whatever physical page the prefetched table
    names — the index_map did the gather, the kernel only re-derives
    the block's logical positions as `pi * ps + lane`."""
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[pl.program_id(0) // hkv]
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (gp, dp)
    k = k_ref[0, 0].astype(jnp.float32)                  # (ps_p, dp)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (gp, ps_p)

    gp = q.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (gp, ps_p), 1)
    k_pos = pi * ps + lane
    mask = (k_pos <= pos) & (lane < ps)
    if window > 0:
        mask &= k_pos > pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)

    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha \
        + jnp.dot(p, v_ref[0, 0].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(pi == pl.num_programs(1) - 1)
    def _flush():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)) \
            .astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "interpret", "vmem_bytes"))
def flash_decode_paged(q: jax.Array, k_pages: jax.Array,
                       v_pages: jax.Array, page_table: jax.Array,
                       pos: jax.Array, *, window: int = 0,
                       scale: float | None = None,
                       interpret: bool = False,
                       vmem_bytes: int = 0) -> jax.Array:
    """Paged flash-decoding: the cache is a shared page pool.

    q: (b, hq, d) one token per slot; k_pages/v_pages:
    (n_pages, page_size, hkv, d) pool shared by every slot;
    page_table: (b, max_pages) int32 — row i's logical block `pi` lives
    in physical page `page_table[i, pi]`; pos: (b,) int32 per-slot
    positions.  Returns (b, hq, d).

    The table joins the per-slot positions as a second prefetched
    scalar operand: the kv BlockSpec index_map reads
    `tbl_ref[bh // hkv, pi]`, so the pipeline DMA fetches exactly the
    pages a row touches (`ceil((pos+1)/page_size)` of them matter;
    later blocks are masked).  When `page_size == bkv` the block
    accumulation order matches `flash_decode` exactly, so paged and
    dense outputs are bit-identical.  ``vmem_bytes`` (the plan's
    modeled working set) sets the scoped VMEM limit.
    """
    b, hq, d = q.shape
    n_pages, ps, hkv, _ = k_pages.shape
    _, max_pages = page_table.shape
    assert hq % hkv == 0
    groups = hq // hkv
    scale = float(scale if scale is not None else d ** -0.5)

    dp = max(LANES, ((d + LANES - 1) // LANES) * LANES)
    gp = max(SUBLANES, ((groups + SUBLANES - 1) // SUBLANES) * SUBLANES)
    ps_p = ((ps + SUBLANES - 1) // SUBLANES) * SUBLANES

    qt = q.reshape(b, hkv, groups, d)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, gp - groups), (0, dp - d)))
    kt = jnp.pad(k_pages, ((0, 0), (0, ps_p - ps), (0, 0),
                           (0, dp - d))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v_pages, ((0, 0), (0, ps_p - ps), (0, 0),
                           (0, dp - d))).transpose(0, 2, 1, 3)

    grid = (b * hkv, max_pages)

    def q_map(bh, pi, pos_ref, tbl_ref):
        return (bh // hkv, bh % hkv, 0, 0)

    def kv_map(bh, pi, pos_ref, tbl_ref):
        return (tbl_ref[bh // hkv, pi], bh % hkv, 0, 0)

    kernel = functools.partial(
        _flash_decode_paged_kernel, scale=scale, window=window, ps=ps,
        ps_p=ps_p, hkv=hkv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, gp, dp), q_map),
            pl.BlockSpec((1, 1, ps_p, dp), kv_map),
            pl.BlockSpec((1, 1, ps_p, dp), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, dp), q_map),
        scratch_shapes=[
            pltpu.VMEM((gp, LANES), jnp.float32),    # running max
            pltpu.VMEM((gp, LANES), jnp.float32),    # running denom
            pltpu.VMEM((gp, dp), jnp.float32),       # accumulator
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, dp), q.dtype),
        compiler_params=_compiler_params(("parallel", "arbitrary"),
                                         vmem_bytes),
        interpret=interpret,
    )(jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)),
      jnp.asarray(page_table, jnp.int32), qt, kt, vt)

    return out[:, :, :groups, :d].reshape(b, hq, d)
