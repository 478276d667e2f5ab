"""Blocked online-softmax attention (flash attention) for TPU Pallas.

Why it lives here: the 32k-prefill and 4k-train shapes make attention the
second GEMM hot-spot after the projections, and the paper's methodology
(VMEM-tiled blocks + analytically chosen block shapes) applies directly —
q/k/v tiles are sized by the same VMEM footprint model used for the GEMM
kernels.

Features: causal masking, sliding-window (SWA) masking, GQA via
index-mapped kv heads (no materialized head repeat), fp32 online softmax
with the standard post-exp re-mask so fully-masked rows stay exact.  The
forward computes only the kv blocks the mask leaves a key in: a block
above the causal diagonal or before the window is skipped, and its index
map clamped to a live block, so no DMA is issued for it.

The backward (:func:`flash_attention_bwd`) is the FlashAttention-2 pair
of kernels fed by the forward's per-row log-sum-exp
(``flash_attention(..., return_lse=True)``): a dK/dV kernel that walks
the q blocks of every q head of a GQA group for one kv block, and a dQ
kernel that walks the kv blocks for one q block.  Both skip the blocks
the mask empties as the forward does.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INF
from repro.kernels import _compiler_params

LANES = 128


def _mask(q_pos, k_pos, *, kv_len, causal, window):
    """Which scores a query attends to: real keys, the causal past, the
    window."""
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    return mask


def _min(a, b):
    both_ints = isinstance(a, int) and isinstance(b, int)
    return min(a, b) if both_ints else jnp.minimum(a, b)


def _max(a, b):
    both_ints = isinstance(a, int) and isinstance(b, int)
    return max(a, b) if both_ints else jnp.maximum(a, b)


def kv_block_range(qi, *, bq: int, bkv: int, nkv: int, causal: bool,
                   window: int, q_offset: int):
    """[lo, hi] kv blocks that q block ``qi`` sees a key of: the causal
    diagonal bounds ``hi``, the window ``lo``.  Works on ints and traced
    scalars alike; an empty range has hi < lo."""
    lo, hi = 0, nkv - 1
    if causal:
        hi = _min(hi, (qi * bq + bq - 1 + q_offset) // bkv)
    if window > 0:
        lo = _max(lo, (qi * bq + q_offset - window + 1) // bkv)
    return lo, hi


def q_block_range(kvi, *, bq: int, bkv: int, nq: int, causal: bool,
                  window: int, q_offset: int):
    """[lo, hi] q blocks that see a key of kv block ``kvi`` (the
    transpose of :func:`kv_block_range`)."""
    lo, hi = 0, nq - 1
    if causal:
        lo = _max(lo, (kvi * bkv - q_offset) // bq)
    if window > 0:
        hi = _min(hi, ((kvi + 1) * bkv - 2 - q_offset + window) // bq)
    return lo, hi


def _clamp(i, lo, hi, n):
    """Block ``i`` moved into the live range [lo, hi] (and the grid), so
    that a skipped block repeats its neighbour's index and is not
    fetched."""
    return jnp.clip(jnp.clip(i, lo, hi), 0, n - 1)


_NT = (((1,), (1,)), ((), ()))          # contract the last dims: A @ B^T


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *refs,
                  scale: float, causal: bool, window: int, q_offset: int,
                  bq: int, bkv: int, kv_len: int, nkv: int,
                  return_lse: bool):
    lse_ref, (m_ref, l_ref, acc_ref) = ((refs[0], refs[1:]) if return_lse
                                        else (None, refs))
    qi = pl.program_id(1)
    kvi = pl.program_id(2)

    @pl.when(kvi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a block outside [lo, hi] holds no key the mask leaves: its scores
    # would all be NEG_INF, which leaves m, l and acc as they are
    lo, hi = kv_block_range(qi, bq=bq, bkv=bkv, nkv=nkv, causal=causal,
                            window=window, q_offset=q_offset)

    @pl.when((kvi >= lo) & (kvi <= hi))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, d)
        s = jax.lax.dot_general(q, k, _NT)                   # (bq, bkv)

        q_pos = (qi * bq + q_offset
                 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0))
        k_pos = kvi * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = _mask(q_pos, k_pos, kv_len=kv_len, causal=causal,
                     window=window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                             # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)                       # exact masked rows
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)

        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha \
            + jnp.dot(p, v_ref[0, 0].astype(jnp.float32),
                      preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kvi == pl.num_programs(2) - 1)
    def _flush():
        l = l_ref[:, :1]
        out = acc_ref[...] / jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)
        if lse_ref is not None:
            # m + log l per row, as a lane-dense row: a row nothing
            # attends to gets +inf, so the backward's exp gives 0 there
            lse = jnp.where(l_ref[...] > 0,
                            m_ref[...] + jnp.log(l_ref[...]), jnp.inf)
            lse_ref[0, 0] = lse.T[:1]


class _Geometry(NamedTuple):
    """Padded sizes and statics shared by the forward and the backward."""

    d: int
    dp: int
    bq: int
    bkv: int
    sq_p: int
    skv_p: int
    groups: int
    q_offset: int
    scale: float


def _geometry(q, k, bq, bkv, scale, q_offset) -> _Geometry:
    """The kernels' block clamp (never above the next power of two of the
    dimension), lane-padded head dim and block-padded lengths."""
    _, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    assert hq % hkv == 0
    bq = min(bq, max(8, 1 << (sq - 1).bit_length()))
    bkv = min(bkv, max(128, 1 << (skv - 1).bit_length()))
    return _Geometry(
        d=d, dp=max(LANES, ((d + LANES - 1) // LANES) * LANES),
        bq=bq, bkv=bkv, sq_p=((sq + bq - 1) // bq) * bq,
        skv_p=((skv + bkv - 1) // bkv) * bkv, groups=hq // hkv,
        q_offset=skv - sq if q_offset is None else q_offset,
        scale=float(scale if scale is not None else d ** -0.5))


def _heads_major(x, s_p, dp):
    """(b, s, h, d) -> (b, h, s_p, dp), zero-padded, so that the last two
    dims tile (s, d)."""
    x = jnp.pad(x, ((0, 0), (0, s_p - x.shape[1]), (0, 0),
                    (0, dp - x.shape[3])))
    return x.transpose(0, 2, 1, 3)


def _rows_major(x, s_p):
    """(b, h, s) -> (b, h, 1, s_p): a per-row statistic as lane-dense
    rows, the layout the kernels read it in."""
    x = jnp.pad(x, ((0, 0), (0, 0), (0, s_p - x.shape[2])))
    return x[:, :, None, :]


def _q_major_maps(g: _Geometry, hq: int, nkv: int, causal: bool,
                  window: int):
    """(q, kv, row) index maps of a grid (b*hq, q blocks, kv blocks), the
    forward's and dQ's.  The kv map clamps each step into its q block's
    live range (:func:`kv_block_range`), so that a skipped step repeats
    its neighbour's block and no copy is issued for it."""
    def q_map(bh, qi, kvi):
        return (bh // hq, bh % hq, qi, 0)

    def kv_map(bh, qi, kvi):
        lo, hi = kv_block_range(qi, bq=g.bq, bkv=g.bkv, nkv=nkv,
                                causal=causal, window=window,
                                q_offset=g.q_offset)
        return (bh // hq, (bh % hq) // g.groups, _clamp(kvi, lo, hi, nkv), 0)

    def row_map(bh, qi, kvi):
        return (bh // hq, bh % hq, 0, qi)

    return q_map, kv_map, row_map


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bkv", "scale", "q_offset", "interpret",
    "vmem_bytes", "return_lse"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: float | None = None, q_offset: int | None = None,
                    bq: int = 512, bkv: int = 512,
                    interpret: bool = False,
                    vmem_bytes: int = 0, return_lse: bool = False):
    """q: (b, sq, hq, d); k, v: (b, skv, hkv, d); returns (b, sq, hq, d),
    and with ``return_lse`` also the per-row log-sum-exp ``m + log l``
    (b, hq, sq) in f32, the residual :func:`flash_attention_bwd` reads.

    hq % hkv == 0 (GQA: kv head = q head // group, via BlockSpec index
    maps).  d is padded to the 128-lane width inside; sq/skv are padded to
    block multiples (scores for padded kv positions are masked by
    ``kv_len``).  ``vmem_bytes`` (the plan's modeled working set) sets
    the scoped VMEM limit.  With ``return_lse`` a q block must be a
    multiple of 128 rows or the whole padded sequence.
    """
    b, sq, hq, _ = q.shape
    _, skv, _, _ = k.shape
    g = _geometry(q, k, bq, bkv, scale, q_offset)
    bq, bkv, dp = g.bq, g.bkv, g.dp
    nkv = g.skv_p // bkv
    qt = _heads_major(q, g.sq_p, dp)
    kt = _heads_major(k, g.skv_p, dp)
    vt = _heads_major(v, g.skv_p, dp)

    grid = (b * hq, g.sq_p // bq, nkv)
    q_map, kv_map, row_map = _q_major_maps(g, hq, nkv, causal, window)

    kernel = functools.partial(
        _flash_kernel, scale=g.scale, causal=causal, window=window,
        q_offset=g.q_offset, bq=bq, bkv=bkv, kv_len=skv, nkv=nkv,
        return_lse=return_lse)

    out_specs = pl.BlockSpec((1, 1, bq, dp), q_map)
    out_shape = jax.ShapeDtypeStruct((b, hq, g.sq_p, dp), q.dtype)
    if return_lse:
        out_specs = (out_specs, pl.BlockSpec((1, 1, 1, bq), row_map))
        out_shape = (out_shape, jax.ShapeDtypeStruct((b, hq, 1, g.sq_p),
                                                     jnp.float32))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dp), q_map),
            pl.BlockSpec((1, 1, bkv, dp), kv_map),
            pl.BlockSpec((1, 1, bkv, dp), kv_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, LANES), jnp.float32),   # running denom
            pltpu.VMEM((bq, dp), jnp.float32),      # output accumulator
        ],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"), vmem_bytes),
        interpret=interpret,
    )(qt, kt, vt)

    out, lse = out if return_lse else (out, None)
    out = out.transpose(0, 2, 1, 3)[:, :sq, :, :g.d]
    if return_lse:
        return out, lse[:, :, 0, :sq]
    return out


# ---------------------------------------------------------------------------
# Backward: FlashAttention-2 dK/dV and dQ kernels fed by the forward's lse
# ---------------------------------------------------------------------------

def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                          causal: bool, window: int, q_offset: int, bq: int,
                          bkv: int, kv_len: int, nq: int):
    """One kv block's dK, dV, accumulated over the inner axis, which walks
    (q head of the group, q block).  Scores are held transposed,
    (bkv, bq), so that the lse and D rows broadcast over sublanes."""
    kvi = pl.program_id(1)
    j = pl.program_id(2)
    qi = j % nq

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    lo, hi = q_block_range(kvi, bq=bq, bkv=bkv, nq=nq, causal=causal,
                           window=window, q_offset=q_offset)

    @pl.when((qi >= lo) & (qi <= hi))
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        st = jax.lax.dot_general(k, q, _NT,
                                 preferred_element_type=jnp.float32) * scale
        k_pos = kvi * bkv + jax.lax.broadcasted_iota(jnp.int32, (bkv, bq), 0)
        q_pos = (qi * bq + q_offset
                 + jax.lax.broadcasted_iota(jnp.int32, (bkv, bq), 1))
        mask = _mask(q_pos, k_pos, kv_len=kv_len, causal=causal,
                     window=window)
        pt = jnp.where(mask, jnp.exp(st - lse_ref[0, 0]), 0.0)
        dv_acc[...] += jnp.dot(pt.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[0, 0])
        dk_acc[...] += jnp.dot(dst.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                         dq_ref, dq_acc, *, scale: float, causal: bool,
                         window: int, q_offset: int, bq: int, bkv: int,
                         kv_len: int, nkv: int):
    """One q block's dQ, accumulated over the kv blocks."""
    qi = pl.program_id(1)
    kvi = pl.program_id(2)

    @pl.when(kvi == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    lo, hi = kv_block_range(qi, bq=bq, bkv=bkv, nkv=nkv, causal=causal,
                            window=window, q_offset=q_offset)

    @pl.when((kvi >= lo) & (kvi <= hi))
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
        q_pos = (qi * bq + q_offset
                 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0))
        k_pos = kvi * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = _mask(q_pos, k_pos, kv_len=kv_len, causal=causal,
                     window=window)
        lse = jnp.expand_dims(lse_ref[0, 0, 0], -1)          # (bq, 1)
        di = jnp.expand_dims(di_ref[0, 0, 0], -1)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di)
        dq_acc[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    @pl.when(kvi == pl.num_programs(2) - 1)
    def _flush():
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bkv", "scale", "q_offset", "interpret",
    "vmem_bytes"))
def flash_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        o: jax.Array, lse: jax.Array, do: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        scale: float | None = None,
                        q_offset: int | None = None,
                        bq: int = 512, bkv: int = 512,
                        interpret: bool = False,
                        vmem_bytes: tuple = (0, 0)):
    """(dq, dk, dv) of :func:`flash_attention` from its output ``o`` and
    log-sum-exp ``lse`` (b, hq, sq), for the output cotangent ``do``.

    ``D = rowsum(dO * O)`` is formed in f32 outside the kernels.  Inside,
    the softmax, ``D`` and ``dS`` stay f32; every product feeds the MXU
    its operands at their storage dtype (P and dS cast to it) with f32
    accumulation.  dK and dV of a kv head sum over the q heads of its
    group in VMEM.  ``vmem_bytes`` is the (dK/dV, dQ) kernels' modeled
    working sets, which set their scoped VMEM limits.
    """
    b, sq, hq, _ = q.shape
    _, skv, hkv, _ = k.shape
    g = _geometry(q, k, bq, bkv, scale, q_offset)
    bq, bkv, dp, groups = g.bq, g.bkv, g.dp, g.groups
    nq, nkv = g.sq_p // bq, g.skv_p // bkv
    qt, dout = (_heads_major(x, g.sq_p, dp) for x in (q, do))
    kt, vt = (_heads_major(x, g.skv_p, dp) for x in (k, v))
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    lse_r = _rows_major(lse, g.sq_p)
    di_r = _rows_major(di.transpose(0, 2, 1), g.sq_p)
    statics = dict(scale=g.scale, causal=causal, window=window,
                   q_offset=g.q_offset, bq=bq, bkv=bkv, kv_len=skv)

    # dK/dV: grid (b*hkv, kv blocks, group x q blocks)
    def dkv_q_map(bh, kvi, j):
        lo, hi = q_block_range(kvi, bq=bq, bkv=bkv, nq=nq, causal=causal,
                               window=window, q_offset=g.q_offset)
        return (bh // hkv, (bh % hkv) * groups + j // nq,
                _clamp(j % nq, lo, hi, nq), 0)

    def dkv_row_map(bh, kvi, j):
        b_, h, qi, _ = dkv_q_map(bh, kvi, j)
        return (b_, h, 0, qi)

    def dkv_kv_map(bh, kvi, j):
        return (bh // hkv, bh % hkv, kvi, 0)

    q_spec = pl.BlockSpec((1, 1, bq, dp), dkv_q_map)
    row_spec = pl.BlockSpec((1, 1, 1, bq), dkv_row_map)
    kv_spec = pl.BlockSpec((1, 1, bkv, dp), dkv_kv_map)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, nq=nq, **statics),
        grid=(b * hkv, nkv, groups * nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=(kv_spec, kv_spec),
        out_shape=(jax.ShapeDtypeStruct(kt.shape, k.dtype),
                   jax.ShapeDtypeStruct(vt.shape, v.dtype)),
        scratch_shapes=[pltpu.VMEM((bkv, dp), jnp.float32),
                        pltpu.VMEM((bkv, dp), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"), vmem_bytes[0]),
        interpret=interpret,
    )(qt, kt, vt, dout, lse_r, di_r)

    # dQ: grid (b*hq, q blocks, kv blocks), the forward's
    dq_q_map, dq_kv_map, dq_row_map = _q_major_maps(g, hq, nkv, causal,
                                                    window)
    q_spec = pl.BlockSpec((1, 1, bq, dp), dq_q_map)
    row_spec = pl.BlockSpec((1, 1, 1, bq), dq_row_map)
    kv_spec = pl.BlockSpec((1, 1, bkv, dp), dq_kv_map)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, nkv=nkv, **statics),
        grid=(b * hq, nq, nkv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dp), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"), vmem_bytes[1]),
        interpret=interpret,
    )(qt, kt, vt, dout, lse_r, di_r)

    def back(x, s):
        return x.transpose(0, 2, 1, 3)[:, :s, :, :g.d]

    return back(dq, sq), back(dk, skv), back(dv, skv)
