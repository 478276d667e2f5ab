"""Blocked online-softmax attention (flash attention) for TPU Pallas.

Why it lives here: the 32k-prefill and 4k-train shapes make attention the
second GEMM hot-spot after the projections, and the paper's methodology
(VMEM-tiled blocks + analytically chosen block shapes) applies directly —
q/k/v tiles are sized by the same VMEM footprint model used for the GEMM
kernels.

Features: causal masking, sliding-window (SWA) masking, GQA via
index-mapped kv heads (no materialized head repeat), fp32 online softmax
with the standard post-exp re-mask so fully-masked rows stay exact.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, q_offset: int,
                  bq: int, bkv: int, kv_len: int):
    qi = pl.program_id(1)
    kvi = pl.program_id(2)

    @pl.when(kvi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bkv)

    q_pos = (qi * bq + q_offset
             + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0))
    k_pos = kvi * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
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


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bkv", "scale", "q_offset", "interpret",
    "vmem_bytes"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: float | None = None, q_offset: int | None = None,
                    bq: int = 512, bkv: int = 512,
                    interpret: bool = False,
                    vmem_bytes: int = 0) -> jax.Array:
    """q: (b, sq, hq, d); k, v: (b, skv, hkv, d); returns (b, sq, hq, d).

    hq % hkv == 0 (GQA: kv head = q head // group, via BlockSpec index
    maps).  d is padded to the 128-lane width inside; sq/skv are padded to
    block multiples (scores for padded kv positions are masked by
    ``kv_len``).  ``vmem_bytes`` (the plan's modeled working set) sets
    the scoped VMEM limit.
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    assert hq % hkv == 0
    groups = hq // hkv
    if q_offset is None:
        q_offset = skv - sq
    scale = float(scale if scale is not None else d ** -0.5)

    dp = max(LANES, ((d + LANES - 1) // LANES) * LANES)
    bq = min(bq, max(8, 1 << (sq - 1).bit_length()))
    bkv = min(bkv, max(128, 1 << (skv - 1).bit_length()))
    sq_p = ((sq + bq - 1) // bq) * bq
    skv_p = ((skv + bkv - 1) // bkv) * bkv

    def pad(x, s_p):
        return jnp.pad(x, ((0, 0), (0, s_p - x.shape[1]), (0, 0),
                           (0, dp - d)))

    # (b, h, s, d) layout so the last two dims tile (s, d).
    qt = pad(q, sq_p).transpose(0, 2, 1, 3)
    kt = pad(k, skv_p).transpose(0, 2, 1, 3)
    vt = pad(v, skv_p).transpose(0, 2, 1, 3)

    grid = (b * hq, sq_p // bq, skv_p // bkv)

    def q_map(bh, qi, kvi):
        return (bh // hq, bh % hq, qi, 0)

    def kv_map(bh, qi, kvi):
        return (bh // hq, (bh % hq) // groups, kvi, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bkv=bkv, kv_len=skv)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dp), q_map),
            pl.BlockSpec((1, 1, bkv, dp), kv_map),
            pl.BlockSpec((1, 1, bkv, dp), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dp), q_map),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_p, dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, LANES), jnp.float32),   # running denom
            pltpu.VMEM((bq, dp), jnp.float32),      # output accumulator
        ],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"), vmem_bytes),
        interpret=interpret,
    )(qt, kt, vt)

    return out.transpose(0, 2, 1, 3)[:, :sq, :, :d]
