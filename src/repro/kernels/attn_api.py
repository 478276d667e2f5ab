"""The attention Spec→Plan→Execute API — the GEMM framework applied to
the second hot-spot.

Mirrors :mod:`repro.kernels.api` exactly: a frozen, hashable
:class:`AttnSpec` describes *what* attention is being asked for
(prefill vs decode vs paged-decode, causal/window, GQA ratio,
per-operand dtypes); :func:`attn_plan`
resolves it at concrete shapes into an :class:`AttnPlan` — the kernel
family (``flash_attention`` / ``attention_blocked`` / ``flash_decode``
/ ``flash_decode_paged`` / the XLA reference paths) **and** its block
sizes, chosen from the same :mod:`repro.core.memory_model` VMEM-fit and
:mod:`repro.core.bandwidth` HBM-billing machinery the GEMM DSE uses
(decode KV streams billed at per-row true positions and page-rounded
pool reads via :func:`repro.core.bandwidth.decode_kv_bytes`); and
:func:`attn_execute` runs the plan through ONE generic
``jax.custom_vjp``.  Its backward is the plan's ``bwd_kernel``: a
``flash_attention`` plan takes the Pallas FlashAttention-2 backward
(:func:`~repro.kernels.flash_attention.flash_attention_bwd`), fed by
the log-sum-exp its forward rule saves; every other family recomputes
through its differentiable XLA composition.

Plans are cached per (spec, shape, dispatch mode) in a
:class:`~repro.kernels.planner.PlanCache` with hit/miss counters, emit
``attn.plan`` telemetry events with the full modeled decision record,
and print themselves via :meth:`AttnPlan.explain` (what
``repro-dryrun --explain`` shows next to the GEMM plans).

The pre-redesign entrypoints (``repro.kernels.ops.attention`` /
``decode_attention`` / ``decode_attention_paged``) live on as deprecated
shims delegating to the one-shot wrappers here.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core import bandwidth
from repro.core.hardware import TPU_V5E, TPUChip
from repro.core.memory_model import PIPELINE_STAGES, fits_vmem_bytes, \
    padded_tile_bytes, vmem_limit_bytes
from repro.core.tiling import cdiv, dtype_bytes, round_up
from repro.dist import sharding as shd
from repro.kernels import ref as _ref
from repro.kernels.blocked_attention import attention_blocked
from repro.kernels.flash_attention import flash_attention, \
    flash_attention_bwd, kv_block_range
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.planner import PlanCache, dispatch_mode, dtname, float0

#: above this many query/kv positions the unblocked reference would
#: materialize (b, h, sq, skv) scores; the planner switches the XLA
#: fallback family to the blocked path (moved here from kernels.ops)
BLOCKED_ATTN_THRESHOLD = 1024

_MODES = ("prefill", "decode", "decode_paged")

#: kernel families whose block sizes are free; paged decode's kv block
#: IS the page size, and the XLA reference paths have no blocks at all
_FREE_BLOCK_KERNELS = ("flash_attention", "attention_blocked", "flash_decode")

_FLOAT_DTYPES = ("float32", "bfloat16", "float16")


# ---------------------------------------------------------------------------
# AttnSpec — the declarative problem description
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """What attention-family member is being asked for (shapes excluded
    — they arrive at :func:`attn_plan` time, so one spec serves every
    shape).

    * ``mode`` — ``prefill`` (q rows over dense k/v, training and
      prompt ingestion), ``decode`` (one token per slot over a dense
      cache + per-slot positions), or ``decode_paged`` (one token per
      slot over the shared page pool + per-slot page tables).
    * ``causal`` / ``window`` — the mask.  Decode is inherently causal;
      a sliding window is a causal look-back construct, so
      ``causal=False`` with ``window > 0`` is rejected.
    * ``group`` — the GQA ratio ``hq // hkv`` (1 = MHA; ``hkv == 1``
      at plan time makes it MQA).
    * ``q_dtype`` / ``kv_dtype`` — per-operand storage dtypes; both
      must be floating.
    * ``bq`` / ``bkv`` — explicit block override, honored verbatim like
      ``GemmSpec(tile=)`` (an infeasible override raises instead of
      silently re-routing).  Rejected for ``decode_paged``: its kv
      block is the page size.
    """

    mode: str = "prefill"
    causal: bool = True
    window: int = 0
    group: int = 1
    q_dtype: str = "bfloat16"
    kv_dtype: str = "bfloat16"
    bq: Optional[int] = None
    bkv: Optional[int] = None

    def __post_init__(self):
        if self.mode not in _MODES:
            raise ValueError(f"mode must be one of {_MODES}, "
                             f"got {self.mode!r}")
        if self.window < 0:
            raise ValueError(f"window must be >= 0, got {self.window}")
        if self.group < 1:
            raise ValueError(f"group (GQA ratio) must be >= 1, "
                             f"got {self.group}")
        if self.mode != "prefill" and not self.causal:
            raise ValueError(f"{self.mode} attention is inherently "
                             "causal; causal=False is a prefill-only "
                             "(cross-attention) shape")
        if not self.causal and self.window:
            raise ValueError("a sliding window is a causal look-back "
                             "construct; window > 0 requires causal=True")
        for name, dt in (("q_dtype", self.q_dtype),
                         ("kv_dtype", self.kv_dtype)):
            if dtname(dt) not in _FLOAT_DTYPES:
                raise ValueError(f"{name} must be floating "
                                 f"({_FLOAT_DTYPES}), got {dt!r}")
        if self.mode == "decode_paged" and (self.bq or self.bkv):
            raise ValueError("decode_paged has no free blocks: the kv "
                             "block is the page size")
        if self.bq is not None and (self.bq < 8 or self.bq % 8):
            raise ValueError(f"bq must be a positive multiple of 8, "
                             f"got {self.bq}")
        if self.bkv is not None and (self.bkv < 128 or self.bkv % 128):
            raise ValueError(f"bkv must be a positive multiple of 128, "
                             f"got {self.bkv}")

    @property
    def key(self) -> str:
        """Canonical string id — starts with ``attn|`` so telemetry
        joins never confuse it with a GEMM spec key."""
        parts = [self.mode, "causal" if self.causal else "full"]
        if self.window:
            parts.append(f"w{self.window}")
        if self.group != 1:
            parts.append(f"g{self.group}")
        parts.append(f"{dtname(self.q_dtype)}x{dtname(self.kv_dtype)}")
        s = ":".join(parts)
        if self.bq is not None or self.bkv is not None:
            s += f"!{self.bq or 0}x{self.bkv or 0}"
        return "attn|" + s

    @classmethod
    def for_operands(cls, q, k, *, mode: str = "prefill",
                     causal: bool = True, window: int = 0,
                     **kw) -> "AttnSpec":
        """Spec inferred from live operands: GQA ratio and per-operand
        dtypes from the arrays, mask/mode from the keywords."""
        hq = q.shape[-2]
        hkv = k.shape[-2]
        if hkv == 0 or hq % hkv:
            raise ValueError(f"hq ({hq}) must be a multiple of "
                             f"hkv ({hkv})")
        return cls(mode=mode, causal=causal, window=window,
                   group=hq // hkv, q_dtype=dtname(q.dtype),
                   kv_dtype=dtname(k.dtype), **kw)


# ---------------------------------------------------------------------------
# AttnProblem — the cost-model view (flops + q/kv/o HBM traffic)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnProblem:
    """One attention problem at concrete shapes, as the cost model sees
    it: true-position flops and the q/kv/o HBM streams.  ``skv`` is the
    dense kv length (for ``decode_paged`` the gathered table extent
    ``max_pages * page_size``); ``page_size`` is 0 unless paged."""

    mode: str
    b: int
    sq: int
    skv: int
    hq: int
    hkv: int
    d: int
    q_dtype: str = "bfloat16"
    kv_dtype: str = "bfloat16"
    causal: bool = True
    window: int = 0
    page_size: int = 0

    # -- mask geometry ----------------------------------------------------
    def _row_extent(self, i: int) -> Tuple[int, int]:
        """[lo, hi) kv positions query row ``i`` attends to (billing
        default: the row block sits at the *end* of the kv sequence,
        ``q_offset = skv - sq`` — the decode/prefill contract)."""
        if not self.causal:
            return 0, self.skv
        hi = min(self.skv, self.skv - self.sq + i + 1)
        lo = max(0, hi - self.window) if self.window > 0 else 0
        return lo, max(hi, 0)

    def attended(self) -> int:
        """True attended kv positions summed over every (batch, q row)
        — the per-row true-position accounting the paged-KV billing
        introduced, applied to flops.  Paged decode rounds up to whole
        pages: the kernel executes every token of a touched page."""
        if self.mode == "prefill":
            per_batch = sum(hi - lo for lo, hi in
                            (self._row_extent(i) for i in range(self.sq)))
            return self.b * per_batch
        hi = self.skv                       # worst case: cache full
        if self.page_size > 0:
            return self.b * cdiv(hi, self.page_size) * self.page_size
        if self.window > 0:
            return self.b * min(hi, self.window)
        return self.b * hi

    # -- flops ------------------------------------------------------------
    @property
    def flops(self) -> float:
        """QK^T + PV: 2 GEMMs of (rows x attended x d) per head."""
        return 4.0 * self.hq * self.d * float(self.attended())

    # -- HBM streams ------------------------------------------------------
    @property
    def q_bytes(self) -> int:
        return self.b * self.sq * self.hq * self.d \
            * dtype_bytes(self.q_dtype)

    @property
    def o_bytes(self) -> int:
        return self.q_bytes                 # output written at q dtype

    def decode_positions(self) -> list:
        """The worst-case per-slot positions the static plan bills at —
        a full cache.  Serve telemetry re-bills with live positions
        through the same :func:`bandwidth.decode_kv_bytes`."""
        return [self.skv - 1] * self.b

    def kv_bytes(self, bq: Optional[int] = None) -> int:
        """Modeled HBM bytes of the k+v streams.

        * decode / decode_paged: one pass over the live cache, billed by
          :func:`repro.core.bandwidth.decode_kv_bytes` — per-row true
          positions, window-clamped dense rows, page-rounded pool reads.
        * prefill flash/blocked: k/v blocks are re-streamed once per
          *query head* per q-block row (the grid walks b*hq rows of
          q blocks), and a causal/windowed row block only reads its
          attended kv extent — so a larger ``bq`` genuinely cuts
          traffic, which is what gives the block DSE a gradient.
        """
        if self.mode != "prefill":
            return int(bandwidth.decode_kv_bytes(
                self.decode_positions(), n_kv_heads=self.hkv,
                head_dim=self.d, dtype=self.kv_dtype,
                window=self.window,
                page_size=self.page_size or None))
        per_tok = 2 * self.d * dtype_bytes(self.kv_dtype)   # k + v
        if bq is None:                      # single pass (XLA reference)
            return self.b * self.hkv * self.skv * per_tok
        toks = 0
        for j0 in range(0, self.sq, bq):
            rows = range(j0, min(self.sq, j0 + bq))
            exts = [self._row_extent(i) for i in rows]
            lo = min(e[0] for e in exts)
            hi = max(e[1] for e in exts)
            toks += max(0, hi - lo)
        return self.b * self.hq * toks * per_tok

    def logits_bytes(self) -> int:
        """The (b, hq, rows, skv) fp32 score round-trip the *unblocked*
        XLA reference materializes (write + softmax read) — the cost the
        flash/blocked families exist to avoid."""
        return 2 * self.b * self.hq * self.sq * self.skv * 4


def attn_traffic(p: AttnProblem, kernel: str,
                 bq: Optional[int], bkv: Optional[int],
                 chip: TPUChip = TPU_V5E) -> bandwidth.TrafficEstimate:
    """Roofline estimate for one (kernel family, blocks) choice —
    same :class:`~repro.core.bandwidth.TrafficEstimate` contract (and
    the same :func:`~repro.core.bandwidth.effective_rates`) as the GEMM
    estimator."""
    hbm = float(p.q_bytes + p.o_bytes)
    if kernel in ("flash_attention", "attention_blocked"):
        hbm += p.kv_bytes(bq or p.sq)
    elif kernel == "xla_ref":
        hbm += p.kv_bytes(None) + p.logits_bytes()
    elif kernel == "xla_decode":
        hbm += p.kv_bytes() + p.logits_bytes()
    elif kernel == "xla_decode_paged":
        # gather materializes a dense copy of the table extent, then the
        # dense path reads it back: pool read + dense write + dense read
        hbm += 3 * p.kv_bytes() + p.logits_bytes()
    else:                                   # flash decode families
        hbm += p.kv_bytes()
    flops = p.flops
    peak, bw = bandwidth.effective_rates(chip, int8=False)
    t_c = flops / peak
    t_m = hbm / bw
    return bandwidth.TrafficEstimate(
        hbm_bytes=hbm, flops=flops, t_compute=t_c, t_memory=t_m,
        arithmetic_intensity=flops / hbm if hbm else 0.0)


# ---------------------------------------------------------------------------
# VMEM footprint of one block choice
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnVmemFootprint:
    """Per-block VMEM bytes of the flash kernels' working set (the XLA
    families report zeros — the compiler manages their buffers)."""

    q_bytes: int
    kv_bytes: int
    o_bytes: int
    scratch_bytes: int

    @property
    def total(self) -> int:
        return (self.q_bytes + self.kv_bytes + self.o_bytes
                + self.scratch_bytes)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self) | {"total": self.total}


def attn_vmem_footprint(p: AttnProblem, kernel: str,
                        bq: Optional[int], bkv: Optional[int],
                        chip: TPUChip = TPU_V5E) -> AttnVmemFootprint:
    """Double-buffered q/k/v streams + the online-softmax scratch
    ((rows, lane) running max/denominator pair and the fp32
    accumulator), via the same ``padded_tile_bytes`` physical-padding
    rule the GEMM footprint uses.

    The backward kernels (``flash_attention_bwd_dkv`` / ``_dq``) stream
    q, dO and the lse and D rows on the q side, k and v on the kv side,
    write double-buffered dK+dV or dQ blocks, and hold their f32
    accumulators plus the (bq, bkv) f32 score, probability, dP and dS
    temporaries and one cast of them."""
    if kernel.startswith("xla"):
        return AttnVmemFootprint(0, 0, 0, 0)
    dp = round_up(p.d, chip.lane)
    if kernel.startswith("flash_attention_bwd"):
        def tile(r, c, dt):
            return padded_tile_bytes(r, c, dt, chip)
        q = PIPELINE_STAGES * (2 * tile(bq, dp, p.q_dtype)
                               + 2 * tile(1, bq, "float32"))
        kv = 2 * PIPELINE_STAGES * tile(bkv, dp, p.kv_dtype)
        temps = 4 * tile(bq, bkv, "float32") + tile(bq, bkv, p.q_dtype)
        if kernel.endswith("dkv"):
            o = 2 * PIPELINE_STAGES * tile(bkv, dp, p.kv_dtype)
            acc = 2 * tile(bkv, dp, "float32")
        else:
            o = PIPELINE_STAGES * tile(bq, dp, p.q_dtype)
            acc = tile(bq, dp, "float32")
        return AttnVmemFootprint(q, kv, o, acc + temps)
    if kernel in ("flash_attention", "attention_blocked"):
        rows = bq or min(p.sq, 512)
        kv_rows = bkv or min(p.skv, 512)
    else:                                   # decode families
        rows = max(8, round_up(p.hq // p.hkv, 8))
        kv_rows = (round_up(p.page_size, 8) if p.page_size
                   else (bkv or 512))
    q = PIPELINE_STAGES * padded_tile_bytes(rows, dp, p.q_dtype, chip)
    kv = 2 * PIPELINE_STAGES * padded_tile_bytes(kv_rows, dp,
                                                 p.kv_dtype, chip)
    o = padded_tile_bytes(rows, dp, p.q_dtype, chip)
    scratch = (2 * padded_tile_bytes(rows, chip.lane, "float32", chip)
               + padded_tile_bytes(rows, dp, "float32", chip))
    return AttnVmemFootprint(q, kv, o, scratch)


def _fits(vmem: AttnVmemFootprint, chip: TPUChip = TPU_V5E) -> bool:
    return fits_vmem_bytes(vmem.total, chip)


#: the two kernels of the Pallas backward, each with its own working set
_BWD_KERNELS = ("flash_attention_bwd_dkv", "flash_attention_bwd_dq")

#: backward blocks in order of preference; the first whose two kernels
#: fit VMEM is taken (clamped like the forward's)
_BWD_BLOCKS = ((512, 512), (256, 256), (128, 128))


class AttnBackward(NamedTuple):
    """How a plan's VJP computes its backward: the kernel family, and for
    the Pallas backward its blocks, the (q, kv) blocks it computes of the
    whole grid, and the two kernels' VMEM working sets."""

    kernel: str
    bq: Optional[int] = None
    bkv: Optional[int] = None
    blocks: Tuple[int, int] = (0, 0)          # (computed, of all)
    vmem: Tuple[int, ...] = ()                # dK/dV, dQ totals

    @property
    def block_share(self) -> Optional[float]:
        return self.blocks[0] / self.blocks[1] if self.blocks[1] else None


def _recompute_family(spec: AttnSpec, p: AttnProblem) -> str:
    """The differentiable XLA composition a backward recomputes through
    when it has no Pallas kernel: blocked for long prefill (no (sq, skv)
    scores), the plain reference for short, the decode einsums."""
    if spec.mode == "decode":
        return "xla_decode"
    if spec.mode == "decode_paged":
        return "xla_decode_paged"
    return ("attention_blocked"
            if max(p.sq, p.skv) > BLOCKED_ATTN_THRESHOLD else "xla_ref")


def _computed_blocks(p: AttnProblem, bq: int, bkv: int) -> Tuple[int, int]:
    """(q, kv) blocks a flash kernel computes, of all: those the causal
    diagonal and the window leave a key in (the kernels' own ranges), at
    the kernels' clamp of the blocks."""
    bq, bkv = min(bq, _pow2_cap(p.sq, 8)), min(bkv, _pow2_cap(p.skv, 128))
    nq, nkv = cdiv(p.sq, bq), cdiv(p.skv, bkv)
    live = 0
    for qi in range(nq):
        lo, hi = kv_block_range(qi, bq=bq, bkv=bkv, nkv=nkv,
                                causal=p.causal, window=p.window,
                                q_offset=p.skv - p.sq)
        live += max(0, hi - lo + 1)
    return live, nq * nkv


def _plan_backward(spec: AttnSpec, p: AttnProblem, kernel: str,
                   bq: Optional[int]) -> AttnBackward:
    """The Pallas backward for a ``flash_attention`` plan whose forward
    can write its lse rows (q block a multiple of the lane width), with
    the first preferred blocks that fit VMEM, or the explicit blocks of
    the spec; every other plan recomputes."""
    if kernel != "flash_attention" or bq % TPU_V5E.lane:
        return AttnBackward(_recompute_family(spec, p))
    bq_cap, bkv_cap = _pow2_cap(p.sq, 8), _pow2_cap(p.skv, 128)
    cands = ([(spec.bq or 512, spec.bkv or 512)]
             if spec.bq or spec.bkv else _BWD_BLOCKS)
    for cq, ckv in cands:
        cq, ckv = min(cq, bq_cap), min(ckv, bkv_cap)
        vmem = tuple(attn_vmem_footprint(p, k, cq, ckv).total
                     for k in _BWD_KERNELS)
        if all(fits_vmem_bytes(v) for v in vmem):
            return AttnBackward("flash_attention_bwd", cq, ckv,
                                _computed_blocks(p, cq, ckv), vmem)
    return AttnBackward(_recompute_family(spec, p))


# ---------------------------------------------------------------------------
# Kernel-family + block-size DSE
# ---------------------------------------------------------------------------

class AttnBlockDesign(NamedTuple):
    """One ranked (blocks, modeled cost) candidate from the block DSE."""

    bq: Optional[int]
    bkv: Optional[int]
    traffic: bandwidth.TrafficEstimate
    vmem: AttnVmemFootprint


def _pow2_cap(x: int, floor: int) -> int:
    """The kernels' internal block clamp: never exceed the next power of
    two of the dimension (floored at the hardware minimum)."""
    return max(floor, 1 << max(0, int(x) - 1).bit_length())


def _choose_kernel(spec: AttnSpec, p: AttnProblem,
                   dispatch: str) -> Tuple[str, Optional[str]]:
    """(kernel family, fallback_reason) — the dispatch decision the
    legacy if/else made, lifted into the plan with the silent
    pallas→XLA fallback made loud via ``fallback_reason``."""
    pallas = dispatch in ("pallas", "interpret")
    if spec.mode == "decode":
        return ("flash_decode" if pallas else "xla_decode"), None
    if spec.mode == "decode_paged":
        return (("flash_decode_paged" if pallas
                 else "xla_decode_paged"), None)
    if pallas and p.sq >= 128:
        return "flash_attention", None
    fam = ("attention_blocked"
           if max(p.sq, p.skv) > BLOCKED_ATTN_THRESHOLD else "xla_ref")
    fallback = None
    if pallas:
        fallback = (f"flash_attention needs sq >= 128 (got sq={p.sq}); "
                    f"falling back to {fam}")
    return fam, fallback


def _block_candidates(kernel: str, p: AttnProblem
                      ) -> Tuple[Tuple[Optional[int], Optional[int]], ...]:
    """Deduped (bq, bkv) candidates, kernel defaults first — modeled
    ties (decode traffic is bkv-invariant) resolve to the default."""
    if kernel == "flash_attention":
        bq_cap = _pow2_cap(p.sq, 8)
        bkv_cap = _pow2_cap(p.skv, 128)
        raw = [(bq, bkv)
               for bq in (512, 1024, 256, 128)
               for bkv in (512, 1024, 256, 128)]
        clamp = [(min(bq, bq_cap), min(bkv, bkv_cap)) for bq, bkv in raw]
    elif kernel == "attention_blocked":
        raw = [(bq, bkv)
               for bq in (512, 1024, 256)
               for bkv in (1024, 2048, 512)]
        clamp = [(min(bq, round_up(p.sq, 8)),
                  min(bkv, round_up(p.skv, 128)))
                 for bq, bkv in raw]
    elif kernel == "flash_decode":
        cap = _pow2_cap(p.skv, 128)
        clamp = [(None, min(bkv, cap))
                 for bkv in (512, 1024, 2048, 256, 128)]
    else:       # paged (block = page size) and the XLA families
        return ((None, None),)
    out, seen = [], set()
    for c in clamp:
        if c not in seen:
            seen.add(c)
            out.append(c)
    return tuple(out)


def attn_solve_topk(spec: AttnSpec, shapes: Tuple[int, ...],
                    k: int = 5) -> Tuple[AttnBlockDesign, ...]:
    """The ranked analytic block candidates — VMEM-fitting (bq, bkv)
    choices for the kernel family the dispatch mode resolves to, best
    modeled roofline time first (stable: ties keep the kernel-default
    ordering); the plan takes the first."""
    p = _problem_for(spec, shapes)
    kernel, _ = _choose_kernel(spec, p, dispatch_mode())
    designs = []
    for bq, bkv in _block_candidates(kernel, p):
        vmem = attn_vmem_footprint(p, kernel, bq, bkv)
        if kernel in ("flash_attention", "flash_decode") \
                and not _fits(vmem):
            continue
        designs.append(AttnBlockDesign(
            bq, bkv, attn_traffic(p, kernel, bq, bkv), vmem))
    designs.sort(key=lambda d: d.traffic.t_model)
    return tuple(designs[:max(int(k), 1)])


# ---------------------------------------------------------------------------
# AttnPlan + the (spec, shape, dispatch-mode)-keyed plan cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnPlan:
    """One resolved attention execution decision: spec x shapes x
    dispatch mode -> kernel family, blocks, and the modeled costs.
    Frozen/hashable so it rides the single custom VJP as a static
    argument."""

    spec: AttnSpec
    b: int
    sq: int
    skv: int
    hq: int
    hkv: int
    d: int
    page_size: int                   # 0 unless decode_paged
    max_pages: int                   # 0 unless decode_paged
    dispatch: str                    # pallas | interpret | ref at plan time
    kernel: str
    bq: Optional[int]
    bkv: Optional[int]
    problem: AttnProblem
    traffic: bandwidth.TrafficEstimate
    vmem: AttnVmemFootprint
    bwd: AttnBackward
    blocks: Tuple[int, int] = (0, 0)          # forward (computed, of all)
    fallback_reason: Optional[str] = None

    @property
    def block_share(self) -> Optional[float]:
        return self.blocks[0] / self.blocks[1] if self.blocks[1] else None

    @property
    def bwd_kernel(self) -> str:
        return self.bwd.kernel

    @property
    def flops(self) -> float:
        return self.traffic.flops

    @property
    def hbm_bytes(self) -> float:
        return self.traffic.hbm_bytes

    @property
    def vmem_bytes(self) -> int:
        return self.vmem.total

    @property
    def shape_key(self) -> str:
        if self.spec.mode == "decode_paged":
            return (f"b{self.b}xp{self.max_pages}x{self.page_size}x"
                    f"h{self.hq}/{self.hkv}xd{self.d}")
        if self.spec.mode == "decode":
            return (f"b{self.b}xS{self.skv}x"
                    f"h{self.hq}/{self.hkv}xd{self.d}")
        return (f"b{self.b}x{self.sq}x{self.skv}x"
                f"h{self.hq}/{self.hkv}xd{self.d}")

    @property
    def grid(self) -> Tuple[int, ...]:
        if self.kernel == "flash_attention":
            return (self.b * self.hq, cdiv(self.sq, self.bq or self.sq),
                    cdiv(self.skv, self.bkv or self.skv))
        if self.kernel == "attention_blocked":
            return (cdiv(self.sq, self.bq or self.sq),
                    cdiv(self.skv, self.bkv or self.skv))
        if self.kernel == "flash_decode":
            return (self.b * self.hkv,
                    cdiv(self.skv, self.bkv or self.skv))
        if self.kernel == "flash_decode_paged":
            return (self.b * self.hkv, self.max_pages)
        return ()

    def explain(self) -> str:
        """Human-readable decision record, the attention analogue of
        ``GemmPlan.explain()``."""
        t = self.traffic
        mib = 2 ** 20
        lines = [f"AttnPlan {self.spec.key} {self.shape_key} "
                 f"[{self.dispatch}]"]
        grid = "x".join(str(g) for g in self.grid) or "-"
        lines.append(f"  kernel   : {self.kernel} (grid {grid})")
        lines.append(f"  blocks   : bq={self.bq or '-'} "
                     f"bkv={self.bkv or '-'}"
                     + (f" page={self.page_size}" if self.page_size
                        else ""))
        if self.vmem.total:
            lines.append(
                f"  vmem     : {self.vmem.total / mib:.2f} MiB, scoped "
                f"limit {vmem_limit_bytes(self.vmem.total) / mib:.0f} of "
                f"{TPU_V5E.vmem_limit_bytes / mib:.0f} MiB "
                f"(q {self.vmem.q_bytes / mib:.2f}, "
                f"kv {self.vmem.kv_bytes / mib:.2f}, "
                f"scratch {self.vmem.scratch_bytes / mib:.2f})")
        else:
            lines.append("  vmem     : XLA-managed")
        kv = t.hbm_bytes - self.problem.q_bytes - self.problem.o_bytes
        pos_note = (" (page-rounded)" if self.page_size
                    else " (true positions)"
                    if self.spec.mode != "prefill" else "")
        lines.append(
            f"  hbm      : {t.hbm_bytes / mib:.2f} MiB "
            f"(q {self.problem.q_bytes / mib:.2f}, "
            f"kv {kv / mib:.2f}{pos_note}, "
            f"o {self.problem.o_bytes / mib:.2f})")
        lines.append(
            f"  roofline : {t.bound}-bound, "
            f"{t.t_model * 1e6:.1f} us modeled "
            f"(AI {t.arithmetic_intensity:.1f} flop/B, "
            f"{t.flops / 1e9:.2f} GFLOP)")
        if self.blocks[1]:
            lines.append(
                f"  forward  : {self.blocks[0]} of {self.blocks[1]} blocks "
                f"computed ({100 * self.block_share:.1f}%)")
        bw = self.bwd
        if bw.bq is not None:
            lines.append(
                f"  backward : {bw.kernel} bq={bw.bq} bkv={bw.bkv}, "
                f"{bw.blocks[0]} of {bw.blocks[1]} blocks computed "
                f"({100 * bw.block_share:.1f}%), vmem dK/dV "
                f"{bw.vmem[0] / mib:.2f} MiB, dQ {bw.vmem[1] / mib:.2f} MiB")
        else:
            lines.append(f"  backward : {bw.kernel} (recompute)")
        if self.fallback_reason:
            lines.append(f"  fallback : {self.fallback_reason}")
        return "\n".join(lines)


def _plan_event(pl: AttnPlan, cache: str) -> None:
    telemetry.event(
        "attn.plan", cache=cache, spec=pl.spec.key, shape=pl.shape_key,
        dispatch=pl.dispatch, kernel=pl.kernel,
        bq=pl.bq, bkv=pl.bkv, page_size=pl.page_size or None,
        hbm_bytes=pl.hbm_bytes, vmem_bytes=pl.vmem_bytes,
        flops=pl.flops, t_model_us=pl.traffic.t_model * 1e6,
        bound=pl.traffic.bound,
        block_share=pl.block_share, bwd_block_share=pl.bwd.block_share,
        fallback_reason=pl.fallback_reason)


#: AttnPlans by (spec, shapes, dispatch mode): REPRO_KERNELS=pallas,
#: interpret and ref resolve to different kernel families
_PLANS = PlanCache("attn", _plan_event)
attn_plan_cache_info = _PLANS.info
attn_plan_cache_clear = _PLANS.clear
attn_plans = _PLANS.values


def _shape_fields(spec: AttnSpec, shapes: Tuple[int, ...]) -> dict:
    """Validated (b, sq, skv, hq, hkv, d, page_size, max_pages) from
    the per-mode canonical shape tuple:

    * prefill:      ``(b, sq, skv, hq, hkv, d)``
    * decode:       ``(b, skv, hq, hkv, d)``
    * decode_paged: ``(b, max_pages, page_size, hq, hkv, d)``
    """
    want = {"prefill": 6, "decode": 5, "decode_paged": 6}[spec.mode]
    if len(shapes) != want:
        raise ValueError(
            f"{spec.mode} shapes must be {want} ints "
            f"(got {len(shapes)}: {shapes})")
    s = tuple(int(x) for x in shapes)
    if any(x <= 0 for x in s):
        raise ValueError(f"shapes must be positive, got {s}")
    if spec.mode == "prefill":
        b, sq, skv, hq, hkv, d = s
        page_size = max_pages = 0
    elif spec.mode == "decode":
        b, skv, hq, hkv, d = s
        sq = 1
        page_size = max_pages = 0
    else:
        b, max_pages, page_size, hq, hkv, d = s
        sq = 1
        skv = max_pages * page_size
    if hq != hkv * spec.group:
        raise ValueError(
            f"hq ({hq}) != hkv ({hkv}) * spec.group ({spec.group})")
    return dict(b=b, sq=sq, skv=skv, hq=hq, hkv=hkv, d=d,
                page_size=page_size, max_pages=max_pages)


def _problem_for(spec: AttnSpec, shapes: Tuple[int, ...]) -> AttnProblem:
    f = _shape_fields(spec, shapes)
    return AttnProblem(
        mode=spec.mode, b=f["b"], sq=f["sq"], skv=f["skv"],
        hq=f["hq"], hkv=f["hkv"], d=f["d"],
        q_dtype=dtname(spec.q_dtype), kv_dtype=dtname(spec.kv_dtype),
        causal=spec.causal, window=spec.window,
        page_size=f["page_size"])


def _resolve(spec: AttnSpec, shapes: Tuple[int, ...]) -> AttnPlan:
    f = _shape_fields(spec, shapes)
    p = _problem_for(spec, shapes)
    dispatch = dispatch_mode()
    kernel, fallback = _choose_kernel(spec, p, dispatch)
    bq = bkv = None
    if kernel in _FREE_BLOCK_KERNELS:
        if spec.bq is not None or spec.bkv is not None:
            # explicit override: honored verbatim, but an infeasible
            # block raises instead of silently re-routing
            cands = _block_candidates(kernel, p)
            bq = spec.bq if spec.bq is not None else cands[0][0]
            bkv = spec.bkv if spec.bkv is not None else cands[0][1]
            if kernel != "attention_blocked" \
                    and not _fits(attn_vmem_footprint(p, kernel, bq, bkv)):
                raise ValueError(
                    f"explicit blocks bq={bq} bkv={bkv} exceed the "
                    f"VMEM budget for {kernel} at {shapes}")
        else:
            designs = attn_solve_topk(spec, shapes, k=1)
            if designs:
                bq, bkv = designs[0].bq, designs[0].bkv
            else:       # nothing fits: smallest candidate, loudly
                bq, bkv = _block_candidates(kernel, p)[-1]
                fallback = ((fallback + "; ") if fallback else "") \
                    + "no block candidate fits VMEM"
    traffic = attn_traffic(p, kernel, bq, bkv)
    vmem = attn_vmem_footprint(p, kernel, bq, bkv)
    return AttnPlan(
        spec=spec, b=f["b"], sq=f["sq"], skv=f["skv"], hq=f["hq"],
        hkv=f["hkv"], d=f["d"], page_size=f["page_size"],
        max_pages=f["max_pages"], dispatch=dispatch, kernel=kernel,
        bq=bq, bkv=bkv, problem=p, traffic=traffic, vmem=vmem,
        fallback_reason=fallback,
        bwd=_plan_backward(spec, p, kernel, bq),
        blocks=(_computed_blocks(p, bq, bkv) if kernel == "flash_attention"
                else (0, 0)))


def attn_plan(spec: AttnSpec, shapes: Tuple[int, ...]) -> AttnPlan:
    """Resolve (and cache) the execution decision for ``spec`` at the
    canonical ``shapes`` tuple (see :func:`_shape_fields` for the
    per-mode layout).  The cache key includes the dispatch mode —
    ``REPRO_KERNELS=pallas|interpret|ref`` resolve to different kernel
    families, so each gets its own entry."""
    return _PLANS.get_or_resolve(
        (spec, tuple(int(x) for x in shapes), dispatch_mode()),
        lambda: _resolve(spec, shapes))


# ---------------------------------------------------------------------------
# The XLA decode paths (moved from kernels.ops — the shims there now
# delegate to this module, so the implementations live with the plan)
# ---------------------------------------------------------------------------

def _decode_attention_xla(q, k_cache, v_cache, pos, *, window):
    """Head-grouped einsums with operands at storage dtype + fp32
    accumulation — casting the cache itself to f32 would materialize and
    rewrite a full-precision copy of the entire stacked cache every
    layer (measured 1.38 TB/step on deepseek decode_32k).

    ``pos``: (b,) per-slot positions (scalar broadcasts) — row i masks
    cache slots > pos[i], the continuous-batching contract."""
    b, hq, d = q.shape
    _, skv, hkv, _ = k_cache.shape
    groups = hq // hkv
    qg = q.reshape(b, hkv, groups, d)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                        preferred_element_type=jnp.float32) * d ** -0.5
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    k_pos = jnp.arange(skv)
    mask = k_pos[None, :] <= posv[:, None]
    if window > 0:
        mask &= k_pos[None, :] > posv[:, None] - window
    logits = jnp.where(mask[:, None, None, :], logits, _ref.NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, hq, d).astype(q.dtype)


def _decode_attention_paged_xla(q, k_pages, v_pages, page_table, pos, *,
                                window):
    """Reference paged decode: gather each row's pages back into a
    dense (b, max_pages * page_size, hkv, d) view and reuse the dense
    path.  Because the engine sizes tables so the gathered length
    equals the dense ``max_len``, the reductions see identical operand
    lengths and the result is bit-identical to the dense cache layout —
    the property the serve acceptance tests pin."""
    n_pages, ps, hkv, d = k_pages.shape
    b, max_pages = page_table.shape
    k = k_pages[page_table].reshape(b, max_pages * ps, hkv, d)
    v = v_pages[page_table].reshape(b, max_pages * ps, hkv, d)
    return _decode_attention_xla(q, k, v, pos, window=window)


# ---------------------------------------------------------------------------
# attn_execute — ONE generic custom VJP for the whole family
# ---------------------------------------------------------------------------

# the Pallas kernels run per device, each on its rows of the batch
_ROWS3, _ROWS4 = ("batch", None, None), ("batch", None, None, None)


def _flash_axes(q, k):
    """Per-device axes of the flash kernels' (b, s, h, d) operands and
    (b, h, s) lse rows: rows over the batch axes, and heads over
    'model' where it divides both the query and the kv heads, so that
    each device holds whole GQA groups."""
    m = shd.axis_sizes(shd.current_mesh()).get("model", 1)
    heads = "model" if q.shape[2] % m == 0 and k.shape[2] % m == 0 \
        else None
    return ("batch", None, heads, None), ("batch", heads, None)


def _flash_forward(pl: AttnPlan, scale, q_offset, q, k, v,
                   return_lse=False):
    spec = pl.spec
    qkv, lse = _flash_axes(q, k)
    return shd.per_device(
        lambda q, k, v: flash_attention(
            q, k, v, causal=spec.causal, window=spec.window,
            scale=scale, q_offset=q_offset, bq=pl.bq, bkv=pl.bkv,
            interpret=pl.dispatch == "interpret",
            vmem_bytes=pl.vmem_bytes, return_lse=return_lse),
        (q, k, v), (qkv,) * 3, (qkv, lse) if return_lse else qkv,
        name="flash_attention")


def _dispatch_attn(pl: AttnPlan, scale, q_offset, q, k, v, pos,
                   page_table):
    spec = pl.spec
    interp = pl.dispatch == "interpret"
    kern = pl.kernel
    if kern == "flash_attention":
        return _flash_forward(pl, scale, q_offset, q, k, v)
    if kern == "attention_blocked":
        return attention_blocked(
            q, k, v, causal=spec.causal, window=spec.window, scale=scale,
            q_offset=q_offset, bq=pl.bq, bkv=pl.bkv)
    if kern == "xla_ref":
        return _ref.attention_ref(
            q, k, v, causal=spec.causal, window=spec.window, scale=scale,
            q_offset=q_offset)
    if kern in ("flash_decode", "flash_decode_paged"):
        # a scalar position reaches every row before the rows split
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (q.shape[0],))
    if kern == "flash_decode":
        return shd.per_device(
            lambda q, k, v, pos: flash_decode(
                q, k, v, pos, window=spec.window, bkv=pl.bkv, scale=scale,
                interpret=interp, vmem_bytes=pl.vmem_bytes),
            (q, k, v, pos), (_ROWS3, _ROWS4, _ROWS4, ("batch",)), _ROWS3,
            name="flash_decode")
    if kern == "xla_decode":
        return _decode_attention_xla(q, k, v, pos, window=spec.window)
    if kern == "flash_decode_paged":
        # the page pool is whole on every device; rows pick their pages
        pool = (None,) * 4
        return shd.per_device(
            lambda q, k, v, tbl, pos: flash_decode_paged(
                q, k, v, tbl, pos, window=spec.window, scale=scale,
                interpret=interp, vmem_bytes=pl.vmem_bytes),
            (q, k, v, page_table, pos),
            (_ROWS3, pool, pool, ("batch", None), ("batch",)), _ROWS3,
            name="flash_decode_paged")
    if kern == "xla_decode_paged":
        return _decode_attention_paged_xla(q, k, v, page_table, pos,
                                           window=spec.window)
    raise AssertionError(f"unknown kernel family {kern!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _attn_core(pl: AttnPlan, scale, q_offset, q, k, v, pos, page_table):
    """The whole attention family behind one VJP: forward dispatches on
    the plan's kernel, backward on its ``bwd_kernel``.
    ``pos``/``page_table`` are int data operands — float0 cotangents."""
    return _dispatch_attn(pl, scale, q_offset, q, k, v, pos, page_table)


def _attn_core_fwd(pl, scale, q_offset, q, k, v, pos, page_table):
    if pl.bwd_kernel == "flash_attention_bwd":
        # the only caller of the forward that asks for its lse
        out, lse = _flash_forward(pl, scale, q_offset, q, k, v,
                                  return_lse=True)
        return out, (q, k, v, pos, page_table, out, lse)
    out = _attn_core(pl, scale, q_offset, q, k, v, pos, page_table)
    return out, (q, k, v, pos, page_table, None, None)


def _attn_core_bwd(pl, scale, q_offset, res, g):
    q, k, v, pos, page_table, out, lse = res
    spec = pl.spec
    bw = pl.bwd
    if bw.kernel == "flash_attention_bwd":
        qkv, rows = _flash_axes(q, k)
        dq, dk, dv = shd.per_device(
            lambda q, k, v, out, lse, g: flash_attention_bwd(
                q, k, v, out, lse, g, causal=spec.causal,
                window=spec.window, scale=scale, q_offset=q_offset,
                bq=bw.bq, bkv=bw.bkv,
                interpret=pl.dispatch == "interpret", vmem_bytes=bw.vmem),
            (q, k, v, out, lse, g), (qkv,) * 4 + (rows, qkv), (qkv,) * 3,
            name="flash_attention_bwd")
        return dq, dk, dv, None, None
    # Recompute backward: re-run the differentiable composition at the
    # saved inputs and pull the cotangent through it.  Long prefill
    # recomputes through the blocked path (lax.scan + checkpoint — no
    # (sq, skv) score materialization); short prefill through the plain
    # reference; decode through the head-grouped XLA einsums.
    if spec.mode == "prefill":
        if bw.kernel == "attention_blocked":
            def fwd(q, k, v):
                return attention_blocked(
                    q, k, v, causal=spec.causal, window=spec.window,
                    scale=scale, q_offset=q_offset,
                    bq=pl.bq or 512, bkv=pl.bkv or 1024)
        else:
            def fwd(q, k, v):
                return _ref.attention_ref(
                    q, k, v, causal=spec.causal, window=spec.window,
                    scale=scale, q_offset=q_offset)
        dq, dk, dv = jax.vjp(fwd, q, k, v)[1](g)
        return dq, dk, dv, None, None
    if spec.mode == "decode":
        def fwd(q, k, v):
            return _decode_attention_xla(q, k, v, pos,
                                         window=spec.window)
        dq, dk, dv = jax.vjp(fwd, q, k, v)[1](g)
        return dq, dk, dv, float0(pos), None

    def fwd(q, k, v):
        return _decode_attention_paged_xla(q, k, v, page_table, pos,
                                           window=spec.window)
    dq, dk, dv = jax.vjp(fwd, q, k, v)[1](g)
    return dq, dk, dv, float0(pos), float0(page_table)


_attn_core.defvjp(_attn_core_fwd, _attn_core_bwd)


def _execute_event(pl: AttnPlan) -> None:
    if not telemetry.enabled():
        return
    if not _PLANS.first_execute(
            (pl.spec, pl.b, pl.sq, pl.skv, pl.hq, pl.d, pl.dispatch)):
        return
    bw = pl.bwd
    telemetry.event("attn.execute", spec=pl.spec.key, shape=pl.shape_key,
                    kernel=pl.kernel, bq=pl.bq, bkv=pl.bkv,
                    hbm_bytes=pl.hbm_bytes, flops=pl.flops,
                    bwd_kernel=bw.kernel, bwd_bq=bw.bq, bwd_bkv=bw.bkv,
                    block_share=pl.block_share,
                    bwd_block_share=bw.block_share)


def attn_execute(pl: AttnPlan, q, k, v, *, pos=None, page_table=None,
                 scale: Optional[float] = None,
                 q_offset: Optional[int] = None):
    """Run a resolved plan on live operands.

    * prefill: ``attn_execute(pl, q, k, v[, scale=, q_offset=])`` with
      q (b, sq, hq, d) and k/v (b, skv, hkv, d);
    * decode: ``attn_execute(pl, q, k_cache, v_cache, pos=pos)`` with
      q (b, hq, d), caches (b, S, hkv, d), pos (b,) int32;
    * decode_paged: ``attn_execute(pl, q, k_pages, v_pages,
      page_table=tbl, pos=pos)`` with pools (n_pages, page_size, hkv, d)
      and tables (b, max_pages) int32.

    Operands that disagree with the plan's spec/shapes raise — a plan
    is a contract, not a hint.
    """
    spec = pl.spec
    if spec.mode == "prefill":
        want_q = (pl.b, pl.sq, pl.hq, pl.d)
        want_kv = (pl.b, pl.skv, pl.hkv, pl.d)
        if pos is not None or page_table is not None:
            raise ValueError("pos/page_table are decode-only operands")
    elif spec.mode == "decode":
        want_q = (pl.b, pl.hq, pl.d)
        want_kv = (pl.b, pl.skv, pl.hkv, pl.d)
        if pos is None:
            raise ValueError("decode plans require pos=")
        if page_table is not None:
            raise ValueError("page_table is a decode_paged operand")
    else:
        want_q = (pl.b, pl.hq, pl.d)
        want_kv = (None, pl.page_size, pl.hkv, pl.d)
        if pos is None or page_table is None:
            raise ValueError("decode_paged plans require pos= and "
                             "page_table=")
        if tuple(page_table.shape) != (pl.b, pl.max_pages):
            raise ValueError(
                f"page_table shape {tuple(page_table.shape)} != plan's "
                f"({pl.b}, {pl.max_pages})")
    if tuple(q.shape) != want_q:
        raise ValueError(f"q shape {tuple(q.shape)} != plan's {want_q}")
    for name, op in (("k", k), ("v", v)):
        got = tuple(op.shape)
        if got[1:] != want_kv[1:] or (want_kv[0] is not None
                                      and got[0] != want_kv[0]):
            raise ValueError(
                f"{name} shape {got} != plan's {want_kv}")
    if dtname(q.dtype) != dtname(spec.q_dtype):
        raise ValueError(f"q dtype {q.dtype} != spec q_dtype "
                         f"{spec.q_dtype}")
    if dtname(k.dtype) != dtname(spec.kv_dtype):
        raise ValueError(f"k dtype {k.dtype} != spec kv_dtype "
                         f"{spec.kv_dtype}")
    if spec.mode != "prefill" and (scale is not None
                                   or q_offset is not None):
        raise ValueError("scale/q_offset are prefill-only statics; "
                         "decode uses d**-0.5 at position pos")
    _execute_event(pl)
    return _attn_core(pl, scale, q_offset, q, k, v, pos, page_table)


# ---------------------------------------------------------------------------
# One-shot wrappers — what every model layer calls (identical dispatch:
# they build the spec and go through the same plan cache)
# ---------------------------------------------------------------------------

def attention(q, k, v, *, causal: bool = True, window: int = 0,
              scale: Optional[float] = None,
              q_offset: Optional[int] = None,
              bq: Optional[int] = None,
              bkv: Optional[int] = None) -> jax.Array:
    """Planned multi-head attention with GQA + optional sliding window.
    q: (b, sq, hq, d); k/v: (b, skv, hkv, d) -> (b, sq, hq, d)."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    spec = AttnSpec.for_operands(q, k, mode="prefill", causal=causal,
                                 window=window, bq=bq, bkv=bkv)
    pl = attn_plan(spec, (b, sq, skv, hq, hkv, d))
    return attn_execute(pl, q, k, v, scale=scale, q_offset=q_offset)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     bkv: Optional[int] = None) -> jax.Array:
    """Planned single-token attention over a dense KV cache.
    q: (b, hq, d); caches: (b, S, hkv, d); pos: (b,) int32 (a scalar
    broadcasts) -> (b, hq, d)."""
    b, hq, d = q.shape
    _, skv, hkv, _ = k_cache.shape
    spec = AttnSpec.for_operands(q, k_cache, mode="decode",
                                 window=window, bkv=bkv)
    pl = attn_plan(spec, (b, skv, hq, hkv, d))
    return attn_execute(pl, q, k_cache, v_cache, pos=pos)


def decode_attention_paged(q, k_pages, v_pages, page_table, pos, *,
                           window: int = 0) -> jax.Array:
    """Planned single-token attention over the block-paged KV pool.
    q: (b, hq, d); pools: (n_pages, page_size, hkv, d); page_table:
    (b, max_pages) int32; pos: (b,) int32 -> (b, hq, d)."""
    b, hq, d = q.shape
    _, page_size, hkv, _ = k_pages.shape
    max_pages = page_table.shape[1]
    spec = AttnSpec.for_operands(q, k_pages, mode="decode_paged",
                                 window=window)
    pl = attn_plan(spec, (b, max_pages, page_size, hq, hkv, d))
    return attn_execute(pl, q, k_pages, v_pages, page_table=page_table,
                        pos=pos)
