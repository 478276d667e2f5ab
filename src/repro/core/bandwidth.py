"""HBM traffic model per tiling — the Table III/IV 'BW' column analogue.

The paper computes, per design, the worst-case off-chip bytes needed to
sustain the accelerator's native throughput and *gates* the DSE on the
device's DRAM bandwidth.  Here the 'off-chip' level is HBM and the gate is
the roofline: a tiling whose HBM traffic pushes the memory term above the
compute term is memory-bound and ranked accordingly.  The roofline rates
are the chip's datasheet constants.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core.hardware import TPU_V5E, TPUChip
from repro.core.tiling import (
    GemmProblem,
    TileConfig,
    dtype_bytes,
    grouped_instances,
)


def effective_rates(chip: TPUChip, int8: bool) -> tuple:
    """(peak flop/s, HBM bytes/s): the int8 MXU rate when both operands
    are 8-bit, else the bf16 rate."""
    peak = chip.peak_int8_ops if int8 else chip.peak_bf16_flops
    return peak, chip.hbm_bw


@dataclasses.dataclass(frozen=True)
class TrafficEstimate:
    """Modeled HBM traffic and roofline terms for one (tile, problem)."""

    hbm_bytes: float          # total HBM bytes moved
    flops: float              # padded (executed) flops
    t_compute: float          # s
    t_memory: float           # s
    arithmetic_intensity: float

    @property
    def t_model(self) -> float:
        """Roofline execution-time estimate (perfect overlap)."""
        return max(self.t_compute, self.t_memory)

    @property
    def bound(self) -> str:
        return "compute" if self.t_compute >= self.t_memory else "memory"


def hbm_traffic_bytes(tile: TileConfig, p: GemmProblem) -> float:
    """Worst-case HBM bytes for one GEMM under a tiling.

    * ``aie`` (output-stationary, grid m,n,k): every A panel is re-read
      once per n-block column, every B panel once per m-block row, C is
      written once.  (A reused gn times from VMEM's perspective — the
      paper's 'A reused W times'.)
    * ``tb`` (A-stationary, grid m,k,n): A is read once; B re-read per
      m-block row; C is read+written once per k step (PL-accumulator
      pattern).

    Operands are billed at their *own* dtype widths (A at a-bytes, B at
    b-bytes — the per-operand-precision accounting the Versal follow-up
    uses for its energy model), and quantized int8 operands additionally
    move their fp32 scale vectors: a (1, n) per-output-channel vector
    rides with every B panel read, a (m, 1) per-row vector with every A
    panel read.

    Fused extensions: the dual-B gated kernel (``p.n_b_operands == 2``)
    bills *both* B streams (and both scale vectors) while A still moves
    once per n-column — this is exactly the traffic credit of fusing
    SwiGLU's gate/up GEMMs: one A stream instead of two, and zero HBM
    bytes for the (m, n) gate/up intermediates the unfused composition
    writes and re-reads.  A fused epilogue bills its own operands: the
    (1, n) f32 bias vector rides with every m-row of B panels, the
    (m, n) residual is read once.

    Grouped ragged GEMMs (``p.n_groups > 0``, output-stationary only):
    A is charged at the *true* routed rows — ``p.m`` is sum(group_sizes),
    not the dense E*capacity — with each of the worst-case
    ``gm + E - 1`` straddling tile instances re-reading its (bm, pk)
    A rows once per n-block column.  B is charged one (pk, pn) expert
    panel per *instance* (an expert active over several m-tiles streams
    its panel once per tile it owns — never the full (E, k, n) bank),
    the per-expert (1, n) dequant-scale/bias vectors ride per instance,
    and C is written once per unique output tile.  Inactive experts
    (empty groups) cost nothing; the model's static worst case assumes
    all E groups are live.
    """
    from repro.kernels.epilogue import Epilogue
    ep = Epilogue.parse(p.epilogue)
    gm, gn, gk = tile.grid(p)
    pm_, pk, pn = tile.padded_dims(p)
    a_b = dtype_bytes(p.a_dtype)
    b_b = dtype_bytes(p.b_dtype)
    out_b = dtype_bytes(p.out_dtype)
    acc_b = dtype_bytes(p.acc_dtype)
    a_bytes = pm_ * pk * a_b
    b_bytes = pk * pn * b_b * p.n_b_operands
    c_bytes = pm_ * pn * out_b
    a_scale = pm_ * 4 if p.a_dtype == "int8" else 0
    b_scale = pn * 4 * p.n_b_operands if p.b_dtype == "int8" else 0
    bias_bytes = pn * 4 * gm if ep.bias else 0
    res_bytes = pm_ * pn * out_b if ep.residual else 0
    if p.n_groups:
        inst = grouped_instances(tile, p)
        a_inst = inst * tile.bm * pk * a_b
        a_s_inst = inst * tile.bm * 4 if p.a_dtype == "int8" else 0
        b_inst = inst * pk * pn * b_b
        b_s_inst = inst * pn * 4 if p.b_dtype == "int8" else 0
        bias_inst = inst * pn * 4 if ep.bias else 0
        return ((a_inst + a_s_inst) * gn + b_inst + b_s_inst
                + c_bytes + bias_inst)
    if tile.strategy == "aie":
        return ((a_bytes + a_scale) * gn + (b_bytes + b_scale) * gm
                + c_bytes + bias_bytes + res_bytes)
    # 'tb'
    c_rmw = pm_ * pn * acc_b
    return (a_bytes + a_scale) + (b_bytes + b_scale) * gm \
        + c_rmw * (2 * gk - 1) + c_bytes + bias_bytes + res_bytes


def decode_kv_bytes(positions, *, n_kv_heads: int, head_dim: int,
                    dtype="bfloat16", window: int = 0,
                    page_size: Optional[int] = None) -> int:
    """Modeled HBM bytes ONE attention layer streams from its KV cache
    for one decode step, billed at *true per-row positions* — not the
    dense ``max_len`` rows the pre-paged cache allocated.

    A row at position ``p`` reads its ``p + 1``-entry causal history (k
    and v each, at storage dtype); a sliding window clamps that to the
    last ``window`` entries.  A block-paged cache bills whole pages
    ``[0, ceil((p + 1) / page_size))`` and IGNORES the window: the
    paged kernel has no ring buffer — windowed layers page at full
    length and mask in-VMEM, so every history page moves regardless of
    the window span.  ``positions``: iterable of per-row cache
    positions (the engine's live slots).
    """
    per_tok = 2 * n_kv_heads * head_dim * dtype_bytes(dtype)
    tokens = 0
    for p in positions:
        hi = int(p) + 1                      # rows [0, hi) are live
        if page_size:
            tokens += -(-hi // page_size) * page_size
        else:
            lo = max(0, hi - window) if window > 0 else 0
            tokens += hi - lo
    return tokens * per_tok


def estimate(tile: TileConfig, p: GemmProblem, chip: TPUChip = TPU_V5E
             ) -> TrafficEstimate:
    pm_, pk, pn = tile.padded_dims(p)
    flops = 2.0 * pm_ * pk * pn * p.n_b_operands
    if p.n_groups:
        # executed flops: every straddling instance recomputes its full
        # (bm, pk, pn) block — the DSE's pressure toward small bm
        flops = 2.0 * grouped_instances(tile, p) * tile.bm * pk * pn
    # int8 MXU rate needs *both* operands at 8 bits; W8A16 dequantizes
    # in-register and multiplies at the bf16 rate.
    int8 = dtype_bytes(p.a_dtype) == 1 and dtype_bytes(p.b_dtype) == 1
    peak, hbm_bw = effective_rates(chip, int8)
    hbm = hbm_traffic_bytes(tile, p)
    return TrafficEstimate(
        hbm_bytes=hbm,
        flops=flops,
        t_compute=flops / peak,
        t_memory=hbm / hbm_bw,
        arithmetic_intensity=flops / hbm,
    )
