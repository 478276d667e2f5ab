"""VMEM footprint model — the eq. 4-5 / eq. 12-14 analogue for TPU.

The paper predicts physical BRAM/URAM/M20K block usage from logical buffer
geometry and *rejects* tilings that over-subscribe the device (the failure
HLS-AUTO hits).  On TPU the physical resource is VMEM: every Pallas block
is padded to (sublane, lane) tiles, the software pipeline double-buffers
HBM<->VMEM streams, and accumulators live in VMEM scratch.  This module
predicts those bytes exactly the same way the paper predicts block counts,
and the DSE (:mod:`repro.core.dse`) uses it as its capacity constraint.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

from repro.core.hardware import MiB, TPU_V5E, TPUChip
from repro.core.tiling import (
    GemmProblem,
    TileConfig,
    dtype_bytes,
    min_sublane,
    round_up,
)

# Pallas pipelines HBM->VMEM streams with two in-flight stages.
PIPELINE_STAGES = 2

#: a kernel's scoped VMEM limit over its modeled working set: room for
#: what the model does not bill.  In v5e compiles the least limit a
#: kernel needed was at most 1.11x its modeled set
VMEM_HEADROOM = 1.25

#: the compiler's default scoped VMEM limit; no kernel asks for less
DEFAULT_SCOPED_VMEM = 16 * MiB


def padded_tile_bytes(rows: int, cols: int, dtype, chip: TPUChip = TPU_V5E
                      ) -> int:
    """Physical VMEM bytes of one (rows, cols) block after (sublane, lane)
    padding — the f_B/f_U analogue: logical size -> physical size."""
    pr = round_up(rows, min_sublane(dtype, chip))
    pc = round_up(cols, chip.lane)
    return pr * pc * dtype_bytes(dtype)


@dataclasses.dataclass(frozen=True)
class VmemFootprint:
    """Per-buffer VMEM bytes for one kernel instance."""

    a_bytes: int
    b_bytes: int
    out_bytes: int
    acc_bytes: int
    scale_bytes: int = 0          # fused-dequant fp32 scale vector blocks
    bias_bytes: int = 0           # fused-epilogue (1, bn) f32 bias blocks
    residual_bytes: int = 0       # fused-epilogue (bm, bn) residual stream
    temp_bytes: int = 0           # (bm, bn) f32 kernel-body temporaries

    @property
    def total(self) -> int:
        return (self.a_bytes + self.b_bytes + self.out_bytes
                + self.acc_bytes + self.scale_bytes + self.bias_bytes
                + self.residual_bytes + self.temp_bytes)

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self) | {"total": self.total}


def vmem_footprint(tile: TileConfig, p: GemmProblem,
                   chip: TPUChip = TPU_V5E) -> VmemFootprint:
    """Predict the kernel's VMEM working set.

    * ``aie`` (output-stationary): A and B blocks stream (x pipeline
      stages); the fp32/int32 accumulator is a persistent scratch; the out
      block streams.
    * ``tb`` (A-stationary): the A block stays for a whole n sweep, but
      Pallas double-buffers it like every input block, so it is billed
      x pipeline stages too; B and the read-modify-written C stream (x
      pipeline stages each way).

    A and B are billed at *their own* dtype widths — an int8 B block costs
    one byte/element, which is exactly what lets the DSE roughly double
    the feasible ``bk`` for W8A16 GEMMs.  A quantized B additionally
    streams a (1, bn) fp32 per-output-channel scale block.

    Fused extensions: the gated dual-B kernel (``p.n_b_operands == 2``)
    doubles the B stream, the scale blocks and the accumulator scratch;
    a fused epilogue (``p.epilogue``) adds its (1, bn) f32 bias blocks
    and/or its (bm, bn) out-dtype residual stream.

    The kernel body adds (bm, bn) f32 temporaries the compiler
    materializes: one for an ``aie`` flush that applies an activation
    (the gated kernel always does), whose input it reads twice
    (``x * sigmoid(x)``), and one per B operand widened from int8 in the
    kernel (W8A16), whose dot result does not accumulate in place.

    Grouped ragged GEMMs (``p.n_groups > 0``) have the ``aie`` working
    set exactly: each instance streams one (bm, bk) A block and one
    (bk, bn) slice of the expert bank — the per-expert scale/bias
    vectors are the same (1, bn) blocks, and the steering tables live in
    scalar memory, not VMEM — so no grouped-specific branch is needed.
    """
    from repro.kernels.epilogue import Epilogue
    ep = Epilogue.parse(p.epilogue)
    a = padded_tile_bytes(tile.bm, tile.bk, p.a_dtype, chip)
    b = p.n_b_operands * padded_tile_bytes(tile.bk, tile.bn, p.b_dtype,
                                           chip)
    o = padded_tile_bytes(tile.bm, tile.bn, p.out_dtype, chip)
    acc = p.n_b_operands * padded_tile_bytes(tile.bm, tile.bn, p.acc_dtype,
                                             chip)
    scale = 0
    if p.b_dtype == "int8":
        scale = p.n_b_operands * PIPELINE_STAGES * padded_tile_bytes(
            1, tile.bn, "float32", chip)
    bias = 0
    if ep.bias:
        bias = PIPELINE_STAGES * padded_tile_bytes(1, tile.bn, "float32",
                                                   chip)
    residual = 0
    if ep.residual:
        residual = PIPELINE_STAGES * padded_tile_bytes(
            tile.bm, tile.bn, p.out_dtype, chip)
    widened = p.b_dtype == "int8" and p.a_dtype != "int8"
    temps = p.n_b_operands if widened else 0
    if tile.strategy == "aie" and ep.activation:
        temps += 1
    temp = temps * padded_tile_bytes(tile.bm, tile.bn, "float32", chip)
    if tile.strategy == "aie":
        return VmemFootprint(
            a_bytes=PIPELINE_STAGES * a,
            b_bytes=PIPELINE_STAGES * b,
            out_bytes=PIPELINE_STAGES * o,
            acc_bytes=acc,
            scale_bytes=scale,
            bias_bytes=bias,
            residual_bytes=residual,
            temp_bytes=temp,
        )
    # 'tb': A resident across the n sweep, in Pallas's two buffers; C is
    # both input and output stream (read-modify-write accumulation in the
    # output buffer, like the paper's PL adders).
    return VmemFootprint(
        a_bytes=PIPELINE_STAGES * a,
        b_bytes=PIPELINE_STAGES * b,
        out_bytes=2 * PIPELINE_STAGES * padded_tile_bytes(
            tile.bm, tile.bn, p.acc_dtype, chip),
        acc_bytes=0,
        scale_bytes=scale,
        bias_bytes=bias,
        residual_bytes=residual,
        temp_bytes=temp,
    )


def vmem_efficiency(tile: TileConfig, p: GemmProblem,
                    chip: TPUChip = TPU_V5E) -> float:
    """Logical bytes / physical (padded) bytes — the paper's RAM
    *efficiency* metric carried to VMEM tiles."""
    logical = tile.bm * tile.bk * dtype_bytes(p.a_dtype) \
        + tile.bk * tile.bn * dtype_bytes(p.b_dtype) \
        + tile.bm * tile.bn * dtype_bytes(p.out_dtype)
    a = padded_tile_bytes(tile.bm, tile.bk, p.a_dtype, chip)
    b = padded_tile_bytes(tile.bk, tile.bn, p.b_dtype, chip)
    o = padded_tile_bytes(tile.bm, tile.bn, p.out_dtype, chip)
    return logical / (a + b + o)


def fits_vmem_bytes(footprint: int, chip: TPUChip = TPU_V5E) -> bool:
    """Whether a kernel with this modeled working set, plus headroom,
    stays inside the scoped VMEM limit the compiler accepts."""
    return footprint * VMEM_HEADROOM <= chip.vmem_limit_bytes


def fits_vmem(tile: TileConfig, p: GemmProblem, chip: TPUChip = TPU_V5E
              ) -> bool:
    """Capacity constraint (eq. 7-8/15 analogue) of one GEMM tile."""
    return fits_vmem_bytes(vmem_footprint(tile, p, chip).total, chip)


def vmem_limit_bytes(footprint: int, chip: TPUChip = TPU_V5E) -> int:
    """The scoped VMEM limit a kernel with this modeled working set asks
    the compiler for: the footprint plus headroom, never below the
    compiler's default and never above the chip's limit."""
    want = math.ceil(footprint * VMEM_HEADROOM)
    return min(chip.vmem_limit_bytes, max(DEFAULT_SCOPED_VMEM, want))
