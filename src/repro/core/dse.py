"""Reuse-maximizing tiling DSE — the paper's IP formulation on TPU.

The paper solves, exhaustively, ``max U*V*W`` (on-chip data reuse) subject
to buffer-depth and block-capacity constraints, then gates designs on
off-chip bandwidth.  The TPU formulation is isomorphic:

    maximize   on-chip reuse  == minimize modeled HBM traffic
    subject to VMEM capacity  (repro.core.memory_model.fits_vmem)
               MXU alignment  (lane/sublane multiples)
    ranked by  roofline time, then traffic, then VMEM efficiency

and the two dataflow strategies ('aie' / 'tb') are searched jointly, the
way the paper searches {A,B,C} -> {BRAM,URAM} mapping permutations.

``solve()`` is exhaustive over the candidate grid (the paper solves its IP
"exhaustively" too) and is cached per problem signature — kernels call it
at trace time.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

from repro.core.bandwidth import (
    TrafficEstimate,
    calibration_version,
    estimate,
)
from repro.core.hardware import TPU_V5E, TPUChip
from repro.core.memory_model import (
    fits_vmem,
    vmem_efficiency,
    vmem_footprint,
)
from repro.core.tiling import (
    STRATEGIES,
    GemmProblem,
    TileConfig,
    dtype_bytes,
    min_sublane,
    round_up,
)

# Candidate block edges.  Lane-dim candidates are 128-multiples (MXU edge);
# the m-dim additionally admits small sublane multiples so that skinny
# GEMMs (decode: m = batch) tile without pathological padding.
_LANE_CANDIDATES = (128, 256, 512, 1024, 2048)
_M_EXTRA = (8, 16, 32, 64)


@dataclasses.dataclass(frozen=True)
class TileDesign:
    """One scored point of the DSE (a Table III/IV row analogue)."""

    tile: TileConfig
    traffic: TrafficEstimate
    vmem_bytes: int
    vmem_eff: float
    tile_eff: float

    @property
    def score(self) -> Tuple:
        # Primary: modeled roofline time.  Ties: less HBM traffic, higher
        # VMEM efficiency, smaller footprint.
        return (self.traffic.t_model, self.traffic.hbm_bytes,
                -self.vmem_eff, self.vmem_bytes)


def _m_candidates(m: int, dtype, chip: TPUChip) -> Sequence[int]:
    base = [c for c in _LANE_CANDIDATES]
    sub = min_sublane(dtype, chip)
    extra = [c for c in _M_EXTRA if c >= sub]
    cands = sorted(set(base + extra))
    # never tile beyond the (padded) problem dim
    cap = round_up(m, sub)
    return [c for c in cands if c <= max(cap, cands[0])] or [cands[0]]


def _lane_candidates(dim: int) -> Sequence[int]:
    cap = round_up(dim, 128)
    out = [c for c in _LANE_CANDIDATES if c <= cap]
    return out or [128]


@functools.lru_cache(maxsize=4096)
def _solve_cached(m: int, k: int, n: int, a_dtype: str, b_dtype: str,
                  out_dtype: str, acc_dtype: str, epilogue: str,
                  n_b_operands: int, n_groups: int, chip_name: str,
                  top: int, cal_version: int
                  ) -> Tuple["TileDesign", ...]:
    assert chip_name == TPU_V5E.name, "single-target build"
    chip = TPU_V5E
    p = GemmProblem(m, k, n, a_dtype, out_dtype, acc_dtype, b_dtype,
                    epilogue, n_b_operands, n_groups)
    designs: List[TileDesign] = []
    for strategy in STRATEGIES:
        if n_b_operands > 1 and strategy == "tb":
            continue    # the gated dual-B kernel is output-stationary only
        if n_groups and strategy == "tb":
            continue    # the grouped sweep is output-stationary only
        # sublane minima are per-operand: bm follows A's dtype; B's
        # (bk, bn) block is billed at b_dtype inside fits_vmem, which is
        # what admits ~2x bigger bk for int8 weight streams.
        for bm in _m_candidates(m, a_dtype, chip):
            for bk in _lane_candidates(k):
                for bn in _lane_candidates(n):
                    tile = TileConfig(bm, bk, bn, strategy)
                    if not tile.mxu_aligned(chip):
                        continue
                    if not fits_vmem(tile, p, chip):
                        continue
                    designs.append(TileDesign(
                        tile=tile,
                        traffic=estimate(tile, p, chip),
                        vmem_bytes=vmem_footprint(tile, p, chip).total,
                        vmem_eff=vmem_efficiency(tile, p, chip),
                        tile_eff=tile.tile_efficiency(p),
                    ))
    if not designs:
        raise ValueError(f"no feasible tiling for {p}")
    designs.sort(key=lambda d: d.score)
    return tuple(designs[:top])


def solve(p: GemmProblem, chip: TPUChip = TPU_V5E, top: int = 10
          ) -> List[TileDesign]:
    """Ranked tiling designs for a GEMM problem.  The memo key includes
    the cost-model calibration version: applying measured constants
    (``repro.tune.calibrate.apply``) re-ranks instead of serving stale
    pre-calibration answers."""
    return list(_solve_cached(p.m, p.k, p.n, p.a_dtype, p.b_dtype,
                              p.out_dtype, p.acc_dtype, p.epilogue,
                              p.n_b_operands, p.n_groups, chip.name, top,
                              calibration_version()))


def best_tile(m: int, k: int, n: int, in_dtype: str = "bfloat16",
              out_dtype: str = "bfloat16", acc_dtype: str = "float32",
              strategy: Optional[str] = None, *,
              b_dtype: Optional[str] = None, epilogue: str = "",
              n_b_operands: int = 1, n_groups: int = 0) -> TileConfig:
    """The DSE winner (optionally restricted to one strategy) — what
    ``repro.kernels.ops.gemm`` uses when no tile is given.

    ``in_dtype`` is A's dtype; pass ``b_dtype="int8"`` for the fused
    quantized-weight path (W8A16 / W8A8) so the search bills B at one
    byte/element.  ``epilogue`` (an :class:`repro.kernels.epilogue
    .Epilogue` key string) bills the fused bias/residual operands, and
    ``n_b_operands=2`` searches the dual-B gated kernel's real footprint
    (second B stream + second accumulator; 'aie' only).  ``n_groups=E``
    searches the grouped ragged sweep ('aie' only): ``m`` is the true
    routed row total and the straddle-instance billing pushes the search
    toward small ``bm`` — exactly the expert-imbalance/tile-granularity
    trade the megablocks formulation makes.
    """
    p = GemmProblem(m, k, n, in_dtype, out_dtype, acc_dtype, b_dtype,
                    epilogue, n_b_operands, n_groups)
    for d in solve(p):
        if strategy is None or d.tile.strategy == strategy:
            return d.tile
    raise ValueError(f"no feasible {strategy!r} tiling for {p}")


# ---------------------------------------------------------------------------
# Layer-level traffic: fused vs unfused MLP compositions
# ---------------------------------------------------------------------------

def _gemm_traffic(p: GemmProblem, chip: TPUChip) -> Tuple[float, float]:
    """(total, weight-stream) HBM bytes of one GEMM at its DSE winner.

    The weight component is billed with the winner's real reuse: the B
    panels (and dequant scale vectors) stream once per m-block row, so
    gm > 1 multiplies the weight bytes — attributing those re-streams to
    the weight side keeps the ``activations`` remainder honest.
    """
    d = solve(p, chip, top=1)[0]
    gm, _, _ = d.tile.grid(p)
    scale = p.n * 4 * p.n_b_operands if p.b_dtype == "int8" else 0
    w = (p.b_bytes * p.n_b_operands + scale) * gm
    return d.traffic.hbm_bytes, w


def mlp_traffic(m: int, d: int, d_ff: int, *, fused: bool,
                gated: bool = True, a_dtype: str = "bfloat16",
                b_dtype: Optional[str] = None,
                residual: bool = False,
                chip: TPUChip = TPU_V5E) -> dict:
    """Modeled HBM bytes of one MLP layer (SwiGLU when ``gated`` else a
    single-activation MLP), with each constituent GEMM at its own DSE
    winner.  Returns ``{"total", "weights", "activations"}``.

    Unfused (the pre-epilogue composition): gate/up (or in) GEMMs write
    their (m, d_ff) intermediates to HBM, an XLA elementwise pass re-reads
    them and writes the gated h, and the down GEMM reads h back.  Fused:
    the gated (or activation-epilogue) kernel emits h directly — the
    gate/up intermediates never touch HBM and A streams once — and the
    down GEMM can absorb the residual add.

    ``weights`` is the B-panel traffic at each winner's real reuse
    (gm passes); at decode shapes (gm == 1, single pass) it is an
    identical irreducible floor on both sides, so the fusion credit
    lands entirely in the ``activations`` component — which is why
    decode-shaped layers report the drop on that component.
    """
    act_b = dtype_bytes(a_dtype)
    n_up = 2 if gated else 1

    if fused:
        if gated:
            p_up = GemmProblem(m, d, d_ff, a_dtype, a_dtype, "float32",
                               b_dtype, "silu", 2)
        else:
            p_up = GemmProblem(m, d, d_ff, a_dtype, a_dtype, "float32",
                               b_dtype, "gelu", 1)
        p_down = GemmProblem(m, d_ff, d, a_dtype, a_dtype, "float32",
                             b_dtype, "res" if residual else "", 1)
        t_up, w_up = _gemm_traffic(p_up, chip)
        t_down, w_down = _gemm_traffic(p_down, chip)
        total, w = t_up + t_down, w_up + w_down
        return {"total": total, "weights": w, "activations": total - w}

    p_wide = GemmProblem(m, d, d_ff, a_dtype, a_dtype, "float32", b_dtype)
    p_down = GemmProblem(m, d_ff, d, a_dtype, a_dtype, "float32", b_dtype)
    t_wide, w_wide = _gemm_traffic(p_wide, chip)
    t_down, w_down = _gemm_traffic(p_down, chip)
    total = n_up * t_wide + t_down
    # XLA epilogue pass: read every (m, d_ff) intermediate, write h once
    total += (n_up + 1) * m * d_ff * act_b
    if residual:
        total += 2 * m * d * act_b          # read x, write x + down(h)
    w = n_up * w_wide + w_down
    return {"total": total, "weights": w, "activations": total - w}
