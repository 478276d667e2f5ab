"""Three-term roofline extraction from compiled XLA artifacts.

Per the task sheet:

    compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
    memory term     = HLO_bytes / HBM_bw               (per chip)
    collective term = collective operand bytes / link_bw

``compiled.cost_analysis()`` on a partitioned module reports *per-device*
FLOPs and bytes — but counts every ``while`` body ONCE regardless of trip
count (verified empirically), which under-counts any scanned model by
~n_layers×.  The three terms therefore come from
:mod:`repro.core.hlo_cost`, a loop-corrected accounting over the
post-SPMD HLO text (dot/conv FLOPs, boundary bytes, collective operand
bytes — each scaled by the enclosing loops' trip counts).  The raw XLA
numbers are kept in the report as diagnostics.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from repro.core import hlo_cost
from repro.core.hardware import TPU_V5E, TPUChip

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^=]*?\)|\S+)\s+([\w\-]+)"
)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def shape_bytes(type_str: str) -> int:
    """Bytes of an HLO result type ('f32[12,34]', tuples summed)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        numel = 1
        if dims:
            for d in dims.split(","):
                numel *= int(d)
        total += numel * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device operand bytes per collective type, from compiled HLO."""
    out: Dict[str, float] = {op: 0.0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        type_str, opname = m.group(2), m.group(3)
        for coll in COLLECTIVE_OPS:
            if opname == coll or opname.startswith(coll + "-start"):
                nbytes = shape_bytes(type_str)
                if coll == "reduce-scatter":
                    nbytes *= _group_size(line)
                out[coll] += nbytes
                break
    return out


@dataclasses.dataclass
class RooflineReport:
    """The per-(arch x shape x mesh) record for EXPERIMENTS.md SSRoofline."""

    flops_per_device: float
    hbm_bytes_per_device: float
    collective_bytes_per_device: float
    per_collective: Dict[str, float]
    t_compute: float
    t_memory: float
    t_collective: float
    peak_flops: float
    model_flops_per_device: Optional[float] = None
    xla_flops_raw: Optional[float] = None     # cost_analysis (loops x1)
    xla_bytes_raw: Optional[float] = None
    n_while: int = 0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)  # type: ignore[arg-type]

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the chip's compute roofline this step achieves,
        assuming perfect overlap: t_compute / max(all terms)."""
        return self.t_compute / self.t_bound if self.t_bound else 0.0

    @property
    def useful_flops_ratio(self) -> Optional[float]:
        """MODEL_FLOPS / HLO_FLOPs — remat/redundancy waste detector."""
        if self.model_flops_per_device is None or not self.flops_per_device:
            return None
        return self.model_flops_per_device / self.flops_per_device

    def as_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d.update(dominant=self.dominant,
                 roofline_fraction=self.roofline_fraction,
                 useful_flops_ratio=self.useful_flops_ratio,
                 t_bound=self.t_bound)
        return d


def analyze(compiled, *, chip: TPUChip = TPU_V5E, int8: bool = False,
            model_flops_per_device: Optional[float] = None,
            hlo_text: Optional[str] = None) -> RooflineReport:
    """Build the 3-term roofline from a compiled (SPMD) executable.

    Compute/memory rates honor any installed cost-model calibration
    (:func:`repro.core.bandwidth.set_calibration` — measured effective
    constants fitted by ``repro.tune.calibrate``); the collective term
    keeps the datasheet ICI rate (no calibration source measures it).
    """
    from repro.core.bandwidth import effective_rates
    cost = compiled.cost_analysis()
    text = hlo_text if hlo_text is not None else compiled.as_text()
    parsed = hlo_cost.analyze_text(text)
    peak, hbm_bw = effective_rates(chip, int8)
    return RooflineReport(
        flops_per_device=parsed.flops,
        hbm_bytes_per_device=parsed.bytes_accessed,
        collective_bytes_per_device=parsed.collective_total,
        per_collective=parsed.collective_bytes,
        t_compute=parsed.flops / peak,
        t_memory=parsed.bytes_accessed / hbm_bw,
        t_collective=parsed.collective_total / chip.ici_link_bw,
        peak_flops=peak,
        model_flops_per_device=model_flops_per_device,
        xla_flops_raw=float(cost.get("flops", 0.0)),
        xla_bytes_raw=float(cost.get("bytes accessed", 0.0)),
        n_while=parsed.n_while,
    )
