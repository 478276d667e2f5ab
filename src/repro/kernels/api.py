"""One declarative GEMM operator API: ``GemmSpec`` -> ``plan`` ->
``execute``.

The paper's core contribution is a *systematic framework*: one GEMM
problem description is mapped onto the best platform-specific execution
strategy (Versal AIE vs Stratix tensor-block) by an analytical DSE, and
the same description drives every precision and fusion variant.  This
module is that pipeline as the reproduction's only GEMM entrypoint:

* :class:`GemmSpec` — a frozen, hashable description of the GEMM family
  member being asked for: per-operand dtypes (a quantized B is an int8
  operand with a per-output-channel scale), an optional fused
  :class:`~repro.kernels.epilogue.Epilogue`, an optional gated second B
  operand (``act(A W_g) * (A W_u)``), and strategy / tile / out-dtype
  overrides.  Invalid strategies and activations fail at *construction*
  with the allowed set — nothing falls through to a silent default.
* :func:`plan` — resolves the spec for concrete ``(m, k, n)`` shapes
  exactly once (cached on the spec+shape key): the reuse-maximizing DSE
  (:mod:`repro.core.dse`) picks strategy + tile, explicit user tiles are
  validated against :func:`repro.core.memory_model.fits_vmem` /
  ``feasible_bk`` (infeasible overrides raise instead of being silently
  replaced), and the modeled HBM traffic, VMEM footprint and flops ride
  on the returned :class:`GemmPlan`.  ``GemmPlan.explain()`` renders the
  whole decision — chosen kernel, tile, modeled bytes, fallback reasons
  — and ``repro-dryrun --explain`` surfaces it per model.
* :func:`execute` — runs a plan on concrete operands through ONE generic
  ``jax.custom_vjp`` whose forward *and* backward are driven by the plan
  (quant routing, epilogue recompute, gated composition), replacing the
  six hand-specialized VJP wrappers the pre-redesign dispatch layer
  accreted.  :func:`gemm` is the one-shot composition of the three.

Dispatch policy (the hardware-adaptation contract) is unchanged: Pallas
kernels on TPU (or under ``REPRO_KERNELS=interpret``), the mathematically
identical pure-jnp references elsewhere — but the *plan* is computed the
same way everywhere, so the cost model stays introspectable on hosts
with no TPU.  The legacy ``repro.kernels.ops`` entrypoints are deprecated
shims over this module.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import quant as _quant
from repro import telemetry
from repro.core import dse
from repro.core.bandwidth import TrafficEstimate, estimate
from repro.core.hardware import TPU_V5E
from repro.core.memory_model import VmemFootprint, fits_vmem, \
    vmem_efficiency, vmem_footprint, vmem_limit_bytes
from repro.core.tiling import STRATEGIES, GemmProblem, TileConfig, \
    grouped_instances, round_up
from repro.dist import sharding as shd
from repro.kernels import ref as _ref
from repro.kernels.epilogue import ACTIVATIONS, Epilogue
from repro.kernels.gemm_aie import gemm_aie
from repro.kernels.gemm_gated import gemm_gated as _gemm_gated_kernel
from repro.kernels.gemm_grouped import gemm_grouped as _gemm_grouped_kernel
from repro.kernels.gemm_tb import feasible_bk, gemm_tb


# ---------------------------------------------------------------------------
# Kernel-mode selection (shared by every kernel entrypoint)
# ---------------------------------------------------------------------------

def _mode() -> str:
    env = os.environ.get("REPRO_KERNELS", "auto")
    if env in ("interpret", "ref", "pallas"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def use_pallas() -> bool:
    return _mode() in ("pallas", "interpret")


def _interpret() -> bool:
    return _mode() == "interpret"


def _dtname(dt) -> str:
    return jnp.dtype(dt).name


def _is_quant(b) -> bool:
    return isinstance(b, dict) and {"q", "scale"} <= set(b)


# ---------------------------------------------------------------------------
# GemmSpec — the declarative problem description
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GemmSpec:
    """What GEMM-family member is being asked for (shapes excluded —
    they arrive at :func:`plan` time, so one spec serves every shape).

    * ``a_dtype`` / ``b_dtype`` — per-operand dtypes.  ``b_quant=True``
      means B arrives as a ``{"q", "scale"}`` int8 struct from
      :mod:`repro.quant` (b_dtype is forced to int8): the kernel streams
      q at one byte/element and applies the per-output-channel scale to
      the accumulator in-register.
    * ``gated`` — dual-B kernel ``act(A B_gate) * (A B_up)`` (the
      SwiGLU core): one resident A stream, both intermediates stay in
      VMEM.  Requires an epilogue activation; bias / residual /
      out-quant terms and the 'tb' strategy are rejected.
    * ``grouped`` — the ragged MoE family member: A is (m, k) tokens
      sorted by expert (m = *true* routed rows), B an (E, k, n) expert
      bank, and ``execute`` takes a ``group_sizes=`` (E,) vector.  Plans
      arrive with extended shapes ``(m, k, n, E[, dense_rows])`` so the
      cost model bills the straddling tile instances and ``explain()``
      can report the padding-flops delta vs the dense E*capacity
      formulation.  Output-stationary only ('tb' rejected), single-B
      (``gated`` rejected), epilogue limited to per-expert bias +
      activation, and measured autotuning is skipped (the tuner's
      measurement harness is dense-only) — plans stay analytic.
    * ``epilogue`` — declarative bias / activation / residual /
      out-quant fused into the kernel flush (an
      :class:`~repro.kernels.epilogue.Epilogue`, or its key string).
    * ``strategy`` / ``tile`` — overrides for the DSE.  An explicit tile
      is honored verbatim (quantized or not) after a feasibility check;
      an infeasible explicit tile raises at plan time.
    * ``out_dtype`` — ``None`` resolves to ``a_dtype`` (int8 when the
      epilogue quantizes the output).
    * ``tune`` — measured autotuning (:mod:`repro.tune`): ``True`` makes
      ``plan()`` consult the persistent tuning cache and, on a miss,
      time the top-K analytic candidates on-device and pick the measured
      winner; ``False`` forces the purely analytic DSE; ``None``
      (default) defers to ``repro.tune.enable()`` / ``REPRO_AUTOTUNE``.
      Excluded from :attr:`key` so tuning-cache entries join with the
      same spec regardless of *how* tuning was switched on.

    Frozen and hashable: specs key the plan cache, ride jit static
    arguments, and serialize their intent into ``GemmProblem`` for the
    cost model.
    """

    a_dtype: str = "bfloat16"
    b_dtype: str = "bfloat16"
    b_quant: bool = False
    gated: bool = False
    grouped: bool = False
    epilogue: Epilogue = Epilogue()
    out_dtype: Optional[str] = None
    strategy: Optional[str] = None
    tile: Optional[TileConfig] = None
    tune: Optional[bool] = None

    def __post_init__(self):
        object.__setattr__(self, "a_dtype", _dtname(self.a_dtype))
        if self.b_quant:
            object.__setattr__(self, "b_dtype", "int8")
        else:
            object.__setattr__(self, "b_dtype", _dtname(self.b_dtype))
        if self.out_dtype is not None:
            object.__setattr__(self, "out_dtype", _dtname(self.out_dtype))
        if isinstance(self.epilogue, str):
            object.__setattr__(self, "epilogue",
                               Epilogue.parse(self.epilogue))
        if self.strategy is not None and self.strategy not in STRATEGIES:
            raise ValueError(
                f"unknown strategy {self.strategy!r}: choose from "
                f"{STRATEGIES} (or None for the DSE to search both)")
        if self.tile is not None and not isinstance(self.tile, TileConfig):
            raise ValueError(f"tile must be a TileConfig, got {self.tile!r}")
        if self.gated:
            if self.epilogue.activation is None:
                raise ValueError(
                    "gated GEMM requires an epilogue activation: choose "
                    f"from {tuple(ACTIVATIONS)}")
            if self.epilogue.bias or self.epilogue.residual \
                    or self.epilogue.out_quant:
                raise ValueError(
                    "gated GEMM fuses only the gate activation; bias / "
                    "residual / out-quant epilogue terms are unsupported "
                    f"(got {self.epilogue.key!r})")
            if self.strategy == "tb" or (self.tile is not None
                                         and self.tile.strategy == "tb"):
                raise ValueError(
                    "the gated dual-B kernel is output-stationary "
                    "('aie') only; strategy/tile 'tb' is infeasible")
        if self.grouped:
            if self.gated:
                raise ValueError("grouped GEMM is single-B; it cannot "
                                 "be gated")
            if self.epilogue.residual or self.epilogue.out_quant:
                raise ValueError(
                    "grouped GEMM fuses only a per-expert bias + "
                    "activation; residual / out-quant epilogue terms "
                    f"are unsupported (got {self.epilogue.key!r})")
            if self.strategy == "tb" or (self.tile is not None
                                         and self.tile.strategy == "tb"):
                raise ValueError(
                    "the grouped ragged kernel is output-stationary "
                    "('aie') only; strategy/tile 'tb' is infeasible")

    @property
    def key(self) -> str:
        """Compact canonical string — the join key telemetry events and
        the model-vs-measured report use for this spec."""
        s = f"{self.a_dtype}x{self.b_dtype}"
        if self.b_quant:
            s += "{q}"
        if self.gated:
            s += ":gated"
        if self.grouped:
            s += ":grouped"
        if self.epilogue.key:
            s += f":{self.epilogue.key}"
        if self.out_dtype:
            s += f"->{self.out_dtype}"
        if self.strategy:
            s += f"!{self.strategy}"
        if self.tile is not None:
            s += f"!{self.tile.bm}x{self.tile.bk}x{self.tile.bn}"
        return s

    @classmethod
    def for_operands(cls, a, b, b2=None, *, bias=None,
                     activation: Optional[str] = None, residual=None,
                     out_scale=None, strategy: Optional[str] = None,
                     tile: Optional[TileConfig] = None,
                     out_dtype=None,
                     tune: Optional[bool] = None) -> "GemmSpec":
        """Spec inferred from concrete operands (arrays or ``{"q",
        "scale"}`` weight structs) plus the optional epilogue set — what
        the one-shot :func:`gemm` and the legacy shims build."""
        bq = _is_quant(b)
        if b2 is not None and _is_quant(b2) != bq:
            raise ValueError("quantize both gated operands or neither")
        gated = b2 is not None
        if gated:
            if bias is not None or residual is not None \
                    or out_scale is not None:
                raise ValueError("gated GEMM takes no bias/residual/"
                                 "out_scale epilogue operands")
            ep = Epilogue(activation=activation)
        else:
            ep = Epilogue.from_args(bias, activation, residual, out_scale)
        return cls(
            a_dtype=_dtname(a.dtype),
            b_dtype="int8" if bq else _dtname(b.dtype),
            b_quant=bq, gated=gated, epilogue=ep,
            out_dtype=None if out_dtype is None else _dtname(out_dtype),
            strategy=strategy, tile=tile, tune=tune)


def gemm_shapes(a, b) -> Tuple[int, int, int]:
    """The planned ``(m, k, n)``: leading dims of ``a`` flatten into M
    (the paper tiles 2-D GEMM; models bring (b, s, d))."""
    k = a.shape[-1]
    n = (b["q"] if _is_quant(b) else b).shape[-1]
    return (math.prod(a.shape[:-1]), k, n)


def gemm_grouped_shapes(a, b, dense_rows: Optional[int] = None
                        ) -> Tuple[int, int, int, int, int]:
    """The planned ``(m, k, n, E, dense_rows)`` of a grouped spec: ``a``
    is the (m, k) group-sorted token buffer (m = true routed rows), ``b``
    the (E, k, n) expert bank.  ``dense_rows`` is what the dense
    capacity-padded formulation would multiply (E * capacity) — it rides
    the plan so ``explain()`` can state the padding-flops savings;
    defaults to ``m`` (no claimed savings)."""
    bank = b["q"] if _is_quant(b) else b
    e, k, n = bank.shape
    m = math.prod(a.shape[:-1])
    return (m, k, n, e, int(dense_rows) if dense_rows else m)


# ---------------------------------------------------------------------------
# GemmPlan + the spec+shape-keyed plan cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TunedInfo:
    """The measured-autotuning record riding a tuned plan: the winner's
    measured time (median, with spread), the analytic first choice it
    was compared against, and whether the answer came from the
    persistent cache (zero re-measurement) or a fresh top-K sweep."""

    t_measured_us: float            # winner median wall-clock
    spread: float                   # (max-min)/median of kept samples
    t_analytic_us: Optional[float]  # measured time of the DSE's rank-0
    analytic_tile: str              # e.g. "aie 16x512x512"
    k_searched: int
    from_cache: bool


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """One resolved execution decision: spec x (m, k, n) -> strategy,
    tile and the modeled costs the DSE ranked it by.  Frozen/hashable so
    it rides the single custom VJP as a static argument."""

    spec: GemmSpec
    m: int
    k: int
    n: int
    problem: GemmProblem
    tile: TileConfig
    traffic: TrafficEstimate
    vmem: VmemFootprint
    fallback_reason: Optional[str] = None
    tuned: Optional[TunedInfo] = None
    n_groups: int = 0           # grouped family: expert-group count E
    dense_rows: int = 0         # ... and the dense E*capacity row count

    @property
    def source(self) -> str:
        """How the tile was chosen: ``'tuned'`` (measured winner from
        the autotuner) or ``'analytic'`` (cost-model DSE)."""
        return "tuned" if self.tuned is not None else "analytic"

    @property
    def hbm_bytes(self) -> float:
        """Modeled HBM bytes of one forward execution at this tile."""
        return self.traffic.hbm_bytes

    @property
    def flops(self) -> float:
        """Padded (executed) flops at this tile."""
        return self.traffic.flops

    @property
    def vmem_bytes(self) -> int:
        """Modeled VMEM working set of the kernel instance."""
        return self.vmem.total

    def explain(self) -> str:
        """Human-readable decision record: chosen kernel, tile, modeled
        traffic/footprint, and why any fallback happened."""
        s, p, t = self.spec, self.problem, self.tile
        mode = _mode()
        if mode in ("pallas", "interpret"):
            kern = "pallas " + ("gemm_gated" if s.gated else
                                "gemm_grouped" if s.grouped else
                                f"gemm_{t.strategy}")
            if mode == "interpret":
                kern += " (interpret)"
        else:
            kern = "jnp reference (no TPU; tile/traffic modeled only)"
        b_desc = p.b_dtype + (" {q,scale}" if s.b_quant else "")
        if s.gated:
            b_desc = "2x " + b_desc
        gm, gn, gk = t.grid(p)
        lines = [
            f"GemmPlan {self.m}x{self.k}x{self.n}  A {p.a_dtype}  "
            f"B {b_desc}  -> {p.out_dtype} (acc {p.acc_dtype})",
            f"  kernel   : {kern}",
            f"  tile     : {t.strategy} {t.bm}x{t.bk}x{t.bn}"
            f"{'  (user override)' if s.tile is not None else ''}  "
            f"grid (gm,gn,gk)=({gm},{gn},{gk})  "
            f"pad eff {t.tile_efficiency(p):.0%}",
            f"  vmem     : {self.vmem.total / 2**20:.2f} MiB, scoped "
            f"limit {vmem_limit_bytes(self.vmem.total) / 2**20:.0f} of "
            f"{TPU_V5E.vmem_limit_bytes / 2**20:.0f} MiB  "
            f"(a {self.vmem.a_bytes >> 10} KiB, b {self.vmem.b_bytes >> 10}"
            f" KiB, acc {self.vmem.acc_bytes >> 10} KiB)  "
            f"eff {vmem_efficiency(t, p):.0%}",
            f"  hbm      : {self.traffic.hbm_bytes / 2**20:.2f} MiB "
            f"modeled  AI {self.traffic.arithmetic_intensity:.0f} flop/B",
            f"  roofline : {self.traffic.bound}-bound  "
            f"t_model {self.traffic.t_model * 1e6:.1f} us  "
            f"(t_comp {self.traffic.t_compute * 1e6:.1f}, "
            f"t_mem {self.traffic.t_memory * 1e6:.1f})",
            f"  epilogue : {s.epilogue.key or '(none)'}"
            + (f"  gated({s.epilogue.activation})" if s.gated else ""),
        ]
        if p.n_groups:
            inst = grouped_instances(t, p)
            dense_flops = 2.0 * self.dense_rows * p.k * p.n
            saved = 1.0 - self.flops / dense_flops if dense_flops else 0.0
            lines.insert(4, (
                f"  grouped  : E={p.n_groups} groups, <={inst} tile "
                f"instances  A/HBM billed at true rows "
                f"(m={self.m} of {self.dense_rows} dense-capacity), "
                f"B one {t.bk}x{t.bn} panel per instance"))
            lines.insert(5, (
                f"  padding  : {self.flops / 1e9:.2f} GFLOP executed vs "
                f"{dense_flops / 1e9:.2f} dense-capacity "
                f"({saved:+.0%} saved)"))
        if self.tuned is not None:
            ti = self.tuned
            t_model_us = self.traffic.t_model * 1e6
            src = (f"  source   : tuned ({'cache' if ti.from_cache else f'measured top-{ti.k_searched}'})  "
                   f"{ti.t_measured_us:.1f} us measured vs "
                   f"{t_model_us:.1f} us modeled "
                   f"({ti.t_measured_us / t_model_us:.1f}x model, "
                   f"spread {ti.spread:.0%})")
            lines.append(src)
            if ti.t_analytic_us is not None \
                    and ti.analytic_tile != f"{t.strategy} {t.bm}x{t.bk}x{t.bn}":
                lines.append(
                    f"             analytic first choice "
                    f"{ti.analytic_tile} measured "
                    f"{ti.t_analytic_us:.1f} us")
        else:
            lines.append("  source   : analytic")
        if self.fallback_reason:
            lines.append(f"  fallback : {self.fallback_reason}")
        return "\n".join(lines)


class PlanCacheInfo(NamedTuple):
    entries: int
    hits: int
    misses: int


_plan_cache: dict = {}
_executed: set = set()          # plan keys whose execute() already traced
_plan_hits = 0
_plan_misses = 0


def plan_cache_info() -> PlanCacheInfo:
    """(entries, hits, misses) of the spec+shape plan cache — repeated-
    shape workloads should show DSE resolution ran once per unique
    (spec, shape)."""
    return PlanCacheInfo(len(_plan_cache), _plan_hits, _plan_misses)


def plan_cache_clear() -> None:
    """Drop every cached plan and zero the hit/miss counters (tests that
    monkeypatch the DSE or feasibility checks must call this, or stale
    plans computed under different rules leak between tests; benchmark
    sections call it so per-section hit/miss counts start clean)."""
    global _plan_hits, _plan_misses
    _plan_cache.clear()
    _executed.clear()
    _plan_hits = 0
    _plan_misses = 0


def plans() -> Tuple[GemmPlan, ...]:
    """Every plan resolved so far (insertion order) — what
    ``repro-dryrun --explain`` dumps after lowering a model."""
    return tuple(_plan_cache.values())


def _clamp_tile(tile: TileConfig, m: int, k: int, n: int) -> TileConfig:
    bm = min(tile.bm, round_up(m, 8))
    bk = min(tile.bk, round_up(k, 128))
    bn = min(tile.bn, round_up(n, 128))
    return TileConfig(bm, bk, bn, tile.strategy)


def _infeasible_reason(tile: TileConfig, p: GemmProblem) -> Optional[str]:
    """Why a tile cannot run, or None.  'tb' keeps a (bm, bk) A block
    VMEM-resident and refines its own k-chunking, so its gate is
    ``feasible_bk``; 'aie' streams everything, so plain ``fits_vmem``."""
    acc = jnp.int32 if p.a_dtype == "int8" else jnp.float32
    if tile.strategy == "tb":
        if feasible_bk(round_up(p.m, tile.bm), round_up(p.k, tile.bk),
                       round_up(p.n, tile.bn), tile,
                       jnp.dtype(p.a_dtype), jnp.dtype(p.b_dtype),
                       jnp.dtype(p.out_dtype), acc,
                       epilogue=p.epilogue) > 0:
            return None
        return ("no k-chunk keeps the resident (bm, bn) blocks inside "
                "the VMEM budget (feasible_bk == 0)")
    if fits_vmem(tile, p):
        return None
    return (f"VMEM footprint {vmem_footprint(tile, p).total / 2**20:.1f} "
            f"MiB with headroom exceeds the "
            f"{TPU_V5E.vmem_limit_bytes / 2**20:.0f} MiB scoped limit")


def plan(spec: GemmSpec, shapes: Tuple[int, ...]) -> GemmPlan:
    """Resolve ``spec`` for concrete ``(m, k, n)`` — strategy + tile via
    the DSE (or a validated user override) plus the modeled costs —
    exactly once per (spec, shape) key.  Grouped specs take the extended
    shapes ``(m, k, n, E[, dense_rows])`` (:func:`gemm_grouped_shapes`)."""
    global _plan_hits, _plan_misses
    shapes = tuple(int(x) for x in shapes)
    if spec.grouped:
        if len(shapes) not in (4, 5):
            raise ValueError(
                "a grouped spec plans with (m, k, n, E[, dense_rows]) "
                f"shapes — got {shapes}")
        m, k, n, e = shapes[:4]
        dense_rows = shapes[4] if len(shapes) == 5 else m
        if e < 1:
            raise ValueError(f"grouped spec needs E >= 1 groups, got {e}")
    else:
        if len(shapes) != 3:
            raise ValueError(
                f"a dense spec plans with (m, k, n) shapes — got {shapes}")
        m, k, n = shapes
        e, dense_rows = 0, 0
    key = (spec, m, k, n, e, dense_rows)
    cached = _plan_cache.get(key)
    if cached is not None:
        _plan_hits += 1
        if telemetry.enabled():
            _plan_event(cached, "hit")
        return cached
    _plan_misses += 1
    resolved = _resolve(spec, m, k, n, e, dense_rows)
    _plan_cache[key] = resolved
    if telemetry.enabled():
        _plan_event(resolved, "miss")
    return resolved


def _plan_event(pl: "GemmPlan", cache: str) -> None:
    """One telemetry event per plan() call: the full decision record —
    spec key, chosen strategy/tile, modeled HBM/VMEM bytes, flops,
    roofline verdict, cache hit/miss, and any fallback reason."""
    t = pl.tile
    telemetry.counter(f"gemm.plan_cache.{cache}").add(1)
    tuned = pl.tuned
    t_model_us = pl.traffic.t_model * 1e6
    telemetry.event(
        "gemm.plan", cache=cache, spec=pl.spec.key,
        m=pl.m, k=pl.k, n=pl.n, strategy=t.strategy,
        tile=f"{t.bm}x{t.bk}x{t.bn}", hbm_bytes=pl.hbm_bytes,
        vmem_bytes=pl.vmem_bytes, flops=pl.flops,
        t_model_us=t_model_us, bound=pl.traffic.bound,
        source=pl.source,
        t_measured_us=tuned.t_measured_us if tuned else None,
        measured_vs_model=(tuned.t_measured_us / t_model_us
                           if tuned and t_model_us else None),
        fallback_reason=pl.fallback_reason)


def _problem_for(spec: GemmSpec, m: int, k: int, n: int,
                 n_groups: int = 0) -> GemmProblem:
    """The cost-model problem a spec resolves to at concrete shapes —
    shared by ``plan()``, :func:`solve_topk` and the autotuner."""
    ep = spec.epilogue
    out_dtype = spec.out_dtype or ("int8" if ep.out_quant
                                   else spec.a_dtype)
    acc = "int32" if spec.a_dtype == "int8" else "float32"
    return GemmProblem(m, k, n, spec.a_dtype, out_dtype, acc,
                       spec.b_dtype, ep.key, 2 if spec.gated else 1,
                       n_groups if spec.grouped else 0)


def solve_topk(spec: GemmSpec, shapes: Tuple[int, int, int],
               k: int = 5) -> Tuple:
    """The ranked analytic tile candidates the autotuner sweeps for
    ``spec`` at ``shapes`` — a thin introspection wrapper over
    ``dse.solve`` (:class:`repro.core.dse.TileDesign` rows, best first,
    restricted to the spec's strategy when one is pinned; a restricted
    spec can return fewer than ``k`` rows)."""
    m, kk, n = (int(x) for x in shapes[:3])
    problem = _problem_for(spec, m, kk, n,
                           int(shapes[3]) if len(shapes) > 3 else 0)
    k = max(int(k), 1)
    designs = dse.solve(problem, top=k)
    if spec.strategy is not None:
        designs = [d for d in designs if d.tile.strategy == spec.strategy]
    return tuple(designs[:k])


def _tune_enabled(spec: GemmSpec) -> bool:
    if spec.tune is not None:
        return spec.tune
    from repro.tune import autotune as _autotune
    return _autotune.is_enabled(None)


def _resolve(spec: GemmSpec, m: int, k: int, n: int, n_groups: int = 0,
             dense_rows: int = 0) -> GemmPlan:
    problem = _problem_for(spec, m, k, n, n_groups)
    fallback = None
    tuned = None
    if spec.tile is not None:
        # explicit override: honored verbatim (quantized B included) —
        # but an infeasible tile raises instead of silently re-routing
        tile = _clamp_tile(spec.tile, m, k, n)
        err = _infeasible_reason(tile, problem)
        if err:
            raise ValueError(
                f"explicit tile {tile.strategy} {tile.bm}x{tile.bk}x"
                f"{tile.bn} is infeasible for {problem}: {err}")
    else:
        tile = None
        # grouped specs stay analytic: the tuner's measurement harness
        # builds dense operands and would mis-time the ragged sweep
        if _tune_enabled(spec) and not spec.grouped:
            # measured autotuning: the persistent tuning cache first,
            # then a top-K measured sweep; any degradation (over-budget
            # problem, stale/corrupt cache, measurement failure) falls
            # through to the analytic DSE below — never an exception
            from repro import tune as _tune
            found = _tune.lookup_or_search(spec, (m, k, n), problem)
            if found is not None:
                cand, tuned = found
                cand = _clamp_tile(cand, m, k, n)
                err = _infeasible_reason(cand, problem)
                if err:
                    # e.g. a cache entry measured on a different host
                    fallback = (f"tuned tile {cand.strategy} {cand.bm}x"
                                f"{cand.bk}x{cand.bn} infeasible here "
                                f"({err}); re-resolved analytically")
                    tuned = None
                else:
                    tile = cand
        if tile is None:
            designs = dse.solve(problem)
            chosen = next((d for d in designs
                           if spec.strategy in (None, d.tile.strategy)),
                          None)
            if chosen is None:
                raise ValueError(
                    f"no feasible {spec.strategy!r} tiling for {problem}")
            tile = _clamp_tile(chosen.tile, m, k, n)
            err = _infeasible_reason(tile, problem)
            if err:
                # the DSE winner can only fail the stricter post-clamp
                # tb recheck; fall back to the best 'aie' design
                aie = next((d for d in designs
                            if d.tile.strategy == "aie"), None)
                if aie is None:
                    raise ValueError(
                        f"no feasible tiling for {problem}: {err}")
                fallback = (f"tb tile {tile.bm}x{tile.bk}x{tile.bn} "
                            f"infeasible ({err}); fell back to the "
                            "DSE's aie winner")
                tile = _clamp_tile(aie.tile, m, k, n)
    traffic = estimate(tile, problem, TPU_V5E)
    vmem = vmem_footprint(tile, problem, TPU_V5E)
    return GemmPlan(spec, m, k, n, problem, tile, traffic, vmem,
                    fallback, tuned, n_groups, dense_rows)


# ---------------------------------------------------------------------------
# Pallas launch helpers (pad to tile multiples, dispatch, slice back)
# ---------------------------------------------------------------------------

def _pad2(x, m_to, n_to):
    m, n = x.shape
    if m == m_to and n == n_to:
        return x
    return jnp.pad(x, ((0, m_to - m), (0, n_to - n)))


def _gemm_pallas(a: jax.Array, b: jax.Array, tile: TileConfig,
                 out_dtype, vmem_bytes: int, *,
                 b_scale: Optional[jax.Array] = None,
                 bias: Optional[jax.Array] = None,
                 residual: Optional[jax.Array] = None,
                 out_scale: Optional[jax.Array] = None,
                 activation: Optional[str] = None) -> jax.Array:
    m, k = a.shape
    _, n = b.shape
    tile = _clamp_tile(tile, m, k, n)
    bm, bk, bn = tile.bm, tile.bk, tile.bn
    mp, kp, np_ = round_up(m, bm), round_up(k, bk), round_up(n, bn)
    ap = _pad2(a, mp, kp)
    bp = _pad2(b, kp, np_)
    sp = None
    if b_scale is not None:
        sp = b_scale if np_ == n else jnp.pad(
            b_scale, ((0, 0), (0, np_ - n)), constant_values=1.0)
        sp = sp.astype(jnp.float32)
    biasp = _pad2(bias, 1, np_) if bias is not None else None
    resp = _pad2(residual, mp, np_) if residual is not None else None
    fn = gemm_aie if tile.strategy == "aie" else gemm_tb
    out = fn(ap, bp, tile=tile, out_dtype=out_dtype, b_scale=sp,
             bias=biasp, residual=resp, out_scale=out_scale,
             activation=activation, interpret=_interpret(),
             vmem_bytes=vmem_bytes)
    return out[:m, :n]


def _gated_pallas(a, bg, bu, tile, out_dtype, activation, vmem_bytes,
                  sg=None, su=None) -> jax.Array:
    m, k = a.shape
    _, n = bg.shape
    tile = _clamp_tile(tile, m, k, n)
    bm, bk, bn = tile.bm, tile.bk, tile.bn
    mp, kp, np_ = round_up(m, bm), round_up(k, bk), round_up(n, bn)
    ap = _pad2(a, mp, kp)
    bgp, bup = _pad2(bg, kp, np_), _pad2(bu, kp, np_)
    if sg is not None and np_ != n:
        pad = ((0, 0), (0, np_ - n))
        sg = jnp.pad(sg, pad, constant_values=1.0)
        su = jnp.pad(su, pad, constant_values=1.0)
    out = _gemm_gated_kernel(ap, bgp, bup, tile=tile,
                             activation=activation, out_dtype=out_dtype,
                             bg_scale=sg, bu_scale=su,
                             interpret=_interpret(),
                             vmem_bytes=vmem_bytes)
    return out[:m, :n]


def _dispatch_grouped(pl: GemmPlan, a, b, b_scale, group_sizes, bias
                      ) -> jax.Array:
    """The grouped-family pallas/reference fan-out: pad to the plan's
    tile, launch the ragged sweep (or the XLA gather oracle), slice
    back.  ``bias`` is (E, n) per-expert; padding rows of A belong to no
    group, padded k/n columns are zeros (scale pads with 1.0), so the
    sliced-back result is exact."""
    spec = pl.spec
    act = spec.epilogue.activation
    out_dtype = jnp.dtype(pl.problem.out_dtype)
    sizes = group_sizes.astype(jnp.int32)
    e = b.shape[0]
    bias3 = bias.reshape((e, 1, bias.shape[-1])) if bias is not None \
        else None
    if use_pallas():
        def local(a, b, sp, sizes, bias_p):
            t = _clamp_tile(pl.tile, *a.shape, b.shape[2])
            m, k = a.shape
            _, _, n = b.shape
            mp, kp, np_ = round_up(m, t.bm), round_up(k, t.bk), \
                round_up(n, t.bn)
            ap = _pad2(a, mp, kp)
            bp = b if (kp, np_) == (k, n) else jnp.pad(
                b, ((0, 0), (0, kp - k), (0, np_ - n)))
            if sp is not None:
                sp = sp if np_ == n else jnp.pad(
                    sp, ((0, 0), (0, 0), (0, np_ - n)),
                    constant_values=1.0)
                sp = sp.astype(jnp.float32)
            if bias_p is not None and np_ != n:
                bias_p = jnp.pad(bias_p, ((0, 0), (0, 0), (0, np_ - n)))
            out = _gemm_grouped_kernel(ap, bp, sizes, tile=t,
                                       out_dtype=out_dtype, b_scale=sp,
                                       bias=bias_p, activation=act,
                                       interpret=_interpret(),
                                       vmem_bytes=pl.vmem_bytes)
            return out[:m, :n]
        # rows are ordered by group, so only the columns split
        bank = (None, None, "model")
        return shd.per_device(local, (a, b, b_scale, sizes, bias3),
                              ((None, None), bank, bank, (None,), bank),
                              (None, "model"))
    return _ref.gemm_grouped_ref(a, b, sizes, b_scale=b_scale,
                                 bias=bias3, activation=act,
                                 out_dtype=out_dtype)


def _dispatch(pl: GemmPlan, a, b, b_scale, b2, b2_scale, bias, residual,
              out_scale) -> jax.Array:
    """The one pallas/reference fan-out every GEMM shares, driven by the
    plan: the tile was resolved and feasibility-checked at plan time, so
    this only pads, launches and slices (or runs the jnp oracle)."""
    spec = pl.spec
    act = spec.epilogue.activation
    out_dtype = jnp.dtype(pl.problem.out_dtype)
    if use_pallas():
        # per device: rows split over the batch axes, columns over
        # 'model'; the contraction stays whole
        rows, cols, out = ("batch", None), (None, "model"), \
            ("batch", "model")
        if spec.gated:
            return shd.per_device(
                lambda a, bg, bu, sg, su: _gated_pallas(
                    a, bg, bu, pl.tile, out_dtype, act, pl.vmem_bytes,
                    sg=sg, su=su),
                (a, b, b2, b_scale, b2_scale),
                (rows, cols, cols, cols, cols), out)
        return shd.per_device(
            lambda a, b, s, bias, res, osc: _gemm_pallas(
                a, b, pl.tile, out_dtype, pl.vmem_bytes, b_scale=s,
                bias=bias, residual=res, out_scale=osc, activation=act),
            (a, b, b_scale, bias, residual, out_scale),
            (rows, cols, cols, cols, out, (None, None)), out)
    if spec.gated:
        return _ref.gemm_gated_ref(a, b, b2, activation=act,
                                   bg_scale=b_scale, bu_scale=b2_scale,
                                   out_dtype=out_dtype)
    if bias is None and act is None and residual is None \
            and out_scale is None:
        if b_scale is not None:
            return _ref.gemm_fused_ref(a, b, b_scale,
                                       out_dtype=out_dtype)
        return _ref.gemm_ref(a, b, out_dtype=out_dtype)
    return _ref.gemm_epilogue_ref(a, b, b_scale=b_scale, bias=bias,
                                  activation=act, residual=residual,
                                  out_scale=out_scale,
                                  out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# The ONE custom VJP of the GEMM family
# ---------------------------------------------------------------------------

def _float0(x):
    return np.zeros(x.shape, jax.dtypes.float0)


def _act_bwd(activation: Optional[str], z: jax.Array, g: jax.Array
             ) -> jax.Array:
    """dL/dz given dL/d(act(z)) — the unfused-composition backward."""
    if activation is None:
        return g
    _, vjp = jax.vjp(ACTIVATIONS[activation], z)
    return vjp(g)[0]


def _plain(a: jax.Array, b: jax.Array, b_scale, out_dtype,
           strategy: Optional[str] = None) -> jax.Array:
    """A planned plain GEMM (no epilogue) — the recompute primitive the
    generic backward is composed from.  Backward GEMMs pin
    ``tune=False``: the autotuner measures forward plans only, and a
    measurement pass must never trigger nested searches from its own
    recompute GEMMs."""
    spec = GemmSpec(a_dtype=a.dtype, b_dtype=b.dtype,
                    b_quant=b_scale is not None, out_dtype=out_dtype,
                    strategy=strategy, tune=False)
    pl = plan(spec, (a.shape[0], a.shape[1], b.shape[1]))
    return _gemm_core(pl, a, b, b_scale, None, None, None, None)


def _bwd_weight(q: jax.Array, b_scale, dtype) -> jax.Array:
    """The ONLY place a quantized weight is dequantized — backward-pass
    rematerialization; the forward never pays 2-byte weight traffic."""
    if b_scale is None:
        return q
    return (q.astype(jnp.float32) * b_scale).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gemm_core(pl: GemmPlan, a, b, b_scale, b2, b2_scale, bias,
               residual) -> jax.Array:
    """epilogue(A @ B) (or the gated dual-B form), forward and backward
    both driven by the plan.  Absent operands are None; quantized
    weights arrive as (int8 q, fp32 per-output-channel scale)."""
    return _dispatch(pl, a, b, b_scale, b2, b2_scale, bias, residual,
                     None)


def _gemm_core_fwd(pl, a, b, b_scale, b2, b2_scale, bias, residual):
    out = _gemm_core(pl, a, b, b_scale, b2, b2_scale, bias, residual)
    return out, (a, b, b_scale, b2, b2_scale, bias, residual)


def _gemm_core_bwd(pl, res, g):
    # Unfused-composition backward: recompute the pre-activation z (one
    # extra GEMM — rematerialization, not HBM round-trips), then the
    # standard cotangents through the elementwise epilogue.  Quantized
    # weights are serving artifacts: int8 q gets a float0 cotangent and
    # the scale a zero — they are dequantized only here, never forward.
    a, b, b_scale, b2, b2_scale, bias, residual = res
    spec = pl.spec
    act = spec.epilogue.activation
    strat = spec.strategy
    gf = g.astype(jnp.float32)
    dres = gf.astype(residual.dtype) if residual is not None else None

    if spec.gated:
        if b_scale is not None and a.dtype == jnp.int8:
            return (_float0(a), _float0(b), jnp.zeros_like(b_scale),
                    _float0(b2), jnp.zeros_like(b2_scale), None, None)
        zg = _plain(a, b, b_scale, jnp.float32)
        zu = _plain(a, b2, b2_scale, jnp.float32)
        dzu = gf * ACTIVATIONS[act](zg)
        dzg = _act_bwd(act, zg, gf * zu)
        wg = _bwd_weight(b, b_scale, a.dtype)
        wu = _bwd_weight(b2, b2_scale, a.dtype)
        da = (_plain(dzg.astype(a.dtype), wg.T, None, a.dtype)
              + _plain(dzu.astype(a.dtype), wu.T, None, a.dtype)
              ).astype(a.dtype)
        if b_scale is not None:
            return (da, _float0(b), jnp.zeros_like(b_scale), _float0(b2),
                    jnp.zeros_like(b2_scale), None, None)
        dbg = _plain(a.T, dzg.astype(a.dtype), None, b.dtype
                     ).astype(b.dtype)
        dbu = _plain(a.T, dzu.astype(a.dtype), None, b2.dtype
                     ).astype(b2.dtype)
        return da, dbg, None, dbu, None, None, None

    if act is not None:
        z = _plain(a, b, b_scale, jnp.float32, strat)
        if bias is not None:
            z = z + bias.astype(jnp.float32)
        dz = _act_bwd(act, z, gf)
    else:
        dz = gf
    dbias = jnp.sum(dz, axis=0, keepdims=True).astype(bias.dtype) \
        if bias is not None else None
    if a.dtype == jnp.int8:
        da = _float0(a)
    else:
        w = _bwd_weight(b, b_scale, a.dtype)
        da = _plain(dz.astype(a.dtype), w.T, None, a.dtype,
                    strat).astype(a.dtype)
    if b_scale is not None:
        db, dbs = _float0(b), jnp.zeros_like(b_scale)
    elif b.dtype == jnp.int8:
        db, dbs = _float0(b), None
    else:
        db = _plain(a.T, dz.astype(a.dtype), None, b.dtype,
                    strat).astype(b.dtype)
        dbs = None
    return da, db, dbs, None, None, dbias, dres


_gemm_core.defvjp(_gemm_core_fwd, _gemm_core_bwd)


# ---------------------------------------------------------------------------
# The grouped family's generic VJP (backward = grouped GEMMs with the
# transposed expert bank steered by the SAME group tables)
# ---------------------------------------------------------------------------

def _group_rows(sizes: jax.Array, m: int):
    """Per-row group id (clamped) and liveness under ``sizes`` — the
    backward's reconstruction of the forward's steering tables."""
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    rows = jnp.arange(m, dtype=jnp.int32)
    gid = jnp.searchsorted(ends, rows, side="right").astype(jnp.int32)
    live = rows < ends[-1]
    return jnp.minimum(gid, sizes.shape[0] - 1), live


def _grouped_plain(a, b, b_scale, sizes, out_dtype) -> jax.Array:
    """A planned plain grouped GEMM — the recompute/backward primitive
    (``tune=False`` like ``_plain``; dense_rows defaults to m, so
    internal plans claim no padding savings)."""
    spec = GemmSpec(a_dtype=a.dtype, b_dtype=b.dtype,
                    b_quant=b_scale is not None, grouped=True,
                    out_dtype=out_dtype, tune=False)
    pl = plan(spec, (a.shape[0], a.shape[1], b.shape[2], b.shape[0]))
    return _grouped_core(pl, a, b, b_scale, sizes, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped_core(pl: GemmPlan, a, b, b_scale, group_sizes, bias
                  ) -> jax.Array:
    """epilogue(A[r] @ B[g(r)]) over the ragged groups, forward and
    backward driven by the plan.  ``group_sizes`` is a data operand
    (int32 — its cotangent is float0)."""
    return _dispatch_grouped(pl, a, b, b_scale, group_sizes, bias)


def _grouped_core_fwd(pl, a, b, b_scale, group_sizes, bias):
    out = _grouped_core(pl, a, b, b_scale, group_sizes, bias)
    return out, (a, b, b_scale, group_sizes, bias)


def _grouped_core_bwd(pl, res, g):
    # dA rows see only their own expert's panel, so dA is itself a
    # grouped GEMM against the transposed bank with the same group
    # tables; dB is the per-expert segment outer product (one-hot
    # einsum — training-path cost, never paid when serving quantized
    # banks: int8 q gets float0 like the dense family).
    a, b, b_scale, sizes, bias = res
    spec = pl.spec
    act = spec.epilogue.activation
    e = b.shape[0]
    gid, live = _group_rows(sizes, a.shape[0])
    gf = jnp.where(live[:, None], g.astype(jnp.float32), 0.0)
    if act is not None:
        z = _grouped_plain(a, b, b_scale, sizes, jnp.float32)
        if bias is not None:
            z = z + bias[gid].astype(jnp.float32)
        dz = _act_bwd(act, z, gf)
        dz = jnp.where(live[:, None], dz, 0.0)
    else:
        dz = gf
    dbias = None
    if bias is not None:
        dbias = jax.ops.segment_sum(dz, gid, num_segments=e
                                    ).astype(bias.dtype)
    if a.dtype == jnp.int8:
        da = _float0(a)
    else:
        w = b if b_scale is None else \
            (b.astype(jnp.float32) * b_scale).astype(a.dtype)
        da = _grouped_plain(dz.astype(a.dtype), w.swapaxes(1, 2), None,
                            sizes, a.dtype).astype(a.dtype)
    if b_scale is not None:
        db, dbs = _float0(b), jnp.zeros_like(b_scale)
    elif b.dtype == jnp.int8:
        db, dbs = _float0(b), None
    else:
        onehot = (jnp.where(live, gid, e)[:, None]
                  == jnp.arange(e)[None, :]).astype(jnp.float32)
        db = jnp.einsum("re,rk,rn->ekn", onehot,
                        a.astype(jnp.float32), dz).astype(b.dtype)
        dbs = None
    return da, db, dbs, _float0(sizes), dbias


_grouped_core.defvjp(_grouped_core_fwd, _grouped_core_bwd)


# ---------------------------------------------------------------------------
# execute + the one-shot gemm
# ---------------------------------------------------------------------------

def _execute_event(pl: GemmPlan) -> None:
    if not telemetry.enabled():
        return
    spec = pl.spec
    ek = (spec, pl.m, pl.k, pl.n)
    if ek in _executed:
        return
    # first trace of this plan only: jitted callers re-enter execute()
    # once per compilation, eager callers every call — the dedup keeps
    # the event stream one record per plan
    _executed.add(ek)
    telemetry.event(
        "gemm.execute", spec=spec.key, m=pl.m, k=pl.k, n=pl.n,
        strategy=pl.tile.strategy, mode=_mode(),
        hbm_bytes=pl.hbm_bytes, flops=pl.flops)
    telemetry.counter("gemm.execute.first_traces").add(1)


def execute(pl: GemmPlan, a: jax.Array, b, *, b2=None,
            bias: Optional[jax.Array] = None,
            residual: Optional[jax.Array] = None,
            out_scale=None, group_sizes=None) -> jax.Array:
    """Run a resolved plan on concrete operands.

    ``a``: (..., k) — leading dims flatten into the planned M.  ``b`` /
    ``b2``: (k, n) arrays, or ``{"q", "scale"}`` structs when the spec
    says ``b_quant``.  Epilogue operands must match the spec (a plan for
    a bias epilogue requires ``bias=``, and vice versa) — mismatches
    raise rather than silently computing something else.

    A grouped plan requires ``group_sizes=`` (an (E,) integer vector)
    and takes ``b`` as the (E, k, n) expert bank (quantized: q (E, k, n)
    with scale (E, 1, n)); ``bias`` is then per-expert (E, n).  Rows of
    ``a`` must be group-sorted; rows at and beyond ``sum(group_sizes)``
    come back zero.  The W8A8 activation-quant re-route below is dense
    family only — a quantized grouped bank always runs W8A16.

    Under ``quant.activation_mode() == "w8a8"`` a quantized-weight,
    linear-epilogue plan re-routes through dynamic per-row int8
    activation quantization (int8 x int8 kernel, int32 accumulation,
    scales applied outside — forward-only), exactly like the
    pre-redesign dispatch.
    """
    spec = pl.spec
    ep = spec.epilogue
    if spec.gated != (b2 is not None):
        raise ValueError(f"plan {'expects' if spec.gated else 'forbids'} "
                         "a second gated B operand `b2`")
    if spec.grouped != (group_sizes is not None):
        raise ValueError(
            f"plan {'requires' if spec.grouped else 'forbids'} "
            "`group_sizes=`")
    for name, want, got in (("bias", ep.bias, bias is not None),
                            ("residual", ep.residual,
                             residual is not None),
                            ("out_scale", ep.out_quant,
                             out_scale is not None)):
        if want != got:
            raise ValueError(
                f"plan epilogue {ep.key or '(none)'!r} "
                f"{'requires' if want else 'forbids'} `{name}=`")
    if spec.b_quant != _is_quant(b):
        raise ValueError(
            "plan expects B as a {'q','scale'} struct" if spec.b_quant
            else "plan expects a plain B array, got a quant struct")
    b_scale = b2_scale = None
    if spec.b_quant:
        b, b_scale = b["q"], b["scale"]
        if spec.gated:
            b2, b2_scale = b2["q"], b2["scale"]
    lead = a.shape[:-1]
    a2 = a.reshape((-1, a.shape[-1]))
    if spec.grouped:
        e = pl.n_groups
        if b.ndim != 3 or b.shape != (e, pl.k, pl.n):
            raise ValueError(
                f"grouped plan expects the ({e}, {pl.k}, {pl.n}) expert "
                f"bank, got B {b.shape}")
        if b_scale is not None and b_scale.shape != (e, 1, pl.n):
            raise ValueError(
                f"grouped quant scale must be ({e}, 1, {pl.n}), got "
                f"{b_scale.shape}")
        if a2.shape != (pl.m, pl.k):
            raise ValueError(
                f"operands {a.shape} @ {b.shape} do not match the "
                f"plan's {pl.m}x{pl.k}x{pl.n}")
        gs = jnp.asarray(group_sizes)
        if gs.shape != (e,) or not jnp.issubdtype(gs.dtype, jnp.integer):
            raise ValueError(
                f"group_sizes must be an ({e},) integer vector, got "
                f"{gs.shape} {gs.dtype}")
        if _dtname(a2.dtype) != spec.a_dtype \
                or _dtname(b.dtype) != spec.b_dtype:
            raise ValueError(
                f"operand dtypes ({_dtname(a2.dtype)}, {_dtname(b.dtype)})"
                f" do not match the spec ({spec.a_dtype}, {spec.b_dtype})")
        bias_g = None
        if bias is not None:
            bias_g = bias.reshape((e, -1))
            if bias_g.shape != (e, pl.n):
                raise ValueError(
                    f"grouped bias must be per-expert ({e}, {pl.n}), "
                    f"got {bias.shape}")
        _execute_event(pl)
        out = _grouped_core(pl, a2, b, b_scale, gs.astype(jnp.int32),
                            bias_g)
        return out.reshape(lead + (pl.n,)).astype(
            jnp.dtype(pl.problem.out_dtype))
    if a2.shape != (pl.m, pl.k) or b.shape != (pl.k, pl.n):
        raise ValueError(
            f"operands {a.shape} @ {b.shape} do not match the plan's "
            f"{pl.m}x{pl.k}x{pl.n}")
    if b2 is not None and b2.shape != (pl.k, pl.n):
        raise ValueError(
            f"gated operand b2 {b2.shape} does not match the plan's "
            f"({pl.k}, {pl.n}) — it would be silently zero-padded")
    if _dtname(a2.dtype) != spec.a_dtype \
            or _dtname(b.dtype) != spec.b_dtype:
        raise ValueError(
            f"operand dtypes ({_dtname(a2.dtype)}, {_dtname(b.dtype)}) "
            f"do not match the spec ({spec.a_dtype}, {spec.b_dtype})")
    _execute_event(pl)
    n = pl.n
    out_dtype = jnp.dtype(pl.problem.out_dtype)
    bias2 = bias.reshape((1, n)) if bias is not None else None
    res2 = residual.reshape((-1, n)) if residual is not None else None
    if res2 is not None and res2.shape[0] != pl.m:
        raise ValueError(
            f"residual {residual.shape} does not match the plan's "
            f"({pl.m}, {n}) output")

    if (spec.b_quant and not spec.gated and ep.activation is None
            and not ep.out_quant
            and _quant.activation_mode() == "w8a8"
            and a2.dtype != jnp.int8):
        # W8A8 + linear epilogue: keep the int8 x int8 / int32 MXU path;
        # the per-row activation scale commutes with bias/residual, so
        # they apply to the scaled fp32 output outside the kernel.
        a_q, a_s = _quant.quantize_activations(
            jax.lax.stop_gradient(a2), axis=-1)
        sub = dataclasses.replace(spec, a_dtype="int8",
                                  epilogue=Epilogue(),
                                  out_dtype="float32", tune=False)
        acc = _gemm_core(plan(sub, (pl.m, pl.k, pl.n)), a_q, b, b_scale,
                         None, None, None, None)
        out = acc * a_s
        if bias2 is not None:
            out = out + bias2.astype(jnp.float32)
        if res2 is not None:
            out = out + res2.astype(jnp.float32)
        return out.astype(out_dtype).reshape(lead + (n,))

    if out_scale is not None:
        # quantized output is a forward-only serving feature (no VJP
        # through the rounding) — dispatch without the VJP wrapper
        osc = jnp.asarray(out_scale, jnp.float32).reshape((1, 1))
        out = _dispatch(pl, a2, b, b_scale, b2, b2_scale, bias2, res2,
                        osc)
        return out.reshape(lead + (n,))
    out = _gemm_core(pl, a2, b, b_scale, b2, b2_scale, bias2, res2)
    return out.reshape(lead + (n,)).astype(out_dtype)


def gemm(a: jax.Array, b, *, b2=None, bias: Optional[jax.Array] = None,
         activation: Optional[str] = None,
         residual: Optional[jax.Array] = None, out_scale=None,
         strategy: Optional[str] = None,
         tile: Optional[TileConfig] = None, out_dtype=None,
         tune: Optional[bool] = None) -> jax.Array:
    """The one-shot planned GEMM: ``spec -> plan -> execute`` in a
    single call.

    * ``gemm(a, b)`` — C = A @ B (``b`` may be a ``{"q", "scale"}``
      int8 weight struct: fused W8A16/W8A8 serving path).
    * ``gemm(a, b, bias=..., activation="gelu", residual=...)`` —
      epilogue fused into the kernel flush.
    * ``gemm(a, b_gate, b2=b_up, activation="silu")`` — the dual-B
      gated SwiGLU core in one kernel call.

    Every call resolves (once, cached) a :class:`GemmPlan`; build the
    spec yourself via :class:`GemmSpec` + :func:`plan` when you want to
    inspect ``plan.explain()`` or amortize the spec construction.
    """
    spec = GemmSpec.for_operands(a, b, b2, bias=bias,
                                 activation=activation, residual=residual,
                                 out_scale=out_scale, strategy=strategy,
                                 tile=tile, out_dtype=out_dtype,
                                 tune=tune)
    pl = plan(spec, gemm_shapes(a, b))
    return execute(pl, a, b, b2=b2, bias=bias, residual=residual,
                   out_scale=out_scale)


def gemm_grouped(a: jax.Array, b, group_sizes: jax.Array, *,
                 bias: Optional[jax.Array] = None,
                 activation: Optional[str] = None,
                 tile: Optional[TileConfig] = None, out_dtype=None,
                 dense_rows: Optional[int] = None) -> jax.Array:
    """The one-shot planned grouped ragged GEMM (the MoE expert sweep):
    ``C[r] = epilogue(A[r] @ B[g(r)])`` with ``g(r)`` the expert owning
    row ``r`` under ``group_sizes``.

    ``a``: (..., k) tokens *sorted by expert* (leading dims flatten into
    the true routed row count m); ``b``: (E, k, n) expert bank, or a
    ``{"q", "scale"}`` W8A16 struct with scale (E, 1, n); ``bias``:
    per-expert (E, n).  Rows at and beyond ``sum(group_sizes)`` come
    back zero.  ``dense_rows`` (the E*capacity rows the dense einsum
    would multiply) feeds ``plan.explain()``'s padding-flops line.
    """
    bq = _is_quant(b)
    bank = b["q"] if bq else b
    spec = GemmSpec(
        a_dtype=_dtname(a.dtype),
        b_dtype="int8" if bq else _dtname(bank.dtype),
        b_quant=bq, grouped=True,
        epilogue=Epilogue.from_args(bias, activation, None, None),
        out_dtype=None if out_dtype is None else _dtname(out_dtype),
        tile=tile)
    pl = plan(spec, gemm_grouped_shapes(a, b, dense_rows))
    return execute(pl, a, b, bias=bias, group_sizes=group_sizes)
