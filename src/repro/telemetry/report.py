"""Model-vs-measured reporting: join every planned GEMM's modeled cost
(HBM bytes, roofline time) with a measured standalone execution.

The DSE is purely analytic; this module is the measurement half the
ROADMAP's autotuning item needs.  For each :class:`~repro.kernels.api.
GemmPlan` in the plan cache (populated by lowering a model, running a
benchmark, or serving a trace), it synthesizes operands matching the
spec, executes the plan through the public ``execute`` path (jitted,
``block_until_ready``), and reports per spec+shape:

* the *modeled* side — HBM bytes, flops, roofline-predicted time and
  whether the model calls it compute- or memory-bound;
* the *measured* side — **median** wall-clock over ``iters``
  device-synced runs after ``warmup`` warm-up calls (compile excluded)
  with MAD outlier rejection, plus the surviving ``spread``
  ((max-min)/median) so a noisy host is visible in the table instead of
  silently folded into a mean — the shared :mod:`repro.tune.measure`
  harness the autotuner uses;
* ``achieved`` — modeled-time / measured-time, the fraction of the
  roofline the execution actually reached.

Honesty note: the roofline is a TPU-v5e model.  On a CPU host (the
``ref``/``interpret`` dispatch modes) the measured numbers are XLA-CPU
or interpreter wall-clock, so ``achieved`` is only meaningful for
*relative* comparisons between specs/tiles on the same host — the
absolute fraction says nothing about TPU behavior.  Each row records the
dispatch mode so downstream consumers can tell.

Plans whose padded flops exceed ``max_flops`` are not silently dropped:
they appear as rows with ``note='skipped (flops budget)'`` and no
measured time.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from repro import telemetry
from repro.tune import measure as _measure
from repro.tune.measure import (  # noqa: F401  (compat re-exports)
    DEFAULT_MAX_FLOPS,
    Measurement,
    synthesize_operands,
)


def measure_plan(pl, *, iters: int = _measure.DEFAULT_ITERS,
                 warmup: int = _measure.DEFAULT_WARMUP,
                 rng: Optional[np.random.Generator] = None
                 ) -> Measurement:
    """Measure one plan with the shared :mod:`repro.tune.measure`
    harness (jit + explicit warm-up, ``iters`` device-synced samples,
    MAD outlier rejection).  Returns the full :class:`Measurement`;
    use ``.median_s`` for the headline number."""
    return _measure.measure_plan(pl, iters=iters, warmup=warmup, rng=rng)


def model_vs_measured(plans: Optional[Sequence] = None, *,
                      max_flops: float = DEFAULT_MAX_FLOPS,
                      iters: int = _measure.DEFAULT_ITERS,
                      warmup: int = _measure.DEFAULT_WARMUP,
                      seed: int = 0) -> List[dict]:
    """One row per plan: the modeled bytes/time next to the measured
    median wall-clock and its spread.  ``plans`` defaults to every plan
    resolved so far (the plan cache in insertion order)."""
    from repro.kernels import api
    if plans is None:
        plans = api.plans()
    rng = np.random.default_rng(seed)
    mode = api._mode()
    rows: List[dict] = []
    for pl in plans:
        t = pl.tile
        row = {
            "spec": pl.spec.key,
            "m": pl.m, "k": pl.k, "n": pl.n,
            "strategy": t.strategy,
            "tile": f"{t.bm}x{t.bk}x{t.bn}",
            "source": pl.source,
            "hbm_mib": round(pl.hbm_bytes / 2**20, 3),
            "flops": pl.flops,
            "bound": pl.traffic.bound,
            "t_model_us": round(pl.traffic.t_model * 1e6, 2),
            "mode": mode,
            "iters": iters,
            "warmup": warmup,
            "t_measured_us": None,
            "spread": None,
            "achieved": None,
            "note": "",
        }
        if pl.flops > max_flops:
            row["note"] = "skipped (flops budget)"
        else:
            meas = measure_plan(pl, iters=iters, warmup=warmup, rng=rng)
            dt = meas.median_s
            row["t_measured_us"] = round(dt * 1e6, 2)
            row["spread"] = round(meas.spread, 4)
            # significant figures: on a slow host the fraction is under
            # 1e-4, where a fixed number of decimals keeps one digit
            row["achieved"] = float(f"{pl.traffic.t_model / dt:.4g}")
            if meas.rejected:
                row["note"] = f"{meas.rejected} outlier(s) rejected"
            telemetry.event("gemm.measured", **{
                k: row[k] for k in ("spec", "m", "k", "n", "strategy",
                                    "tile", "source", "hbm_mib",
                                    "t_model_us", "t_measured_us",
                                    "spread", "achieved", "mode")})
        rows.append(row)
    return rows


def summarize(rows: Sequence[dict]) -> dict:
    measured = [r for r in rows if r["t_measured_us"] is not None]
    skipped = len(rows) - len(measured)
    mean = (float(f"{np.mean([r['achieved'] for r in measured]):.4g}")
            if measured else None)
    return {
        "n_plans": len(rows),
        "n_measured": len(measured),
        "n_skipped": skipped,
        "mean_achieved": mean,
    }


def render(rows: Sequence[dict]) -> str:
    """Aligned text table of a model-vs-measured report."""
    cols = ("spec", "shape", "tile", "src", "hbm_mib", "t_model_us",
            "t_measured_us", "spread", "achieved", "note")
    table = [cols]
    for r in rows:
        table.append((
            r["spec"], f"{r['m']}x{r['k']}x{r['n']}",
            f"{r['strategy']} {r['tile']}",
            r.get("source", "analytic"), f"{r['hbm_mib']:.2f}",
            f"{r['t_model_us']:.1f}",
            "-" if r["t_measured_us"] is None
            else f"{r['t_measured_us']:.1f}",
            "-" if r.get("spread") is None else f"{r['spread'] * 100:.0f}%",
            "-" if r["achieved"] is None else f"{r['achieved']:.3f}",
            r["note"]))
    widths = [max(len(row[i]) for row in table) for i in range(len(cols))]
    lines = ["  ".join(c.ljust(w) for c, w in zip(row, widths)).rstrip()
             for row in table]
    lines.insert(1, "  ".join("-" * w for w in widths))
    s = summarize(rows)
    lines.append(f"[{s['n_measured']}/{s['n_plans']} plans measured, "
                 f"{s['n_skipped']} skipped; mode sees a "
                 f"{rows[0]['mode'] if rows else '?'} dispatch — achieved "
                 "fractions compare hosts, not TPUs]")
    return "\n".join(lines)
