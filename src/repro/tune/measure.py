"""The shared on-device measurement harness — the *measured* half of
every model-vs-reality loop in the repo.

One plan, one number: synthesize operands matching the plan's spec
(quantized ``{q, scale}`` structs, gated dual-B, bias/residual/out-scale
epilogue terms), run it through the public ``execute`` path under
``jax.jit`` with an explicit warm-up count (compile excluded), then time
``iters`` device-synced repeats and reduce them **robustly**: outliers
are rejected by median-absolute-deviation before the median is taken, and
the surviving spread is reported so noisy hosts are *visible* instead of
silently folded into a mean.

Consumers:

* :mod:`repro.telemetry.report` — the model-vs-measured table
  (``repro-dryrun --measure``) joins each plan's modeled bytes/roofline
  time with a :class:`Measurement`.
* :mod:`repro.tune.autotune` — the top-K tile search times each analytic
  candidate with this harness and picks the measured winner.
* :mod:`repro.tune.calibrate` — every sample the tuner records regresses
  against modeled bytes/flops to fit effective hardware constants.

The ``timer`` parameter exists for determinism tests: injecting a fake
clock makes the winner selection reproducible without real devices.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Callable, Optional, Tuple

import numpy as np

#: per-GEMM flop budget for measured passes — dryrun plan caches contain
#: million-token train GEMMs that would take hours on a CPU host
DEFAULT_MAX_FLOPS = 5e10

#: default repeat / warm-up counts (median-of-5 after 2 warm-up calls)
DEFAULT_ITERS = 5
DEFAULT_WARMUP = 2

#: samples farther than this many (scaled) MADs from the median are
#: rejected before the median is taken — one GC pause or page-fault storm
#: must not decide a tile search
MAD_CUTOFF = 3.0


@dataclasses.dataclass(frozen=True)
class Measurement:
    """Robust wall-clock summary of repeated plan executions."""

    times_s: Tuple[float, ...]      # every post-warm-up sample
    kept_s: Tuple[float, ...]       # samples surviving outlier rejection
    warmup: int                     # warm-up calls excluded from times_s

    @property
    def iters(self) -> int:
        return len(self.times_s)

    @property
    def rejected(self) -> int:
        return len(self.times_s) - len(self.kept_s)

    @property
    def median_s(self) -> float:
        return statistics.median(self.kept_s)

    @property
    def mean_s(self) -> float:
        return statistics.fmean(self.kept_s)

    @property
    def spread(self) -> float:
        """(max - min) / median over the kept samples — the honest
        noise-floor indicator reported next to every measured number."""
        med = self.median_s
        if not med:
            return 0.0
        return (max(self.kept_s) - min(self.kept_s)) / med


def reject_outliers(times: Tuple[float, ...],
                    cutoff: float = MAD_CUTOFF) -> Tuple[float, ...]:
    """Drop samples beyond ``cutoff`` scaled MADs from the median.  At
    least half the samples always survive (a bimodal run keeps its
    faster mode rather than rejecting everything)."""
    if len(times) <= 2:
        return tuple(times)
    med = statistics.median(times)
    mad = statistics.median(abs(t - med) for t in times)
    if mad == 0.0:
        return tuple(times)
    scaled = 1.4826 * mad           # MAD -> sigma under normality
    kept = tuple(t for t in times if abs(t - med) <= cutoff * scaled)
    if len(kept) < max(1, len(times) // 2):
        return tuple(times)
    return kept


def _rand(rng: np.random.Generator, shape, dtype: str):
    import jax.numpy as jnp
    if dtype == "int8":
        return jnp.asarray(
            rng.integers(-127, 128, shape).astype(np.int8))
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                       ).astype(dtype)


def synthesize_operands(pl, rng: np.random.Generator) -> dict:
    """execute() operands matching the plan's spec — quantized weight
    structs, the gated second B, and every epilogue term it declares."""
    spec, ep = pl.spec, pl.spec.epilogue
    m, k, n = pl.m, pl.k, pl.n

    def weight():
        if spec.b_quant:
            return {"q": _rand(rng, (k, n), "int8"),
                    "scale": _rand(rng, (1, n), "float32") * 0.01 + 0.02}
        return _rand(rng, (k, n), spec.b_dtype)

    return {
        "a": _rand(rng, (m, k), spec.a_dtype),
        "b": weight(),
        "b2": weight() if spec.gated else None,
        "bias": _rand(rng, (n,), spec.a_dtype) if ep.bias else None,
        "residual": (_rand(rng, (m, n), spec.a_dtype)
                     if ep.residual else None),
        "out_scale": 0.05 if ep.out_quant else None,
    }


def measure_plan(pl, *, iters: int = DEFAULT_ITERS,
                 warmup: int = DEFAULT_WARMUP,
                 rng: Optional[np.random.Generator] = None,
                 timer: Callable[[], float] = time.perf_counter
                 ) -> Measurement:
    """Time one plan's forward execution: jit once, warm up ``warmup``
    times, then take ``iters`` individually device-synced samples and
    summarize them robustly (median after MAD outlier rejection)."""
    import jax
    from repro.kernels import api
    rng = rng or np.random.default_rng(0)
    ops = synthesize_operands(pl, rng)
    out_scale = ops["out_scale"]

    def f(a, b, b2, bias, residual):
        return api.execute(pl, a, b, b2=b2, bias=bias,
                           residual=residual, out_scale=out_scale)

    jitted = jax.jit(f)
    args = (ops["a"], ops["b"], ops["b2"], ops["bias"], ops["residual"])
    for _ in range(max(1, warmup)):          # compile + warm-up
        jax.block_until_ready(jitted(*args))
    times = []
    for _ in range(max(1, iters)):
        t0 = timer()
        jax.block_until_ready(jitted(*args))
        times.append(timer() - t0)
    return Measurement(times_s=tuple(times),
                       kept_s=reject_outliers(tuple(times)),
                       warmup=max(1, warmup))


def synthesize_attn_operands(pl, rng: np.random.Generator) -> dict:
    """attn_execute() operands matching an :class:`AttnPlan` — dense
    q/k/v at spec dtypes for prefill, a full cache (worst-case ``pos``,
    what the plan bills) for decode, and a pool where each slot owns its
    own pages for paged decode."""
    import jax.numpy as jnp
    spec = pl.spec
    if spec.mode == "prefill":
        return {
            "q": _rand(rng, (pl.b, pl.sq, pl.hq, pl.d), spec.q_dtype),
            "k": _rand(rng, (pl.b, pl.skv, pl.hkv, pl.d), spec.kv_dtype),
            "v": _rand(rng, (pl.b, pl.skv, pl.hkv, pl.d), spec.kv_dtype),
            "pos": None, "page_table": None,
        }
    q = _rand(rng, (pl.b, pl.hq, pl.d), spec.q_dtype)
    pos = jnp.full((pl.b,), pl.skv - 1, jnp.int32)
    if spec.mode == "decode":
        kv = (pl.b, pl.skv, pl.hkv, pl.d)
        return {"q": q, "k": _rand(rng, kv, spec.kv_dtype),
                "v": _rand(rng, kv, spec.kv_dtype),
                "pos": pos, "page_table": None}
    pool = (pl.b * pl.max_pages, pl.page_size, pl.hkv, pl.d)
    table = jnp.arange(pl.b * pl.max_pages, dtype=jnp.int32
                       ).reshape(pl.b, pl.max_pages)
    return {"q": q, "k": _rand(rng, pool, spec.kv_dtype),
            "v": _rand(rng, pool, spec.kv_dtype),
            "pos": pos, "page_table": table}


def measure_attn_plan(pl, *, iters: int = DEFAULT_ITERS,
                      warmup: int = DEFAULT_WARMUP,
                      rng: Optional[np.random.Generator] = None,
                      timer: Callable[[], float] = time.perf_counter
                      ) -> Measurement:
    """The :func:`measure_plan` harness for attention plans — same jit /
    warm-up / device-sync / robust-median contract."""
    import jax
    from repro.kernels import attn_api
    rng = rng or np.random.default_rng(0)
    ops = synthesize_attn_operands(pl, rng)

    def f(q, k, v, pos, page_table):
        return attn_api.attn_execute(pl, q, k, v, pos=pos,
                                     page_table=page_table)

    jitted = jax.jit(f)
    args = (ops["q"], ops["k"], ops["v"], ops["pos"], ops["page_table"])
    for _ in range(max(1, warmup)):          # compile + warm-up
        jax.block_until_ready(jitted(*args))
    times = []
    for _ in range(max(1, iters)):
        t0 = timer()
        jax.block_until_ready(jitted(*args))
        times.append(timer() - t0)
    return Measurement(times_s=tuple(times),
                       kept_s=reject_outliers(tuple(times)),
                       warmup=max(1, warmup))
