"""What the two planners share: the kernel dispatch decision and the
plan cache.

:mod:`repro.kernels.api` (GEMMs) and :mod:`repro.kernels.attn_api`
(attention) each resolve a frozen spec at concrete shapes into a plan
once, through the analytic cost model, and keep it in a
:class:`PlanCache` of their own.  Each cache has its own key and its
own telemetry event; the counting, the ``<family>.plan_cache.<hit|miss>``
counters and the once-per-key execute record are the same for both and
live here.

Dispatch: ``REPRO_KERNELS=pallas|interpret|ref`` forces a mode;
otherwise Pallas kernels run on a TPU backend and the pure-jnp
references everywhere else.  Plans are computed the same way in every
mode, so the cost model stays introspectable on hosts with no TPU.
"""

from __future__ import annotations

import os
from typing import Callable, Hashable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry


def dispatch_mode() -> str:
    """``'pallas'``, ``'interpret'`` or ``'ref'``: the kernels a call
    traced now runs."""
    env = os.environ.get("REPRO_KERNELS", "auto")
    if env in ("interpret", "ref", "pallas"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def use_pallas() -> bool:
    return dispatch_mode() in ("pallas", "interpret")


def interpret() -> bool:
    return dispatch_mode() == "interpret"


def dtname(dt) -> str:
    return jnp.dtype(dt).name


def float0(x):
    """The cotangent of an integer operand."""
    return np.zeros(x.shape, jax.dtypes.float0)


class PlanCacheInfo(NamedTuple):
    entries: int
    hits: int
    misses: int


class PlanCache:
    """Resolved plans by key, in insertion order, with hit and miss
    counts.  ``family`` names the telemetry counters; ``on_plan(plan,
    'hit'|'miss')`` records the planner's own event for every lookup
    while telemetry is on."""

    def __init__(self, family: str,
                 on_plan: Callable[[object, str], None]):
        self._family = family
        self._on_plan = on_plan
        self._plans: dict = {}
        self._executed: set = set()
        self._hits = 0
        self._misses = 0

    def get_or_resolve(self, key: Hashable, resolve: Callable[[], object]):
        """The plan cached under ``key``, or ``resolve()``'s, cached."""
        pl = self._plans.get(key)
        if pl is not None:
            self._hits += 1
            cache = "hit"
        else:
            self._misses += 1
            pl = self._plans[key] = resolve()
            cache = "miss"
        if telemetry.enabled():
            telemetry.counter(f"{self._family}.plan_cache.{cache}").add(1)
            self._on_plan(pl, cache)
        return pl

    def first_execute(self, key: Hashable) -> bool:
        """True the first time ``key`` executes since the last
        :meth:`clear`: jitted callers re-enter execute once per trace,
        eager callers every call, and the execute event is recorded
        once per plan."""
        if key in self._executed:
            return False
        self._executed.add(key)
        return True

    def info(self) -> PlanCacheInfo:
        """(entries, hits, misses): a workload of repeated shapes
        resolves each unique key once."""
        return PlanCacheInfo(len(self._plans), self._hits, self._misses)

    def clear(self) -> None:
        """Drop every plan and the execute records and zero the counts.
        Tests that monkeypatch the cost model or the feasibility checks
        call it, so that plans resolved under other rules do not leak."""
        self._plans.clear()
        self._executed.clear()
        self._hits = 0
        self._misses = 0

    def values(self) -> Tuple:
        """Every plan resolved so far, in insertion order — what
        ``repro-dryrun --explain`` prints."""
        return tuple(self._plans.values())
