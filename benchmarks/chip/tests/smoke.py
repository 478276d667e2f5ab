"""Drive a benchmark cell on the CPU at the program's smoke size.

The device check is skipped; everything else of a run happens: weights
from the seed, the warm-up, the window through the program's own entry
point, the reference and the decision on ``correct``.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
for p in (str(ROOT / "src"), str(CHIP)):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


#: the real cell each smoke cell stands in for, whose metrics it reports
STANDS_FOR = {"train": "smollm-360m.train-2k",
              "chat": "smollm-360m.serve-chat"}


def cell(kind: str, limits: dict) -> dict:
    """A smoke cell: ``kind`` is ``train`` or ``chat``."""
    traffic = json.loads((HERE / "data" / f"{kind}-smoke.json").read_text())
    config = json.loads(
        (HERE / "data" / "smollm-360m-smoke.json").read_text())
    return {"cell": {"name": f"smoke.{kind}", "config": config["name"],
                     "traffic": f"{kind}-smoke", "chips": 1},
            "config": config, "traffic": traffic,
            "limits": {"checks": limits}, "stands_for": STANDS_FOR[kind]}


def bench_for(found: dict) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = found["cell"]["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if found["stands_for"] in m.get("workloads", ()):
            m["workloads"] = list(m["workloads"]) + [name]
    return bench


def run(found: dict, *, seed: int = 5, seconds: float = 1.0,
        trace: int = 0, control: int = 0) -> dict:
    """The parsed result line of one smoke run."""
    args = argparse.Namespace(workload=found["cell"]["name"], seed=seed,
                              seconds=seconds, trace=trace, rate=None,
                              control=control)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.run_resolved(bench_for(found), found, args,
                                  time.perf_counter(), device=DEVICE)
    assert rc == 0, rc
    return json.loads(out.getvalue().strip().splitlines()[-1])
