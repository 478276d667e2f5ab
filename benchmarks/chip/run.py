#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload smollm-360m.train-2k \
        --seed 7 --seconds 30 --trace 0

The cell, its configuration file and its traffic file are found by
name through ``BENCHMARK.json`` at the root of the checkout.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of part
of the window.  The last line of standard output is one JSON object;
the numbers that decided ``correct`` are the last lines of standard
error.  Without a TPU of a known kind, with fewer chips than the cell
asks for, or with kernel dispatch other than Pallas, it exits non-zero
and prints no result.

``--rate`` overrides a serving mix's arrival rate (the knee sweep) and
``--control 1`` also prints the readings of the lower-precision
control (and, for training, of a step that drops half of its batch);
the benchmark's own runs use neither.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import harness
    return harness.run_cell(bench, args, T_START)


if __name__ == "__main__":
    sys.exit(main())
