"""The control comes out not correct against each cell's limits.

The control is the reference in the program's place at float8 (e4m3,
per-tensor scale) on every operand of every product, the step below
the bfloat16 the configurations state; for training the run also reads
a step over half the batch.  At smoke size on the CPU it has to fail at
least one of the cell's limits; on the chip, at the cells' own sizes,
its readings are in PERF.md.
"""

import json
from pathlib import Path

import pytest

import smoke
import harness

LIMITS = Path(__file__).resolve().parents[1] / "limits"


#: no serving cell is in the benchmark yet; the chat smoke run is held
#: to a served-token gap of the order the chip's sound runs read (PERF.md)
SERVE_LIMITS = {"served_gap": 0.1}


def limits(cell):
    if cell == "smollm-360m.serve-chat":
        return SERVE_LIMITS
    return json.loads((LIMITS / f"{cell}.json").read_text())["checks"]


@pytest.mark.parametrize("kind,cell,seconds", [
    ("train", "smollm-360m.train-2k", 0.5),
    ("chat", "smollm-360m.serve-chat", 2.0)])
def test_control_fails_a_limit(kind, cell, seconds, capsys):
    found = smoke.cell(kind, limits(cell))
    out = smoke.run(found, seconds=seconds, control=1)
    assert out["correct"] is True
    err = capsys.readouterr().err
    line = next(x for x in err.splitlines()
                if x.startswith("[bench] control readings: "))
    control = json.loads(line.split(": ", 1)[1])
    ok, checks = harness.decide(control["fp8"], limits(cell))
    assert not ok, checks
