"""Training cells whose state is sharded over a mesh: the train driver
(``drivers/train.py``), with the reference module the configuration
file names (``"reference"``) in place of ``reference.py``.  For
h2o-danube-3-4b's 2x2 cell that is ``reference_sharded.py``, which fits
beside the weights the driver holds where ``reference.py`` does not
(PERF.md)."""

from __future__ import annotations

from pathlib import Path

from harness import load_module

CHIP = Path(__file__).resolve().parents[1]


def run(cell) -> dict:
    train = load_module(CHIP / "drivers" / "train.py")
    train.reference = load_module(CHIP / f"{cell.config['reference']}.py")
    return train.run(cell)
