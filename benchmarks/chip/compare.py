"""The numbers that decide ``correct``, from a run's readings and the
reference's.

Training (the first ``checked_steps`` steps of the timed step):

* ``grad_gap``: over leaves, the gap between the norms of the first
  clipped gradient (the program's read from AdamW's first moment after
  one step), over the reference's norm of that leaf or of the median
  leaf, whichever is larger;
* ``change_gap``: the same for the norm of each leaf's change after the
  checked steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone).

The largest relative gap of a step's loss is printed beside them and not
compared: no control or fault reads three times its sound readings
(PERF.md gives the readings).

Serving: ``served_gap``, the widest gap by which a served token's
reference logit lies below the reference's best at its position.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

#: leaves whose reference gradient norm is under this share of the
#: median leaf's are left out of ``change_gap``
NOUGHT_GRAD = 1e-3


def _leaf_gap(got: Dict[str, float], want: Dict[str, float],
              keep: Optional[set] = None) -> float:
    keys = sorted(want if keep is None else keep)
    med = float(np.median([want[k] for k in want]))
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in keys)


def train_gaps(got: dict, want: dict) -> Dict[str, float]:
    """got / want: {"losses", "first_grad", "change"} as
    :func:`reference.train_steps` returns them."""
    g = want["first_grad"]
    med = float(np.median(list(g.values())))
    moving = {k for k, v in g.items() if v >= NOUGHT_GRAD * med}
    return {"grad_gap": _leaf_gap(got["first_grad"], g),
            "change_gap": _leaf_gap(got["change"], want["change"], moving)}


def loss_gap(got: dict, want: dict) -> float:
    """The largest relative gap of a step's loss (printed, not compared)."""
    return float(max(abs(a - b) / abs(b)
                     for a, b in zip(got["losses"], want["losses"])))
