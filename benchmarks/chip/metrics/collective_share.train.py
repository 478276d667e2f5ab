"""Share of a train step's device busy time spent in collectives: the
device time of the all-gather, reduce-scatter, all-reduce,
collective-permute and all-to-all ops (and the ``-start`` / ``-done``
halves of their asynchronous forms), per device and traced step, over
the busy time per device and traced step.  It reads the trace's
``op_s`` (the 40 ops with the most time; every collective op of the
four-chip cell's step is among them, checked by hand on a trace,
PERF.md).  A collective that overlaps compute counts in full."""

import re

LAYER = "sharding"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"

COLLECTIVE = re.compile(r"^(all-gather|reduce-scatter|all-reduce"
                        r"|collective-permute|all-to-all)(-start|-done)?$")


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec.get("traced_steps") or not tr["devices"] \
            or tr["busy_s"] <= 0:
        return None
    spent = sum(s for name, s in tr["op_s"].items()
                if COLLECTIVE.match(name))
    return 100.0 * spent / tr["devices"] / tr["busy_s"]
