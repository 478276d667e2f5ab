"""Mean time of one batched decode step: the engine's decode_time (host
clock around each burst, which ends in a sync) over its decode_steps."""

LAYER = "model step"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "program_span"


def read(rec):
    e = rec["engine"]
    if not e["decode_steps"]:
        return None
    return 1e3 * e["decode_time"] / e["decode_steps"]
