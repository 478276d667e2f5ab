"""Share of the traced window in which no operation ran on the device,
during training steps."""

LAYER = "device"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0 or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
