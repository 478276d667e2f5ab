"""Model FLOP/s utilization of training: operations per token (forward
and backward, recomputation not counted) times tokens per second, over
the chips' bf16 peak.  The rate is taken over the window's steps that
ran with the profiler off (host clock, each step ending in a sync)."""

LAYER = "model step"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(rec):
    import flops
    per_token = flops.train_flops_per_token(rec["config"],
                                            rec["traffic"]["seq_len"])
    chips = rec["config"]["mesh"]["data"] * rec["config"]["mesh"]["model"]
    steps = rec["untraced_step_s"]
    if not steps:
        return None
    p = rec["traffic"]
    rate = len(steps) * p["global_batch"] * p["seq_len"] / sum(steps)
    return 100.0 * per_token * rate / (chips * rec["peak"]["bf16_flops"])
