"""Roofline share of a train step's GEMMs: the least time of the
projection, MLP and lm-head products the step needs (forward, input
gradient and weight gradient, each bounded by its operations at the
bf16 peak or its bytes at the HBM peak) over the device time of the
Pallas GEMM kernels per traced step.  Every such product runs as a
Pallas GEMM (checked by hand on a trace, PERF.md); recomputation under
remat and in the GEMM backward is in the time but not in the count.
Attention is in neither."""

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(rec):
    import flops
    tr = rec.get("trace")
    if not tr or not rec.get("traced_steps"):
        return None
    fam = tr["family_s"]
    spent = fam["gemm_pallas"] / tr["devices"] / rec["traced_steps"]
    if spent <= 0:
        return None
    p = rec["traffic"]
    chips = rec["config"]["mesh"]["data"] * rec["config"]["mesh"]["model"]
    tokens = p["global_batch"] * p["seq_len"] // chips
    least = flops.roofline_seconds(flops.train_gemms(rec["config"], tokens),
                                   rec["peak"])
    return 100.0 * least / spent
