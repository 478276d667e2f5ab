"""What the drivers share: the program's configuration, checked
against the configuration file's sizes, and the traced window's
reduction."""

from __future__ import annotations

import json
import shutil


def _check_sizes(cfg, c: dict) -> None:
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.hd, cfg.d_ff, cfg.vocab, cfg.window, cfg.rope_theta,
           cfg.norm_eps, cfg.dtype)
    want = (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["intermediate_size"], c["vocab_size"],
            c["sliding_window"], c["rope_theta"], c["rms_norm_eps"],
            c["dtype"])
    if got != want:
        raise RuntimeError(f"program config {cfg.name} is {got}, the "
                           f"configuration file states {want}")


def program_config(c: dict):
    from repro.configs.base import get_config, get_smoke_config
    name = c["registry"]
    cfg = get_smoke_config(name[:-len("-smoke")]) \
        if name.endswith("-smoke") else get_config(name)
    _check_sizes(cfg, c)
    return cfg


def reduce_trace(tracer):
    """The reduced trace of a finished tracer (None where it holds no
    device op, as on the CPU); the trace files are removed."""
    from harness import log
    from trace import reduce, load, find_xplane
    try:
        if not tracer.done:
            return None
        out = reduce(load(find_xplane(tracer.log_dir)))
    except ValueError as e:
        log(f"trace: {e}")
        return None
    finally:
        shutil.rmtree(tracer.log_dir, ignore_errors=True)
    log("trace: " + json.dumps({k: out[k] for k in (
        "busy_s", "window_s", "devices", "ops", "family_s", "op_s")}))
    return out
