"""Training cells: the jitted step of ``repro.launch.train.build``.

Set-up builds the step and its state on the cell's mesh, puts in the
benchmark's weights and a fresh AdamW state at the traffic file's
``start_step``, and drives the first ``checked_steps`` steps through the
same call and feed as the window, reading the losses, the first
gradient (AdamW's first moment over 1 - b1) and the change of every
leaf.  The first call compiles, so set-up ends compiled.  The window
then runs whole steps until ``seconds`` have passed; the last one
finishes in a sync.  After the window the program's state is freed and
the reference trains the same weights on the same batches.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp

import compare
import reference
import weights
from drivers_common import program_config, reduce_trace
from harness import log, memory_peak_bytes, tmp_dir
from trace import Tracer


def run(cell) -> dict:
    from repro.dist import sharding as shd
    from repro.launch import train as TR
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T
    from repro.optim import adamw
    from repro.train import train_step as TS

    c, p, o = cell.config, cell.traffic, cell.traffic["optimizer"]
    cfg = program_config(c)
    gen = cell.generator
    mesh = make_host_mesh(**c.get("mesh", {}))
    state, step_fn, state_sh = TR.build(cfg, mesh, peak_lr=o["peak_lr"],
                                        total_steps=o["total_steps"])
    del state                     # the program's own weights go unused
    struct = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    w0 = weights.make(struct, cell.seed, shardings=state_sh.params)
    state = jax.jit(
        lambda w: TS.TrainState(params=w, opt=adamw.init(w),
                                step=jnp.asarray(o["start_step"], jnp.int32)),
        out_shardings=state_sh)(w0)
    b1 = o["b1"]
    batches = [gen.batch(p, cell.seed, i, c["vocab_size"])
               for i in range(p["checked_steps"])]
    got = {"losses": []}
    with shd.use_mesh(mesh):
        for i, b in enumerate(batches):
            state, m = step_fn(state, b)
            got["losses"].append(float(m["loss"]))
            if i == 0:
                got["first_grad"] = {
                    k: v / (1 - b1) for k, v in
                    reference.leaf_norms(state.opt.mu).items()}
        got["change"] = reference.leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            state.params, w0))

        tokens_per_step = p["global_batch"] * p["seq_len"]
        tracer = Tracer(tmp_dir("bench_trace_")) if cell.trace else None
        trace_from = cell.seconds / 2
        step_i = len(batches)
        nxt = gen.batch(p, cell.seed, step_i, c["vocab_size"])
        compiles0 = cell.compiles.count
        setup_s = time.perf_counter() - cell.t_start
        losses, traced_steps, step_s = [], 0, []
        t0 = time.perf_counter()
        while True:
            if tracer and not tracer.on and not tracer.done \
                    and time.perf_counter() - t0 >= trace_from:
                tracer.start()
            traced = bool(tracer and tracer.on)
            t_step = time.perf_counter()
            state, m = step_fn(state, nxt)
            step_i += 1
            nxt = gen.batch(p, cell.seed, step_i, c["vocab_size"])
            losses.append(float(m["loss"]))
            step_s.append((time.perf_counter() - t_step, traced))
            if traced:
                traced_steps += 1
                if traced_steps >= p["trace_steps"]:
                    tracer.stop()
            if time.perf_counter() - t0 >= cell.seconds \
                    and (not tracer or tracer.done):
                break
        jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    compiles = cell.compiles.count - compiles0
    peak_bytes = memory_peak_bytes()
    del state, m, step_fn
    rec = {"kind": "train", "config": c, "traffic": p, "peak": cell.peak,
           "setup_s": setup_s, "window_s": window_s, "steps": len(losses),
           "tokens": len(losses) * tokens_per_step,
           "memory_peak_bytes": peak_bytes, "compiles_in_window": compiles,
           "attempted": len(losses),
           "failed": sum(not math.isfinite(x) for x in losses),
           "trace": None, "traced_steps": traced_steps,
           "untraced_step_s": [d for d, traced in step_s if not traced]}
    rec["e2e"] = {"train_tokens_per_s": rec["tokens"] / window_s,
                  "setup_s": setup_s}
    if tracer:
        rec["trace"] = reduce_trace(tracer)
    log(f"window: {len(losses)} steps in {window_s:.3f}s, setup "
        f"{setup_s:.3f}s, losses {losses[:2]}..{losses[-1:]}")

    t_ref = time.perf_counter()
    want = reference.train_steps(c, w0, batches, o)
    rec["readings"] = compare.train_gaps(got, want)
    rec["info"] = {"loss_gap": compare.loss_gap(got, want),
                   "program_losses": got["losses"],
                   "reference_losses": want["losses"],
                   "reference_s": time.perf_counter() - t_ref}
    if cell.control:
        low = reference.train_steps(c, w0, batches, o, precision="fp8")
        half = reference.train_steps(
            c, w0, batches, o, rows=range(p["global_batch"] // 2))
        rec["control"] = {
            name: dict(compare.train_gaps(x, want),
                       loss_gap=compare.loss_gap(x, want))
            for name, x in (("fp8", low), ("half_batch", half))}
    return rec
