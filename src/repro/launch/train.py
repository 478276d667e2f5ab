"""Production training driver.

Wires every substrate together: config registry -> mesh -> layout engine
shardings -> donated/jitted train step -> deterministic data pipeline ->
async checkpointing -> straggler watchdog -> preemption-safe restart.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 200 --seq-len 512 --global-batch 8 --smoke

On a real cluster each host runs this same driver under its own
process-index (jax.distributed); the mesh builder and the row-sharded
data pipeline are already host-aware, so the single-host path here is
the degenerate case of the multi-pod one.
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import numpy as np

from repro import telemetry
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import get_config, get_smoke_config
from repro.data import pipeline
from repro.dist import layout, sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.runtime import elastic
from repro.runtime.compile_cache import use_compile_cache
from repro.runtime.fault_tolerance import StepWatchdog
from repro.train import train_step as TS


def build(cfg, mesh, *, peak_lr=3e-4, total_steps=1000, microbatches=1,
          seed=0, optimizer: Optional[str] = None):
    """(state, jitted step, shardings) on ``mesh``."""
    step_fn = TS.make_train_step(cfg, peak_lr=peak_lr,
                                 total_steps=total_steps,
                                 microbatches=microbatches,
                                 optimizer=optimizer)
    with shd.use_mesh(mesh):
        state_struct = jax.eval_shape(
            lambda: TS.init_state(jax.random.PRNGKey(seed), cfg,
                                  optimizer))
        state_sh = elastic.state_shardings(state_struct, cfg, mesh)
        init = jax.jit(
            lambda k: TS.init_state(k, cfg, optimizer),
            out_shardings=state_sh)
        state = init(jax.random.PRNGKey(seed))
        jitted = jax.jit(step_fn, donate_argnums=(0,),
                         in_shardings=(state_sh, None),
                         out_shardings=(state_sh, None))
    return state, jitted, state_sh


def train(cfg, *, steps: int, seq_len: int, global_batch: int,
          mesh=None, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 50, log_every: int = 10, seed: int = 0,
          microbatches: int = 1, resume: bool = True,
          watchdog: Optional[StepWatchdog] = None) -> dict:
    """Run (or resume) a training job; returns final metrics."""
    mesh = mesh or make_host_mesh(data=len(jax.devices()))
    state, jitted, state_sh = build(cfg, mesh, total_steps=steps,
                                    microbatches=microbatches, seed=seed)
    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    start = 0
    if ckpt and resume and ckpt.latest_step() is not None:
        state = elastic.remesh_restore(ckpt, state, cfg, mesh)
        start = int(state.step)
        print(f"[train] resumed from step {start}")

    data_cfg = pipeline.DataConfig(seq_len=seq_len,
                                   global_batch=global_batch, seed=seed)
    watchdog = watchdog or StepWatchdog()
    metrics = {}
    with shd.use_mesh(mesh):
        for step in range(start, steps):
            batch = pipeline.make_batch(cfg, data_cfg, step)
            t0 = time.time()
            with telemetry.span("train.step", step=step) as sp:
                state, metrics = jitted(state, batch)
                sp.sync(metrics["loss"])
                jax.block_until_ready(metrics["loss"])
            dt = time.time() - t0
            ev = watchdog.observe(step, dt)
            if ev:
                print(f"[train] straggler: step {ev.step} took "
                      f"{ev.duration:.2f}s (median {ev.median:.2f}s)")
            if step % log_every == 0 or step == steps - 1:
                print(f"[train] step {step} loss "
                      f"{float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"{dt*1e3:.0f}ms")
            if ckpt and (step + 1) % ckpt_every == 0:
                ckpt.save(step + 1, state, blocking=False)
    if ckpt:
        ckpt.save(steps, state, blocking=True)
    return {k: float(v) for k, v in metrics.items()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="record per-step spans + GEMM plan events and "
                         "write PATH.jsonl + PATH.trace.json")
    args = ap.parse_args()
    print(f"[train] compile cache: {use_compile_cache()}")
    if args.telemetry:
        telemetry.enable()
    cfg = get_smoke_config(args.arch) if args.smoke \
        else get_config(args.arch)
    out = train(cfg, steps=args.steps, seq_len=args.seq_len,
                global_batch=args.global_batch,
                microbatches=args.microbatches,
                ckpt_dir=args.ckpt_dir, seed=args.seed)
    print("[train] final:", {k: round(v, 4) for k, v in out.items()})
    if args.telemetry:
        snap = telemetry.snapshot()
        paths = telemetry.export(args.telemetry)
        print(f"[train] telemetry: {snap['n_events']} events, "
              f"plan cache {snap['plan_cache']}; wrote "
              f"{paths[0]} and {paths[1]}")
        routed = snap["counters"].get("moe.group_sizes")
        if routed is not None:
            dropped = snap["counters"].get("moe.dropped_tokens", 0)
            total = routed + dropped
            print(f"[train] moe: {int(routed)} rows through grouped "
                  f"expert GEMMs, {int(dropped)} capacity-dropped "
                  f"({dropped / max(total, 1):.1%} of assignments)")


if __name__ == "__main__":
    main()
