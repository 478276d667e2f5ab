#!/usr/bin/env python3
"""Smoke run of training and serving on a TPU v5e, at full model width.

    python3 chip_smoke.py             # one chip: train, serve, parity, plans
    python3 chip_smoke.py --chips 4   # 2x2 mesh train step vs one device

One process drives every phase.  With no option it takes a few steps of
the full-width ``smollm-360m`` train step through ``repro.launch.train``,
serves a handful of requests through ``repro.launch.serve.load_params``
and a paged ``DecodeEngine``, compares one prefill's last-token logits
under Pallas dispatch with the XLA reference, and lists every GEMM and
attention plan the run resolved.  ``--chips 4`` runs only the sharded
train step on a 2x2 ``(data, model)`` mesh and its one-device
comparison.  Weights and data are random, made from a seed.

Progress goes to earlier lines; the last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``.  Any failed check raises,
and the script then exits non-zero and prints no such line.  It refuses
to run where JAX finds no TPU.  Times printed here are host-clock
readings for orientation, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-360m"
TRAIN_STEPS = 4
#: (seq, global batch) tried in order; the first whose compiled step fits
#: the device memory is run
TRAIN_SIZES = ((2048, 8), (2048, 4), (1024, 4), (1024, 2))
#: share of the device's memory a compiled train step may plan to use
MEMORY_SHARE = 0.9
#: serving: two requests at each prompt length, all prefilled in chunks
PROMPT_LENS = (128, 256, 384, 512) * 2
NEW_TOKENS = 32
#: paged KV page = the flash-decode kernel's default kv block (bkv), so
#: paged decode accumulates in the same block order as dense decode
PAGE_SIZE = 512
#: prompt chunks of >= 128 tokens keep prefill on the flash kernel
PREFILL_CHUNK = 128
PARITY_LEN = 512
#: Pallas vs XLA-reference logits, as a share of the largest reference
#: logit: both take bf16 operands with f32 accumulation, but tiles and
#: the flash softmax accumulate in another order, and each layer rounds
#: its output to bf16, so the two residual streams drift apart with
#: depth (1.9% after 32 bf16 layers at smoke width, Pallas interpreter
#: on the CPU); a wrong kernel is off by the logits' own size
PARITY_RTOL = 0.05
#: four-chip vs one-device first step: the same math with reductions
#: split across devices, so only summation order differs
MESH_RTOL = 1e-2

PALLAS_ATTN = ("flash_attention", "flash_decode", "flash_decode_paged")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(what)


def device_check(chips: int) -> dict:
    import jax
    from repro.core.hardware import chip_for_kind
    from repro.kernels.api import _mode

    devices = jax.devices()
    d0 = devices[0]
    check(d0.platform == "tpu",
          f"no TPU: JAX found platform {d0.platform!r}")
    chip = chip_for_kind(d0.device_kind)
    check(len(devices) >= chips,
          f"{chips} chips asked for, JAX found {len(devices)}")
    check(_mode() == "pallas",
          f"kernel dispatch is {_mode()!r}, not 'pallas' "
          f"(REPRO_KERNELS={os.environ.get('REPRO_KERNELS')!r})")
    log(f"device {d0.device_kind} x{len(devices)} -> {chip.name}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def _planned_bytes(compiled) -> int:
    """Device bytes the compiled program plans to hold at its peak."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes)


def _compile_step(jitted, state, batch, mesh):
    from repro.dist import sharding as shd
    t0 = time.perf_counter()
    with shd.use_mesh(mesh):
        compiled = jitted.lower(state, batch).compile()
    return compiled, time.perf_counter() - t0


def train_phase(cfg) -> None:
    import jax
    from repro.data import pipeline
    from repro.dist import sharding as shd
    from repro.launch import train as TR
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    state, jitted, _ = TR.build(cfg, mesh, total_steps=TRAIN_STEPS)
    budget = MEMORY_SHARE * jax.devices()[0].memory_stats()["bytes_limit"]
    for seq, gb in TRAIN_SIZES:
        data = pipeline.DataConfig(seq_len=seq, global_batch=gb, seed=0)
        compiled, compile_s = _compile_step(
            jitted, state, pipeline.make_batch(cfg, data, 0), mesh)
        need = _planned_bytes(compiled)
        log(f"train seq {seq} batch {gb}: compiled in {compile_s:.1f}s "
            f"(informational), plans {need / 2**30:.2f} GiB of "
            f"{budget / 2**30:.2f} GiB")
        if need <= budget:
            break
    else:
        raise RuntimeError("no train size fits the device memory")
    log(f"train size chosen: seq {seq}, batch {gb}")
    check("tpu_custom_call" in compiled.as_text(),
          "compiled train step holds no Pallas kernel")

    losses, times = [], []
    with shd.use_mesh(mesh):
        for step in range(TRAIN_STEPS):
            batch = pipeline.make_batch(cfg, data, step)
            t0 = time.perf_counter()
            state, metrics = compiled(state, batch)
            losses.append(float(metrics["loss"]))
            times.append(time.perf_counter() - t0)
            log(f"train step {step}: loss {losses[-1]:.4f} "
                f"grad norm {float(metrics['grad_norm']):.4f}")
    check(all(math.isfinite(x) for x in losses),
          f"non-finite train loss: {losses}")
    log("train step seconds (host clock, informational): "
        + ", ".join(f"{t:.3f}" for t in times))


def serve_phase(cfg, params) -> None:
    import numpy as np
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_host_mesh
    from repro.serve.engine import DecodeEngine, Request

    rng = np.random.default_rng(1)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, (n,))
                    .astype(np.int32), max_tokens=NEW_TOKENS)
            for n in PROMPT_LENS]
    with shd.use_mesh(make_host_mesh()):
        engine = DecodeEngine(params, cfg, batch=len(reqs),
                              max_len=max(PROMPT_LENS) + NEW_TOKENS,
                              page_size=PAGE_SIZE,
                              prefill_chunk=PREFILL_CHUNK)
        t0 = time.perf_counter()
        results = engine.run(reqs)
        run_s = time.perf_counter() - t0
    got = sorted((r.prompt_len, r.n_tokens) for r in results)
    check(len(results) == len(reqs),
          f"{len(results)} of {len(reqs)} requests finished")
    check(all(n == NEW_TOKENS for _, n in got),
          f"requests ended short of {NEW_TOKENS} tokens: {got}")
    log(f"served {len(results)}/{len(reqs)} requests x {NEW_TOKENS} "
        f"tokens; {engine.metrics['prefill_chunks']} prefill chunks; "
        f"{run_s:.1f}s with compiles (host clock, informational)")


def _prefill_logits(cfg, params, tokens):
    """Last-token logits of one prefill, from a freshly built jit so the
    dispatch mode in force now is the one traced."""
    import jax
    from repro.models import transformer as T
    fn = jax.jit(lambda p, t: T.prefill(
        p, cfg, t, T.init_cache(cfg, 1, t.shape[1]))[0])
    return fn(params, tokens)


def plan_report() -> None:
    from repro import ops
    bad = []
    for p in ops.plans():
        t, s = p.tile, p.spec
        kern = ("gemm_gated" if s.gated else "gemm_grouped" if s.grouped
                else f"gemm_{t.strategy}")
        log(f"plan gemm {p.m}x{p.k}x{p.n} {p.problem.a_dtype}->"
            f"{p.problem.out_dtype} [{s.epilogue.key or '-'}] {kern} "
            f"tile {t.bm}x{t.bk}x{t.bn} vmem {p.vmem_bytes / 2**20:.2f} MiB"
            f" fallback {p.fallback_reason}")
        if p.fallback_reason:
            bad.append(p)
    for p in ops.attn_plans():
        log(f"plan attn {p.spec.mode} b{p.b} sq{p.sq} skv{p.skv} "
            f"h{p.hq}/{p.hkv} d{p.d} {p.kernel} bq {p.bq} bkv {p.bkv} "
            f"page {p.page_size} fallback {p.fallback_reason}")
        if p.fallback_reason or p.kernel not in PALLAS_ATTN:
            bad.append(p)
    check(bool(ops.plans()) and bool(ops.attn_plans()),
          "the run resolved no plans")
    check(not bad, f"{len(bad)} plans left the Pallas path")


def parity_and_plans(cfg, params) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro import ops

    tokens = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab, (1, PARITY_LEN)), jnp.int32)
    got = np.asarray(_prefill_logits(cfg, params, tokens), np.float32)
    plan_report()               # before the reference trace adds its own
    ops.plan_cache_clear()
    ops.attn_plan_cache_clear()
    was = os.environ.get("REPRO_KERNELS")
    os.environ["REPRO_KERNELS"] = "ref"
    try:
        want = np.asarray(_prefill_logits(cfg, params, tokens), np.float32)
    finally:
        if was is None:
            os.environ.pop("REPRO_KERNELS")
        else:
            os.environ["REPRO_KERNELS"] = was
    check(bool(np.isfinite(got).all()), "non-finite Pallas logits")
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    log(f"parity prefill {PARITY_LEN} tokens: max abs err {err:.6f}, "
        f"max |ref logit| {scale:.4f}, tolerance {PARITY_RTOL} x "
        f"{scale:.4f} = {PARITY_RTOL * scale:.6f}")
    check(err <= PARITY_RTOL * scale,
          "Pallas logits disagree with the XLA reference")


def four_chip_phase(cfg) -> None:
    import jax
    from repro.data import pipeline
    from repro.launch import train as TR
    from repro.launch.mesh import make_host_mesh

    seq, gb = TRAIN_SIZES[0]
    data = pipeline.DataConfig(seq_len=seq, global_batch=gb, seed=0)
    batch = pipeline.make_batch(cfg, data, 0)
    first = {}
    for name, mesh in (("2x2", make_host_mesh(data=2, model=2)),
                       ("1x1", make_host_mesh())):
        state, jitted, _ = TR.build(cfg, mesh, total_steps=TRAIN_STEPS)
        leaves = jax.tree.leaves(state.params)
        spans = {len(x.sharding.device_set) for x in leaves}
        split = sum(not x.sharding.is_fully_replicated for x in leaves)
        log(f"{name}: {len(leaves)} parameter arrays on {sorted(spans)} "
            f"devices, {split} of them split")
        if name == "2x2":
            check(spans == {4}, "parameters do not span 4 devices")
            check(split > 0, "no parameter is sharded over the mesh")
        compiled, compile_s = _compile_step(jitted, state, batch, mesh)
        check("tpu_custom_call" in compiled.as_text(),
              f"{name} train step holds no Pallas kernel")
        _, metrics = compiled(state, batch)
        first[name] = (float(metrics["loss"]),
                       float(metrics["grad_norm"]))
        log(f"{name} seq {seq} batch {gb}: first-step loss "
            f"{first[name][0]:.6f} grad norm {first[name][1]:.6f} "
            f"(compile {compile_s:.1f}s, informational)")
        del state, metrics, compiled
    for i, what in enumerate(("loss", "grad norm")):
        a, b = first["2x2"][i], first["1x1"][i]
        check(math.isfinite(a) and abs(a - b) <= MESH_RTOL * abs(b),
              f"2x2 {what} {a} vs one device {b} beyond {MESH_RTOL}")
    log(f"2x2 mesh matches one device within {MESH_RTOL} relative")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    from repro.configs.base import get_config
    from repro.runtime.compile_cache import use_compile_cache

    device = device_check(args.chips)
    log(f"compile cache: {use_compile_cache()}")
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(cfg)
    else:
        from repro.launch.mesh import make_host_mesh
        from repro.launch.serve import load_params
        train_phase(cfg)
        params = load_params(cfg, make_host_mesh(), seed=0)
        serve_phase(cfg, params)
        parity_and_plans(cfg, params)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
