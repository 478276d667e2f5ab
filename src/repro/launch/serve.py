"""Production serving driver: sharded continuous-batching decode.

Builds the mesh + layout-engine shardings, places (randomly initialized
or checkpointed) params, and serves generation requests through
:class:`repro.serve.engine.DecodeEngine` — either a fixed batch
(``--batch``) or a Poisson-arrival request trace (``--trace N``) that
exercises the continuous scheduler end-to-end and reports throughput
plus mean/p99 request latency.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --smoke --batch 4 --prompt-len 32 --steps 16

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --smoke --trace 16 --rate 4 --slots 2 --steps 16
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import telemetry
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import get_config, get_smoke_config
from repro.dist import layout, sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.runtime.compile_cache import use_compile_cache
from repro.serve.engine import DecodeEngine, Request

#: prompt lengths a trace draws from — bucketed so the slot-prefill jit
#: compiles once per bucket instead of once per request
TRACE_PROMPT_BUCKETS = (4, 8, 16, 32)


def load_params(cfg, mesh, ckpt_dir=None, seed: int = 0,
                int8: bool = False):
    with shd.use_mesh(mesh):
        struct = jax.eval_shape(
            lambda: T.init_params(jax.random.PRNGKey(seed), cfg))
        sh = layout.param_shardings(struct, cfg, mesh)
        if ckpt_dir:
            params = Checkpointer(ckpt_dir).restore(struct, shardings=sh)
        else:
            init = jax.jit(lambda k: T.init_params(k, cfg),
                           out_shardings=sh)
            params = init(jax.random.PRNGKey(seed))
        if int8:                    # paper-precision serving mode
            from repro import quant
            before = quant.param_bytes(params)
            params, n = quant.quantize_params(params)
            print(f"[serve] int8-quantized {n} weight banks: "
                  f"{before/2**20:.0f} -> "
                  f"{quant.param_bytes(params)/2**20:.0f} MiB")
        return params


def make_trace(cfg, n_requests: int, rate: float, max_steps: int,
               temperature: float, seed: int = 0) -> list:
    """Poisson-arrival workload: exponential inter-arrival gaps at
    ``rate`` req/s, prompt lengths from TRACE_PROMPT_BUCKETS, max_tokens
    uniform in [2, max_steps]."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
    arrivals -= arrivals[0]                  # first request at t=0
    reqs = []
    for t in arrivals:
        plen = int(rng.choice(TRACE_PROMPT_BUCKETS))
        reqs.append(Request(
            prompt=rng.integers(0, cfg.vocab, (plen,)).astype(np.int32),
            max_tokens=int(rng.integers(2, max(max_steps, 2) + 1)),
            temperature=temperature, arrival=float(t)))
    return reqs


def _warmup(engine: DecodeEngine, cfg, prompt_lens,
            temperature: float = 0.0) -> None:
    """Compile the slot-prefill for every prompt-length bucket plus the
    decode step AND the sampling path the trace will use (greedy vs
    temperature) before any timed work, so reported tokens/sec excludes
    jit compilation."""
    rng = np.random.default_rng(1234)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, (int(p),))
                    .astype(np.int32), max_tokens=2,
                    temperature=temperature)
            for p in sorted(set(int(p) for p in prompt_lens))]
    engine.run(reqs)
    engine.reset_metrics()
    # warm-up traced every prefill/decode GEMM through the planned
    # GemmSpec API; the cache now holds one resolved plan per unique
    # (spec, shape) — steady-state serving adds no DSE work
    from repro import ops
    info = ops.plan_cache_info()
    print(f"[serve] gemm plan cache after warm-up: {info.entries} "
          f"plans ({info.hits} hits / {info.misses} misses)")


def run_trace(engine: DecodeEngine, cfg, args) -> None:
    reqs = make_trace(cfg, args.trace, args.rate, args.steps,
                      args.temperature, seed=args.seed)
    _warmup(engine, cfg, [r.prompt.shape[0] for r in reqs],
            temperature=args.temperature)
    t0 = time.perf_counter()
    results = engine.run(reqs,
                         now_fn=lambda: time.perf_counter() - t0)
    dt = time.perf_counter() - t0
    lat = np.asarray([r.finished_time - r.arrival for r in results])
    ttft = np.asarray([r.ttft for r in results])
    qwait = np.asarray([r.queue_wait for r in results])
    gen = sum(r.n_tokens for r in results)
    m = engine.metrics
    print(f"[serve] trace: {len(results)}/{args.trace} requests, "
          f"{gen} tokens in {dt:.2f}s "
          f"({gen / dt:.1f} tok/s end-to-end, "
          f"{engine.tokens_per_sec():.1f} tok/s decode)")
    print(f"[serve] latency: mean {lat.mean()*1e3:.0f} ms, "
          f"p99 {np.percentile(lat, 99)*1e3:.0f} ms; "
          f"slot occupancy {engine.occupancy():.2f} "
          f"({m['decode_steps']} steps x {engine.n_slots} slots, "
          f"{m['prefill_tokens']} prompt tokens)")
    print(f"[serve] ttft: mean {ttft.mean()*1e3:.0f} ms, "
          f"p99 {np.percentile(ttft, 99)*1e3:.0f} ms; "
          f"queue wait: mean {qwait.mean()*1e3:.0f} ms, "
          f"p99 {np.percentile(qwait, 99)*1e3:.0f} ms")
    if engine.paged:
        print(f"[serve] paged KV: {m['prefill_chunks']} prefill "
              f"chunks, max decode stall "
              f"{m['max_prefill_stall_tokens']} prompt tokens; "
              f"prefix cache {m['prefix_hits']} hits / "
              f"{m['prefix_misses']} misses "
              f"({m['shared_prompt_tokens']} prompt tokens shared)")
        dense = m["modeled_kv_bytes_dense_rows"]
        if dense:
            print(f"[serve] modeled decode KV stream "
                  f"{m['modeled_kv_bytes'] / 2**20:.2f} MiB at true "
                  f"positions vs {dense / 2**20:.2f} MiB at dense "
                  f"max_len rows "
                  f"({m['modeled_kv_bytes'] / dense:.2f}x)")


def run_batch(engine: DecodeEngine, cfg, args) -> None:
    rng = np.random.default_rng(0)
    prompts = jax.numpy.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)),
        jax.numpy.int32)
    frames = None
    if cfg.family == "audio":
        frames = jax.numpy.asarray(
            rng.standard_normal(
                (args.batch, cfg.encoder_seq, cfg.d_model),
                dtype=np.float32), cfg.dtype)

    # timing fix: one throwaway generation compiles prefill + step +
    # sampling, so the timed run (and its tokens/sec) excludes the jit
    # compile; engine bursts block_until_ready before reading the clock.
    # max_tokens=2 so at least one decode burst actually runs (a
    # 1-token request completes at admission without touching _step)
    engine.generate(prompts, min(2, args.steps + 1), frames=frames)
    engine.reset_metrics()
    t0 = time.perf_counter()
    result = engine.generate(prompts, args.steps, frames=frames)
    dt = time.perf_counter() - t0
    tok_s = args.batch * result.steps / dt
    print(f"[serve] generated {result.steps} steps x {args.batch} seqs "
          f"in {dt:.2f}s ({tok_s:.1f} tok/s, "
          f"{engine.tokens_per_sec():.1f} tok/s decode-only)")
    print("[serve] first sequence:", result.tokens[0][:16], "...")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--trace", type=int, default=0,
                    help="serve N Poisson-arrival requests through the "
                         "continuous-batching scheduler")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="trace arrival rate (requests/sec)")
    ap.add_argument("--slots", type=int, default=None,
                    help="cache slots for --trace (default --batch)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="record spans/counters for the whole run and "
                         "write PATH.jsonl + PATH.trace.json (the "
                         "latter loads in chrome://tracing or "
                         "ui.perfetto.dev)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="block-paged KV cache with this page size "
                         "(tokens); max_len rounds up to a page "
                         "multiple")
    ap.add_argument("--pages", type=int, default=None,
                    help="KV pool size in pages incl. the sink page "
                         "(default: dense-equivalent capacity)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="split paged admissions into chunks of this "
                         "many prompt tokens, interleaved with decode "
                         "bursts")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable content-hash prefix sharing of "
                         "paged prompt pages")
    ap.add_argument("--int8", action="store_true",
                    help="fused int8 weights, bf16 activations (W8A16)")
    ap.add_argument("--w8a8", action="store_true",
                    help="int8 weights + dynamic int8 activations "
                         "(the paper's int8 x int8 / int32-accumulate "
                         "scheme); implies --int8")
    args = ap.parse_args()
    print(f"[serve] compile cache: {use_compile_cache()}")
    if args.telemetry:
        telemetry.enable()
    if args.w8a8:
        args.int8 = True
        from repro import quant
        quant.set_activation_mode("w8a8")

    cfg = get_smoke_config(args.arch) if args.smoke \
        else get_config(args.arch)
    mesh = make_host_mesh(data=len(jax.devices()))
    params = load_params(cfg, mesh, args.ckpt_dir, int8=args.int8)
    n_slots = args.slots or args.batch
    if args.max_len:
        max_len = args.max_len
    elif args.trace:
        # trace prompts come from the buckets; +1 slack for warm-up
        max_len = max(TRACE_PROMPT_BUCKETS) + max(args.steps, 2)
    else:
        max_len = args.prompt_len + args.steps

    with shd.use_mesh(mesh):
        engine = DecodeEngine(params, cfg, batch=n_slots,
                              max_len=max_len,
                              temperature=args.temperature,
                              page_size=args.page_size,
                              n_pages=args.pages,
                              prefill_chunk=args.prefill_chunk,
                              prefix_cache=not args.no_prefix_cache)
        if engine.paged:
            print(f"[serve] paged KV: {engine.kv.pool.n_pages - 1} "
                  f"pages x {engine.page_size} tokens (+1 sink), "
                  f"{engine.kv.max_pages} pages/slot"
                  + (f", prefill chunk {engine.prefill_chunk}"
                     if engine.prefill_chunk else ""))
        bpt = engine.modeled_bytes_per_token()
        mode = "w8a8" if args.w8a8 else \
            ("w8a16" if args.int8 else "bf16")
        print(f"[serve] {mode}: modeled GEMM weight stream "
              f"{bpt / 2**20:.1f} MiB/step "
              f"({bpt / n_slots / 2**20:.2f} MiB per seq-token "
              f"at {n_slots} slots)")
        if args.trace:
            run_trace(engine, cfg, args)
        else:
            run_batch(engine, cfg, args)
    if args.telemetry:
        snap = telemetry.snapshot()
        paths = telemetry.export(args.telemetry)
        print(f"[serve] telemetry: {snap['n_events']} events, "
              f"plan cache {snap['plan_cache']}; wrote "
              f"{paths[0]} and {paths[1]}")
        routed = snap["counters"].get("moe.group_sizes")
        if routed is not None:
            dropped = snap["counters"].get("moe.dropped_tokens", 0)
            total = routed + dropped
            print(f"[serve] moe: {int(routed)} rows through grouped "
                  f"expert GEMMs, {int(dropped)} capacity-dropped "
                  f"({dropped / max(total, 1):.1%} of assignments)")


if __name__ == "__main__":
    main()
