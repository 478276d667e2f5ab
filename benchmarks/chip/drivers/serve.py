"""Serving cells: ``repro.serve.engine.DecodeEngine.run`` under an
open-loop trace.

Set-up makes the benchmark's weights on the device, builds the paged
engine the traffic file describes, and runs the mix's warm-up requests
through it, so that every prefill chunk start and every decode-burst
length the window can use is compiled; it then empties the prefix cache
and the counters.  The window submits every request of the seeded trace
with its arrival time; the engine admits each once its time has come.
Arrivals span ``seconds``; the run then drains, and the drain counts
only for requests that arrived in the window.  After it the engine is
freed and the reference recomputes the logits of a sample of the served
requests, drawn from the seed with the longest always in it.
"""

from __future__ import annotations

import time

import jax
import numpy as np

import reference
import weights
from drivers_common import program_config, reduce_trace
from harness import log, memory_peak_bytes, tmp_dir
from trace import Tracer


def _pct(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else float("nan")


def run(cell) -> dict:
    from repro.models import transformer as T
    from repro.serve.engine import DecodeEngine, Request

    c, p = cell.config, cell.traffic
    cfg = program_config(c)
    gen = cell.generator
    struct = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    params = weights.make(struct, cell.seed)
    engine = DecodeEngine(params, cfg, batch=p["slots"],
                          max_len=p["max_len"], page_size=p["page_size"],
                          n_pages=p["n_pages"],
                          prefill_chunk=p["prefill_chunk"],
                          prefix_cache=p["prefix_cache"])
    for r in gen.warmup(p, c["vocab_size"], cell.seed):
        engine.run([Request(prompt=r["prompt"], max_tokens=r["max_tokens"])])
    if engine.kv.prefix is not None:
        engine.kv.prefix.drop_all(engine.kv.pool)
    engine.reset_metrics()

    trace = gen.generate(p, cell.seed, cell.seconds, c["vocab_size"],
                         rate=cell.rate)
    reqs = [Request(prompt=r["prompt"], max_tokens=r["max_tokens"],
                    arrival=r["arrival"]) for r in trace]
    tracer = Tracer(tmp_dir("bench_trace_")) if cell.trace else None
    t_on = (cell.seconds - p["trace_seconds"]) / 2
    t_off = t_on + p["trace_seconds"]
    compiles0 = cell.compiles.count
    setup_s = time.perf_counter() - cell.t_start
    t0 = time.perf_counter()

    def now() -> float:
        t = time.perf_counter() - t0
        if tracer is not None:
            if not tracer.on and not tracer.done and t >= t_on:
                tracer.start()
            elif tracer.on and t >= t_off:
                tracer.stop()
        return t

    results = engine.run(reqs, now_fn=now)
    run_s = time.perf_counter() - t0
    if tracer is not None:
        tracer.stop()
    compiles = cell.compiles.count - compiles0
    peak_bytes = memory_peak_bytes()
    counters = dict(engine.metrics)
    del engine
    by_rid = {r.rid: r for r in results}
    served = []
    for q in reqs:
        r = by_rid.get(q.rid)
        if r is None:
            continue
        first = r.arrival + r.ttft
        served.append({"rid": q.rid, "arrival": r.arrival, "ttft": r.ttft,
                       "queue_wait": r.queue_wait,
                       "finished": r.finished_time, "n_tokens": r.n_tokens,
                       "prompt_len": r.prompt_len,
                       "tpot": (r.finished_time - first)
                       / max(r.n_tokens - 1, 1)})
    missing = len(reqs) - len(served)
    inf = [float("inf")] * missing
    ttft = [s["ttft"] for s in served] + inf
    tpot = [s["tpot"] for s in served] + inf
    done_in_window = [s for s in served if s["finished"] <= cell.seconds]
    rec = {"kind": "serve", "config": c, "traffic": p, "peak": cell.peak,
           "setup_s": setup_s, "seconds": cell.seconds, "run_s": run_s,
           "requests": served, "engine": counters,
           "memory_peak_bytes": peak_bytes, "compiles_in_window": compiles,
           "attempted": len(reqs), "failed": missing, "trace": None}
    rec["e2e"] = {
        "setup_s": setup_s,
        **{f"{name}_p{q}_ms": 1e3 * _pct(xs, q)
           for name, xs in (("ttft", ttft), ("tpot", tpot))
           for q in (50, 75, 90)},
        "serve_tokens_per_s": sum(s["n_tokens"] for s in done_in_window)
        / cell.seconds,
    }
    if tracer is not None:
        rec["trace"] = reduce_trace(tracer)
    rec["info"] = {k: rec["e2e"][k] for k in rec["e2e"]}
    thirds = [[s["queue_wait"] for s in served
               if k * cell.seconds / 3 <= s["arrival"] < (k + 1) * cell.seconds / 3]
              for k in range(3)]
    rec["info"].update(requests=len(reqs), offered_per_s=len(reqs)
                       / cell.seconds, run_s=run_s,
                       queue_wait_by_third_s=[
                           float(np.mean(t)) if t else None for t in thirds],
                       decode_steps=counters["decode_steps"],
                       prefill_tokens=counters["prefill_tokens"],
                       shared_prompt_tokens=counters["shared_prompt_tokens"])
    log("window: " + ", ".join(f"{k} {v}" for k, v in rec["info"].items()))

    # the check: a seeded sample of finished requests, the longest in it
    prompts = {q.rid: q.prompt for q in reqs}
    toks = {r.rid: r.tokens for r in results}
    pool = sorted(toks, key=lambda k: (-(len(prompts[k]) + len(toks[k])), k))
    rng = np.random.default_rng(np.random.SeedSequence([cell.seed, 11]))
    rest = list(rng.permutation(pool[1:]))[:p["check_requests"] - 1]
    sample = pool[:1] + [int(k) for k in rest]
    t_ref = time.perf_counter()
    gaps = [reference.served_gap(c, params, prompts[k], toks[k])
            for k in sample]
    rec["readings"] = {"served_gap": max(gaps)}
    rec["info"].update(checked_requests=len(sample),
                       checked_tokens=int(sum(len(toks[k]) for k in sample)),
                       reference_s=time.perf_counter() - t_ref)
    if cell.control:
        rec["control"] = {"fp8": {"served_gap": max(
            reference.control_gap(c, params, prompts[k], toks[k])
            for k in sample)}}
    return rec
