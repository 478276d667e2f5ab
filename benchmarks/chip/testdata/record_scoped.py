#!/usr/bin/env python3
"""Record a traced train step with its compiled HLO, on the chip, and
sum its device time by region (``regions.py``).

    python3 benchmarks/chip/testdata/record_scoped.py <out-dir>
    python3 benchmarks/chip/testdata/record_scoped.py <out-dir> \\
        --workload smollm-360m.train-2k --steps 2

Without ``--workload`` it traces one step of the program's smoke-size
smollm-360m (``tests/data``) and writes ``scoped.xplane.pb`` and
``scoped.hlo.txt`` to ``<out-dir>``, the test data of
``tests/test_regions.py``.  With ``--workload`` it builds that training
cell's step at the cell's size (the program's own initial weights),
times ``--steps`` untraced steps, traces as many, and writes the HLO and
the reduction with every instruction's seconds (``<cell>.hlo.txt``,
``<cell>.regions.json``), not the trace.  Both print the op and region
tables, the two region metrics, the largest ops of each region, the step
times with and without the profiler, and the first device events with
their statistics and the ``/host:metadata`` plane, so that what the
profile itself carries can be checked by hand.  Source paths in the
written HLO are made relative to the checkout.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(CHIP))


def _cell(workload):
    import harness
    if workload is None:
        data = CHIP / "tests" / "data"
        return (json.loads((data / "smollm-360m-smoke.json").read_text()),
                json.loads((data / "train-smoke.json").read_text()))
    found = harness.resolve(
        json.loads((ROOT / "BENCHMARK.json").read_text()), workload)
    return found["config"], found["traffic"]


def _show(prof) -> None:
    for plane in prof.planes:
        print("PLANE", plane.name, [ln.name for ln in plane.lines])
        if plane.name.startswith("/device") or plane.name == "/host:metadata":
            for line in plane.lines:
                for ev in list(line.events)[:3]:
                    print("  ", line.name, "|", ev.name[:160],
                          ev.duration_ns, [(k, str(v)[:80])
                                           for k, v in ev.stats][:12])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("out")
    ap.add_argument("--workload", default=None)
    ap.add_argument("--steps", type=int, default=1)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import flops
    import harness
    import regions
    import trace
    from drivers_common import program_config
    from repro.dist import sharding as shd
    from repro.launch import train as TR
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.compile_cache import use_compile_cache

    device = harness.check_device(1)
    use_compile_cache()
    c, p = _cell(args.workload)
    o = p["optimizer"]
    gen = harness.load_module(CHIP / "traffic" / f"{p['generator']}.py")
    mesh = make_host_mesh(**c.get("mesh", {}))
    state, step_fn, _ = TR.build(program_config(c), mesh,
                                 peak_lr=o["peak_lr"],
                                 total_steps=o["total_steps"])
    batches = [gen.batch(p, args.seed, i, c["vocab_size"])
               for i in range(args.steps + 2)]
    log_dir = tempfile.mkdtemp(prefix="bench_record_")
    tracer = trace.Tracer(log_dir)
    times = {"untraced": [], "traced": []}
    with shd.use_mesh(mesh):
        for b in batches[:2]:                       # compile and warm up
            state, m = step_fn(state, b)
            float(m["loss"])
        for key in ("untraced", "traced"):
            if key == "traced":
                tracer.start()
            for b in batches[2:]:
                t0 = time.perf_counter()
                state, m = step_fn(state, b)
                float(m["loss"])
                times[key].append(time.perf_counter() - t0)
        tracer.stop()
        hlo = step_fn.lower(state, batches[-1]).compile().as_text()
    xplane = trace.find_xplane(log_dir)
    prof = trace.load(xplane)
    _show(prof)
    red = trace.reduce(prof)
    red.update(regions.reduce(prof, hlo))
    instr: dict = {}
    top: dict = {}
    for name, region, d in regions.events(prof, hlo):
        per = top.setdefault(region, {})
        per[name] = per.get(name, 0) + d
        instr[name] = instr.get(name, 0) + d
    rec = {"trace": red, "traced_steps": args.steps, "config": c,
           "traffic": p, "peak": flops.peaks(device["kind"])}
    summary = {
        "device": device, "steps": args.steps, "step_s": times,
        "attn_roofline": regions.attn_roofline(rec),
        "scan_glue_share": regions.scan_glue_share(rec),
        "region_join_share": red["region_join_share"],
        "region_s": red["region_s"], "family_s": red["family_s"],
        "busy_s": red["busy_s"], "window_s": red["window_s"],
        "device_ops": red["breakdown"]["device_ops"],
        "top_ops": {r: sorted(((n, v / 1e9) for n, v in per.items()),
                              key=lambda kv: -kv[1])[:8]
                    for r, per in top.items()}}
    full = dict(summary, instr_s={n: v / 1e9 for n, v in instr.items()})
    print(regions.table(red["region_s"], args.steps, red["devices"]))
    print(json.dumps(summary, indent=1, default=str))
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    hlo = hlo.replace(f'"{ROOT}/', '"')     # source paths of the checkout
    if args.workload is None:
        shutil.copy(xplane, out / "scoped.xplane.pb")
        (out / "scoped.hlo.txt").write_text(hlo)
    else:
        (out / f"{args.workload}.hlo.txt").write_text(hlo)
        (out / f"{args.workload}.regions.json").write_text(
            json.dumps(full, indent=1, default=str))
    shutil.rmtree(log_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
