import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST precede every other import (jax locks the
# device count at first init).  REPRO_DRYRUN_DEVICES overrides the
# placeholder-device count for small-mesh debugging — still before any
# jax import.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"])

import argparse          # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Optional  # noqa: E402

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input-shape) cell, lower + compile the cell's
step function (train_step / prefill / decode_step) against the production
mesh — 16×16 ('data','model') single-pod and 2×16×16 ('pod','data',
'model') multi-pod — from ShapeDtypeStructs only (no allocation), then
record ``memory_analysis()`` (proves it fits), ``cost_analysis()``
(FLOPs/bytes for §Roofline) and per-collective operand bytes parsed from
the post-SPMD HLO.

Usage:
    # one cell (what --all spawns per cell, for crash isolation):
    python -m repro.launch.dryrun --arch smollm-360m --shape train_4k \
        --mesh single --out artifacts/dryrun
    # the full 40-cell × {single,multi} sweep (skips cached results):
    python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun
"""


def _mesh_for(mode: str, debug_shape: Optional[str]):
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_production_mesh
    if debug_shape:
        dims = tuple(int(x) for x in debug_shape.split(","))
        names = {2: ("data", "model"),
                 3: ("pod", "data", "model")}[len(dims)]
        return shd.make_mesh(dims, names)
    return make_production_mesh(multi_pod=(mode == "multi"))


def _memory_analysis(compiled) -> dict:
    try:
        m = compiled.memory_analysis()
    except Exception as e:                      # CPU backends may lack it
        return {"available": False, "error": repr(e)}
    if m is None:
        return {"available": False}
    fields = ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes")
    out = {f: int(getattr(m, f)) for f in fields if hasattr(m, f)}
    out["available"] = bool(out)
    if {"argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes"} <= out.keys():
        # peak per-device HBM: args + outputs + temps - donated aliases
        out["peak_bytes_per_device"] = (
            out["argument_size_in_bytes"] + out["output_size_in_bytes"]
            + out["temp_size_in_bytes"] - out["alias_size_in_bytes"])
    return out


def _shard_bytes(struct_tree, sharding_tree) -> int:
    """Per-device bytes of a (struct, sharding) pytree pair — the manual
    fallback when the backend lacks memory_analysis, and an input-side
    cross-check when it doesn't."""
    import jax
    import numpy as np
    total = 0
    structs = jax.tree.leaves(struct_tree)
    shards = jax.tree.leaves(
        sharding_tree, is_leaf=lambda x: hasattr(x, "shard_shape"))
    for s, sh in zip(structs, shards):
        shape = sh.shard_shape(s.shape) if hasattr(sh, "shard_shape") \
            else s.shape
        total += int(np.prod(shape, dtype=np.int64)) * s.dtype.itemsize
    return total


def run_cell(arch: str, shape_name: str, mesh_mode: str,
             debug_shape: Optional[str] = None,
             layout_name: Optional[str] = None,
             explain: bool = False) -> dict:
    import jax
    from repro.configs.base import get_config
    from repro.core import hlo_cost, roofline
    from repro.core.hardware import TPU_V5E
    from repro.dist import sharding as shd
    from repro.launch import specs
    from repro.launch.shapes import SHAPES, skip_reason

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_mode,
           "kind": shape.kind, "ok": False}
    skip = skip_reason(cfg, shape)
    if skip:
        rec.update(skipped=True, skip_reason=skip, ok=True)
        return rec

    mesh = _mesh_for(mesh_mode, debug_shape)
    n_devices = mesh.devices.size
    rec.update(mesh_shape=list(mesh.devices.shape),
               mesh_axes=list(mesh.axis_names), n_devices=n_devices)

    with shd.use_mesh(mesh):
        p = specs.build_problem(arch, shape_name, mesh, layout_name)
        rec.update(layout=p.layout_name, tokens_per_step=p.tokens)
        t0 = time.time()
        lowered = specs.lower_problem(p)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()

    rec.update(lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2))

    mem = _memory_analysis(compiled)
    rec["memory_analysis"] = mem
    rec["arg_bytes_per_device"] = _shard_bytes(p.args, p.in_shardings)
    rec["hbm_per_device"] = TPU_V5E.hbm_bytes

    cost = compiled.cost_analysis()
    rec["cost_analysis"] = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }

    model_flops = cfg.model_flops(p.tokens, training=p.training)
    hlo_text = compiled.as_text()
    report = roofline.analyze(
        compiled, model_flops_per_device=model_flops / n_devices,
        hlo_text=hlo_text)
    rec["roofline"] = report.as_dict()
    parsed = hlo_cost.analyze_text(hlo_text)
    rec["bytes_by_scope"] = {k: round(v) for k, v
                             in parsed.bytes_by_scope.items()}
    rec["flops_by_scope"] = {k: round(v) for k, v
                             in parsed.flops_by_scope.items()}
    rec["params"] = cfg.param_count()
    rec["params_active"] = cfg.param_count(active_only=True)

    # Every GEMM the cell traced went through the planned GemmSpec API;
    # the plan cache therefore holds the cell's full per-GEMM decision
    # record (kernel, tile, modeled bytes, fallback reasons).
    from repro import ops as rops
    rec["gemm_plan_cache"] = rops.plan_cache_info()._asdict()
    rec["attn_plan_cache"] = rops.attn_plan_cache_info()._asdict()
    if explain:
        rec["gemm_plans"] = [p.explain() for p in rops.plans()]
        rec["attn_plans"] = [p.explain() for p in rops.attn_plans()]
    rec["ok"] = True
    return rec


# ---------------------------------------------------------------------------
# Sweep orchestration (subprocess per cell: fresh jax state + isolation)
# ---------------------------------------------------------------------------

def _out_path(out_dir: str, arch: str, shape: str, mesh: str) -> str:
    return os.path.join(out_dir, mesh, f"{arch}__{shape}.json")


def sweep(out_dir: str, mesh_modes, force: bool = False,
          archs=None, shapes=None, timeout: int = 7200) -> int:
    from repro.launch.shapes import all_cells
    cells = all_cells()
    failures = 0
    for mesh_mode in mesh_modes:
        for arch, shape, skip in cells:
            if archs and arch not in archs:
                continue
            if shapes and shape not in shapes:
                continue
            path = _out_path(out_dir, arch, shape, mesh_mode)
            if os.path.exists(path) and not force:
                continue
            os.makedirs(os.path.dirname(path), exist_ok=True)
            if skip:
                json.dump({"arch": arch, "shape": shape,
                           "mesh": mesh_mode, "ok": True, "skipped": True,
                           "skip_reason": skip}, open(path, "w"), indent=1)
                print(f"[dryrun] SKIP {mesh_mode} {arch} {shape}: {skip}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_mode,
                   "--out", out_dir]
            t0 = time.time()
            try:
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=timeout)
            except subprocess.TimeoutExpired:
                failures += 1
                json.dump({"arch": arch, "shape": shape,
                           "mesh": mesh_mode, "ok": False,
                           "error": f"timeout after {timeout}s"},
                          open(path, "w"), indent=1)
                print(f"[dryrun] TIMEOUT {mesh_mode} {arch} {shape}")
                continue
            dt = time.time() - t0
            if r.returncode != 0:
                failures += 1
                json.dump({"arch": arch, "shape": shape,
                           "mesh": mesh_mode, "ok": False,
                           "error": r.stderr[-4000:]},
                          open(path, "w"), indent=1)
                print(f"[dryrun] FAIL {mesh_mode} {arch} {shape} "
                      f"({dt:.0f}s)\n{r.stderr[-2000:]}")
            else:
                print(f"[dryrun] ok {mesh_mode} {arch} {shape} "
                      f"({dt:.0f}s)")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=("single", "multi", "both"))
    ap.add_argument("--all", action="store_true",
                    help="run every (arch × shape) cell via subprocesses")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--explain", action="store_true",
                    help="print GemmPlan.explain() for every GEMM the "
                         "cell planned (kernel, tile, modeled HBM/VMEM "
                         "bytes, fallback reasons)")
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="record plan events; writes PATH.jsonl + "
                         "PATH.trace.json")
    ap.add_argument("--layout", default=None,
                    choices=(None, "tp", "fsdp_tp"))
    ap.add_argument("--debug-mesh", default=None,
                    help="e.g. '2,4' — small mesh for local debugging "
                         "(set REPRO_DRYRUN_DEVICES to match)")
    ap.add_argument("--archs", nargs="*", default=None)
    ap.add_argument("--shapes", nargs="*", default=None)
    args = ap.parse_args()

    modes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    if args.all:
        failures = sweep(args.out, modes, force=args.force,
                         archs=args.archs, shapes=args.shapes)
        sys.exit(1 if failures else 0)

    assert args.arch and args.shape, "--arch/--shape or --all"
    if args.telemetry:
        from repro import telemetry
        telemetry.enable()
    try:
        rec = run_cell(args.arch, args.shape, modes[0],
                       debug_shape=args.debug_mesh,
                       layout_name=args.layout, explain=args.explain)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape, "mesh": modes[0],
               "ok": False, "error": traceback.format_exc()}
    path = _out_path(args.out, args.arch, args.shape, modes[0])
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    if args.explain and rec.get("gemm_plans"):
        print(f"[dryrun] {len(rec['gemm_plans'])} planned GEMMs "
              f"(cache {rec['gemm_plan_cache']}):")
        for text in rec["gemm_plans"]:
            print(text)
    if args.explain and rec.get("attn_plans"):
        print(f"[dryrun] {len(rec['attn_plans'])} planned attentions "
              f"(cache {rec['attn_plan_cache']}):")
        for text in rec["attn_plans"]:
            print(text)
    if args.telemetry:
        paths = telemetry.export(args.telemetry)
        print(f"[dryrun] telemetry: wrote {paths[0]} and {paths[1]}")
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("error", "gemm_plans", "attn_plans")},
                     indent=1))
    if not rec["ok"]:
        print(rec.get("error", ""), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
