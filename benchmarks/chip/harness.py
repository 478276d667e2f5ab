"""The benchmark's core: find a cell's pieces by name, check the device,
drive the cell's driver, decide ``correct`` and print the result line.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``: sizes, the program's registry name and
  the reference module;
* ``traffic/<mix>.json``: parameters, with ``driver`` naming
  ``drivers/<driver>.py`` and ``generator`` naming ``traffic/<gen>.py``;
* ``limits/<cell>.json``: the limit of each number compared;
* ``metrics/<metric>.py``: the reader of one per-layer metric.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from pathlib import Path
from typing import Any, Dict, Optional

HERE = Path(__file__).resolve().parent


def load_module(path: Path):
    """Import a file by path (names may hold dots or dashes)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class DeviceError(RuntimeError):
    """No usable accelerator: the run must print no result."""


def check_device(chips: int) -> dict:
    """The TPU this run measures; raises :class:`DeviceError` where JAX
    finds no TPU, too few chips, a kind the peak table lacks, or kernel
    dispatch other than Pallas."""
    import jax
    import flops
    from repro.kernels.api import _mode
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise DeviceError(f"no TPU: JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise DeviceError(f"{chips} chips asked for, JAX found "
                          f"{len(devices)}")
    try:
        flops.peaks(devices[0].device_kind)
    except KeyError as e:
        raise DeviceError(str(e)) from None
    if _mode() != "pallas":
        raise DeviceError(f"kernel dispatch is {_mode()!r}, not 'pallas'")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


class CompileCounter:
    """Counts XLA backend compilations through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == self.EVENT:
            self.count += 1


@dataclasses.dataclass
class Cell:
    """What a driver is given."""
    name: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    control: bool
    rate: Optional[float]
    t_start: float
    peak: dict
    compiles: CompileCounter
    generator: Any


def memory_peak_bytes() -> int:
    import jax
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in jax.local_devices() if d.memory_stats()]
    return int(max(peaks)) if peaks else 0


def decide(readings: Dict[str, float], limits: Dict[str, Any]) -> tuple:
    """(correct, checks): every reading at or under its limit.  A
    reading with no limit, or that is not a finite number, fails."""
    checks, ok = {}, True
    for name, value in readings.items():
        limit = limits.get(name)
        good = (limit is not None and value is not None
                and math.isfinite(value) and value <= limit)
        ok &= good
        checks[name] = {"value": value, "limit": limit}
    return bool(ok and readings), checks


def per_layer(bench: dict, cell_name: str, reported: set) -> list:
    """Per-layer metrics this cell reports: those listing it, and those
    without a list whose end-to-end metric the cell reports."""
    out = []
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if (cell_name in cells) if cells is not None \
                else m["moves"] in reported:
            out.append(m)
    return out


def end_to_end(bench: dict, cell_name: str) -> list:
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def resolve(bench: dict, workload: str) -> Optional[dict]:
    """The cell named ``workload`` with its configuration, traffic and
    limits read from their files; None where BENCHMARK.json lacks it."""
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        return None
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    limits = HERE / "limits" / f"{cell['name']}.json"
    return {"cell": cell,
            "config": json.loads((HERE.parents[1] / entry["file"])
                                 .read_text()),
            "traffic": json.loads((HERE / "traffic"
                                   / f"{cell['traffic']}.json").read_text()),
            "limits": json.loads(limits.read_text()) if limits.exists()
            else {}}


def run_cell(bench: dict, args, t_start: float) -> int:
    """Run ``args.workload`` on the chip and print its result; returns
    the exit code."""
    found = resolve(bench, args.workload)
    if found is None:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    return run_resolved(bench, found, args, t_start)


def run_resolved(bench: dict, found: dict, args, t_start: float,
                 device: Optional[dict] = None) -> int:
    """Run a resolved cell; ``device`` skips the device check (tests on
    the CPU)."""
    cell, config = found["cell"], found["config"]
    traffic, limits = found["traffic"], found["limits"]
    import jax
    import flops
    if device is None:
        try:
            device = check_device(cell["chips"])
        except DeviceError as e:
            log(f"refusing to run: {e}")
            return 3
        from repro.runtime.compile_cache import use_compile_cache
        log(f"compile cache: {use_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        peak = flops.peaks(device["kind"])
    else:
        peak = flops.peaks("TPU v5 lite")
    ctx = Cell(name=cell["name"], config=config, traffic=traffic,
               seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
               control=bool(args.control), rate=args.rate,
               t_start=t_start, peak=peak,
               compiles=CompileCounter(),
               generator=load_module(
                   HERE / "traffic" / f"{traffic['generator']}.py"))
    driver = load_module(HERE / "drivers" / f"{traffic['driver']}.py")
    rec = driver.run(ctx)

    e2e = end_to_end(bench, cell["name"])
    if args.trace:
        metrics = {}
        for m in per_layer(bench, cell["name"], {x["name"] for x in e2e}):
            reader = load_module(HERE / "metrics" / f"{m['name']}.py")
            value = reader.read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": rec["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in e2e if m["name"] in rec["e2e"]}
    correct, checks = decide(rec["readings"], limits.get("checks", {}))
    dev = dict(device, memory_peak_bytes=rec["memory_peak_bytes"])
    if args.trace and rec.get("trace"):
        dev["busy_s"] = rec["trace"]["busy_s"]
        dev["window_s"] = rec["trace"]["window_s"]
    for key in ("compiles_in_window", "info"):
        if key in rec:
            log(f"{key}: {json.dumps(rec[key])}")
    if rec.get("control"):
        log("control readings: " + json.dumps(rec["control"]))
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": dev}
    if args.trace and rec.get("trace"):
        result["breakdown"] = rec["trace"]["breakdown"]
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


def tmp_dir(prefix: str) -> str:
    import tempfile
    return tempfile.mkdtemp(prefix=prefix, dir=os.environ.get("TMPDIR"))
