"""Reduce a profiler trace (``.xplane.pb``) to device busy time, time
by kernel family, and the breakdown of the result line.

Device planes are those named ``/device:TPU:<n>``.  On each, the op
events are those of its ``XLA Ops`` line; an event's name is the HLO
instruction's text (``%gemm_tb.1 = f32[...] custom-call(...)``), from
which the instruction's name (``gemm_tb``, the Pallas kernel's jitted
function) and opcode are read.  Control-flow ops (``while``,
``conditional``, ``call``) span the ops of their bodies and are left
out.  An op belongs to the first family whose pattern matches its name.
Busy time is the union of the op intervals; the traced window runs from
the first op's start to the last op's end on any device, so that the
profiler's own start and stop, whose host and device clocks agree only
to a millisecond or so, stay out of it.  Idle gaps are the holes between busy
intervals on the first device, each named by the shortest host event
that covers most of it (the profiler's Python tracer is on during the
traced window).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

#: kernel families by name: Pallas GEMM kernels, every other matrix
#: product XLA runs (dots, convolutions), Pallas attention kernels
FAMILIES = {
    "gemm_pallas": r"^gemm_(aie|gated|tb|grouped)",
    "attention_pallas": r"^flash_",
    "matmul_xla": r"^(convolution|dot)",
}

#: entries in each breakdown list
TOP = 10

#: holes between busy intervals shorter than this are not listed as gaps
MIN_GAP_NS = 1000

#: ops whose interval spans the ops of their bodies
CONTROL = {"while", "conditional", "call"}


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def op_name(text: str) -> tuple:
    """(name without its instance number, opcode) of an HLO op event."""
    m = re.match(r"%?([^\s=]+?)(\.\d+)?\s*=", text)
    name = m.group(1) if m else text.split()[0] if text else ""
    op = re.search(r"\s([a-z][\w\-]*)\(", text)
    return name, (op.group(1) if op else "")


def load(path: str):
    import jax
    return jax.profiler.ProfileData.from_file(path)


def reduce(profile) -> dict:
    """busy_s (mean over devices), window_s, seconds by family (summed
    over devices), op counts, and the breakdown lists."""
    pats = {k: re.compile(v) for k, v in FAMILIES.items()}
    devices, host = [], []
    for plane in profile.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_dev and line.name == "XLA Ops":
                devices.append((plane.name, list(line.events)))
            elif plane.name == "/host:CPU" and line.name.startswith("python"):
                host.extend(line.events)
    ops = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
           for _, events in devices for ev in events
           if op_name(ev.name)[1] not in CONTROL]
    if not ops:
        raise ValueError("the trace holds no device op")
    t_lo = min(a for a, _ in ops)
    t_hi = max(b for _, b in ops)
    window_ns = t_hi - t_lo
    fam_ns = {k: 0 for k in FAMILIES}
    by_op: Dict[str, int] = {}
    busy_ns: List[int] = []
    merged0: List[Tuple[int, int]] = []
    n_ops = 0
    for i, (_name, events) in enumerate(sorted(devices)):
        spans = []
        for ev in events:
            name, opcode = op_name(ev.name)
            if opcode in CONTROL:
                continue
            a, d = int(ev.start_ns), int(ev.duration_ns)
            spans.append((a, a + d))
            n_ops += 1
            by_op[name] = by_op.get(name, 0) + d
            for fam, pat in pats.items():
                if pat.search(name) or pat.search(opcode):
                    fam_ns[fam] += d
                    break
        merged = _union(spans)
        busy_ns.append(sum(b - a for a, b in merged))
        if i == 0:
            merged0 = merged
    gaps = [(b0, a1) for (_, b0), (a1, _) in zip(merged0, merged0[1:])]
    if merged0:
        gaps += [(t_lo, merged0[0][0]), (merged0[-1][1], t_hi)]
    gaps = sorted((g for g in gaps if g[1] - g[0] >= MIN_GAP_NS),
                  key=lambda g: g[0] - g[1])[:TOP]
    idle = [[_host_name(host, a, b), (b - a) / 1e9] for a, b in gaps]
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": (sum(busy_ns) / len(busy_ns) / 1e9) if busy_ns else 0.0,
        "window_s": window_ns / 1e9,
        "devices": len(devices),
        "ops": n_ops,
        "family_s": {k: v / 1e9 for k, v in fam_ns.items()},
        "op_s": {k: v / 1e9 for k, v in
                 sorted(by_op.items(), key=lambda kv: -kv[1])[:40]},
        "breakdown": {"device_ops": [[k, v / 1e9] for k, v in ops],
                      "idle_gaps": idle},
    }


def _host_name(host, a: int, b: int) -> str:
    """The shortest host event covering at least half of [a, b)."""
    best, best_d = "no host event", None
    need = (b - a) / 2
    for ev in host:
        s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
        if min(e, b) - max(s, a) >= need:
            if best_d is None or ev.duration_ns < best_d:
                best, best_d = ev.name, ev.duration_ns
    return best


class Tracer:
    """Profiler session over part of a window, with the Python tracer
    on so that idle gaps can be named."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.on = False
        self.done = False

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.on = True

    def stop(self) -> None:
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on = False
            self.done = True
