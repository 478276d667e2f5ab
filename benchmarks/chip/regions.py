"""Device time of a train step by model region.

The program opens a ``jax.named_scope`` at each region of its step, and
XLA keeps the scope path of every HLO instruction in its
``metadata={op_name="jit(train_step)/transpose(jvp(layers))/while/body/
closed_call/checkpoint/attention/..."}``: the forward, the remat
recompute and the backward of a region all carry its name.  The
profiler's op events carry only the instruction's text, so an event is
joined by its instruction name (``fusion.123``) with the compiled step's
HLO text (``compiled.as_text()``), and only inside the intervals of that
HLO module on the ``XLA Modules`` line.

An instruction's region is the innermost of :data:`REGIONS` that is a
component of its ``op_name`` once ``jvp(...)`` and ``transpose(...)``
are unwrapped.  ``layers`` with no sublayer region under it is
:data:`GLUE`: the scan's stacking and slicing of per-layer buffers.  An
instruction whose ``op_name`` names no region takes the region of its
fused computation's instructions (root first), else that of the first of
its users that has one (the compiler's copies, broadcasts and split
reductions keep no scope of their own), else that of the instruction
that calls its computation (a loop body's copies are the loop's), else
:data:`UNSCOPED`.  Events that do not join are :data:`UNJOINED`.
Control-flow ops span their bodies and are left out, as
``trace.reduce`` leaves them out.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, NamedTuple, Optional

#: the step's scopes, as the program names them (a copy: the benchmark
#: reads the names, it does not import them)
REGIONS = ("embed", "layers", "norm", "qkv", "attention", "attn_out",
           "mlp", "lm_head_xent", "grad_clip", "optimizer")
GLUE = "layers.glue"
UNSCOPED = "unscoped"
UNJOINED = "unjoined"

#: ops whose interval spans the ops of their bodies
CONTROL = {"while", "conditional", "call"}

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation"
                    r"|false_computation)=%?([\w.\-]+)")
_CALL_SETS = re.compile(r"\b(?:branch_computations|called_computations)"
                        r"=\{([^}]*)\}")
_WRAP = re.compile(r"^(?:jvp|transpose)\((.*)\)$")
_EVENT = re.compile(r"^%?([^\s=]+)\s*=")
_REF = re.compile(r"%([\w.\-]+)")


class Instr(NamedTuple):
    name: str
    opcode: str
    op_name: Optional[str]
    computation: str
    calls: tuple
    operands: tuple


class Hlo(NamedTuple):
    """A parsed HLO module: its name, instructions by name, each
    computation's instructions (root first), the instruction that calls
    each computation and the users of each instruction."""
    module: str
    instrs: Dict[str, Instr]
    computations: Dict[str, List[str]]
    caller: Dict[str, str]
    users: Dict[str, List[str]]


def parse(text: str) -> Hlo:
    module, comp = "", None
    instrs: Dict[str, Instr] = {}
    comps: Dict[str, List[str]] = {}
    caller: Dict[str, str] = {}
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        if not line.startswith((" ", "\t")):
            m = _HEADER.match(line)
            comp = m.group(1) if m else None
            if comp is not None:
                comps[comp] = []
            continue
        m = _INSTR.match(line)
        if comp is None or not m:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(" " + rest)
        on = _OP_NAME.search(rest)
        called = list(_CALLS.findall(rest))
        for group in _CALL_SETS.findall(rest):
            called += [c.strip().lstrip("%") for c in group.split(",")
                       if c.strip()]
        args = rest.split(", metadata=")[0]
        instrs[name] = Instr(name, op.group(1) if op else "",
                             on.group(1) if on else None, comp,
                             tuple(called), tuple(_REF.findall(args)))
        if line.lstrip().startswith("ROOT "):
            comps[comp].insert(0, name)
        else:
            comps[comp].append(name)
        for c in called:
            caller.setdefault(c, name)
    users: Dict[str, List[str]] = {}
    for ins in instrs.values():
        for o in ins.operands:
            if o in instrs and instrs[o].computation == ins.computation:
                users.setdefault(o, []).append(ins.name)
    return Hlo(module, instrs, comps, caller, users)


def executed(hlo: Hlo) -> List[str]:
    """Instructions that run as ops of their own, and so appear as
    profiler events: those of the entry computation and of the bodies
    of control flow, not of fused or reducer computations."""
    out = []
    for comp, names in hlo.computations.items():
        up = hlo.caller.get(comp)
        if up is None or hlo.instrs[up].opcode in CONTROL:
            out += names
    return out


def region_of_path(op_name: str) -> Optional[str]:
    """The innermost region named in an ``op_name`` path, ``layers``
    alone being :data:`GLUE`; None where it names none."""
    found = None
    for part in op_name.split("/"):
        m = _WRAP.match(part)
        while m:
            part = m.group(1)
            m = _WRAP.match(part)
        if part in REGIONS:
            found = part
    return GLUE if found == "layers" else found


def regions(hlo: Hlo) -> Dict[str, str]:
    """Every instruction's region, by instruction name."""
    memo: Dict[str, str] = {}

    def own(ins: Instr) -> Optional[str]:
        return region_of_path(ins.op_name) if ins.op_name else None

    def fused(ins: Instr, depth: int = 0) -> Optional[str]:
        if ins.opcode != "fusion" or depth > 8:
            return None
        for c in ins.calls:
            for n in hlo.computations.get(c, ()):
                inner = hlo.instrs[n]
                r = own(inner) or fused(inner, depth + 1)
                if r:
                    return r
        return None

    def downstream(name: str, seen: set) -> Optional[str]:
        for u in hlo.users.get(name, ()):
            if u in seen:
                continue
            seen.add(u)
            ins = hlo.instrs[u]
            r = own(ins) or fused(ins) or downstream(u, seen)
            if r:
                return r
        return None

    def resolve(name: str) -> str:
        if name in memo:
            return memo[name]
        memo[name] = UNSCOPED             # breaks a cycle, if any
        ins = hlo.instrs[name]
        r = own(ins) or fused(ins) or downstream(name, {name})
        if r is None:
            up = hlo.caller.get(ins.computation)
            r = resolve(up) if up is not None else UNSCOPED
        memo[name] = r
        return r

    return {name: resolve(name) for name in hlo.instrs}


def _module_spans(evs, module: str):
    spans = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                   for ev in evs
                   if ev.name.split("(")[0] == module)
    return [a for a, _ in spans], [b for _, b in spans]


def events(profile, hlo_text: str):
    """(instruction name, region, nanoseconds) of every non-control op
    event on the device planes; the name is None and the region
    :data:`UNJOINED` where the event does not join."""
    from trace import op_name
    hlo = parse(hlo_text)
    table = regions(hlo)
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        mods = lines.get("XLA Modules")
        starts, ends = _module_spans(mods.events if mods else [],
                                     hlo.module)
        ops = lines.get("XLA Ops")
        for ev in (ops.events if ops else []):
            if op_name(ev.name)[1] in CONTROL:
                continue
            a, d = int(ev.start_ns), int(ev.duration_ns)
            m = _EVENT.match(ev.name)
            name = m.group(1) if m and m.group(1) in table else None
            if mods is not None and name is not None:
                i = bisect.bisect_right(starts, a) - 1
                if i < 0 or a >= ends[i]:
                    name = None
            yield name, table[name] if name else UNJOINED, d


def reduce(profile, hlo_text: str) -> dict:
    """``region_s``: device seconds of op events by region, summed over
    devices (:data:`UNJOINED` holding those that do not join), and
    ``region_join_share``: the share of op time that joins."""
    out: Dict[str, int] = {}
    joined = total = 0
    for name, region, d in events(profile, hlo_text):
        out[region] = out.get(region, 0) + d
        total += d
        joined += d if name else 0
    return {"region_s": {k: v / 1e9 for k, v in
                         sorted(out.items(), key=lambda kv: -kv[1])},
            "region_join_share": joined / total if total else 0.0}


def attention_least_seconds(c: dict, traffic: dict, peak: dict,
                            chips: int = 1) -> float:
    """Least time of one train step's attention on one chip: forward
    and backward operations (3 x the forward's, causal) at the bf16
    peak, or the bytes of q, k, v read and o written forward, and q, k,
    v, o, do read and dq, dk, dv written backward, at the HBM peak."""
    import flops
    seq = traffic["seq_len"]
    tokens = traffic["global_batch"] * seq // chips
    mean_ctx = sum(flops._context(c, p) for p in range(seq)) / seq
    ops = 3.0 * flops.attention_flops(c, mean_ctx) * tokens
    width = 2 if c["dtype"] == "bfloat16" else 4
    q = tokens * c["num_attention_heads"] * c["head_dim"] * width
    kv = tokens * c["num_key_value_heads"] * c["head_dim"] * width
    least_bytes = 6.0 * c["num_hidden_layers"] * (q + kv)
    return max(ops / peak["bf16_flops"], least_bytes / peak["hbm_bytes_per_s"])


def attn_roofline(rec) -> Optional[float]:
    """Least time of the step's attention over the device time of the
    ``attention`` region per traced step, in %."""
    tr = rec.get("trace")
    if not tr or "region_s" not in tr or not rec.get("traced_steps"):
        return None
    spent = tr["region_s"].get("attention", 0.0) / tr["devices"] \
        / rec["traced_steps"]
    if spent <= 0:
        return None
    mesh = rec["config"]["mesh"]
    least = attention_least_seconds(rec["config"], rec["traffic"],
                                    rec["peak"],
                                    chips=mesh["data"] * mesh["model"])
    return 100.0 * least / spent


def scan_glue_share(rec) -> Optional[float]:
    """Device time of :data:`GLUE` over all op time, in %."""
    tr = rec.get("trace")
    if not tr or "region_s" not in tr:
        return None
    total = sum(tr["region_s"].values())
    return 100.0 * tr["region_s"].get(GLUE, 0.0) / total if total else None


def table(region_s: Dict[str, float], steps: int, devices: int = 1) -> str:
    """The region table, one line: seconds per step and share."""
    total = sum(region_s.values()) or 1.0
    return "regions: " + ", ".join(
        f"{k} {v / steps / devices:.6g}s {100.0 * v / total:.2f}%"
        for k, v in region_s.items())
