"""Telemetry tests: span nesting + attribute propagation, counter and
gauge registries, the disabled-mode no-op contract (shared singletons,
zero allocation), JSONL / Chrome-trace export round-trips with
schema-checked keys, modeled-traffic fields on plan/execute events, the
serve-engine request lifecycle over the shared acceptance trace, and the
repo-wide stray-print gate (telemetry is the sanctioned channel for
structured output from library code; ``print`` belongs to launch/)."""

import ast
import json
import pathlib
import time

import jax
import numpy as np
import pytest

from repro import ops, telemetry
from repro.telemetry import TRACK_TID_BASE, Recorder

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"


@pytest.fixture
def rec():
    """Fresh recorder for the test; always uninstalled afterwards so
    the suite's default stays disabled-mode."""
    r = telemetry.enable(Recorder())
    yield r
    telemetry.disable()


@pytest.fixture(autouse=True)
def _always_disabled_after():
    yield
    telemetry.disable()


# ---------------------------------------------------------------- spans

def test_span_nesting_and_attrs(rec):
    with telemetry.span("outer", a=1) as outer:
        with telemetry.span("inner") as inner:
            inner.set(b=2)
        assert inner.parent == outer.sid
        assert inner.depth == outer.depth + 1
    spans = {e["name"]: e for e in rec.events if e["type"] == "span"}
    assert set(spans) == {"outer", "inner"}
    # children close (and are emitted) before their parents
    assert rec.events[0]["name"] == "inner"
    assert spans["outer"]["attrs"] == {"a": 1}
    assert spans["inner"]["attrs"] == {"b": 2}
    assert spans["inner"]["parent"] == spans["outer"]["sid"]
    assert spans["inner"]["depth"] == 1 and spans["outer"]["depth"] == 0
    # the inner interval nests inside the outer one
    assert spans["inner"]["ts"] >= spans["outer"]["ts"]
    assert spans["inner"]["dur"] <= spans["outer"]["dur"]


def test_span_sync_blocks_device_work(rec):
    x = jax.numpy.ones((128, 128))
    with telemetry.span("gemm") as sp:
        y = sp.sync(jax.jit(lambda a: a @ a)(x))
    assert float(y[0, 0]) == 128.0
    (ev,) = [e for e in rec.events if e["type"] == "span"]
    assert ev["dur"] > 0


def test_span_stack_survives_exception(rec):
    with pytest.raises(RuntimeError):
        with telemetry.span("outer"):
            with telemetry.span("inner"):
                raise RuntimeError("boom")
    with telemetry.span("after") as sp:
        pass
    assert sp.depth == 0 and sp.parent is None


def test_complete_span_gets_request_track(rec):
    t = time.perf_counter()
    telemetry.complete_span("serve.request", t, t + 0.5, tid=3, rid=3)
    (ev,) = rec.events
    assert ev["tid"] == TRACK_TID_BASE + 3
    assert abs(ev["dur"] - 0.5) < 1e-6


# ----------------------------------------------------- counters / gauges

def test_counters_and_gauges(rec):
    telemetry.counter("tok").add(3)
    telemetry.counter("tok").add()
    assert telemetry.counter("tok") is rec.counter("tok")
    assert rec.counter("tok").value == 4

    g = telemetry.gauge("slots")
    g.set(2)
    g.set(2)          # unchanged -> no new timeline sample
    g.set(1)
    samples = [e for e in rec.events if e["type"] == "gauge"]
    assert [s["value"] for s in samples] == [2.0, 1.0]

    snap = rec.snapshot()
    assert snap["counters"]["tok"] == 4
    assert snap["gauges"]["slots"] == 1.0
    assert "plan_cache" in snap and "entries" in snap["plan_cache"]


# ------------------------------------------------------- disabled mode

def test_disabled_mode_is_allocation_free_noop():
    assert telemetry.recorder() is None and not telemetry.enabled()
    # shared stateless singletons: every call returns the SAME object,
    # so the disabled hot path allocates nothing
    assert telemetry.span("a", x=1) is telemetry.span("b")
    assert telemetry.counter("a") is telemetry.counter("b")
    assert telemetry.gauge("a") is telemetry.gauge("b")
    with telemetry.span("a") as sp:
        v = sp.sync(42)            # passthrough
    assert v == 42 and sp.set(k=1) is sp
    telemetry.counter("a").add(5)
    telemetry.gauge("a").set(5)
    telemetry.event("a", x=1)
    telemetry.complete_span("a", 0.0, 1.0)
    assert telemetry.snapshot() is None
    assert telemetry.export("/nonexistent/should-not-write") is None


def test_disabled_span_is_the_shared_noop_and_annotates_nothing():
    from repro.telemetry import telemetry as tmod
    sp = telemetry.span("a")
    assert sp is tmod._NOOP_SPAN and type(sp).__slots__ == ()
    with sp as inner:
        assert inner is sp


# ----------------------------------------------------- profiler annotation

def _xplane_events(log_dir):
    path = next(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    prof = jax.profiler.ProfileData.from_file(str(path))
    return [(plane.name, ev.name) for plane in prof.planes
            for line in plane.lines for ev in line.events]


def test_enabled_span_lands_on_the_profiler_host_plane(rec, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.span("probe.annotated"):
            jax.block_until_ready(jax.numpy.ones(4) + 1)
        telemetry.complete_span("probe.retroactive", 0.0, 1.0)
    events = _xplane_events(tmp_path)
    hosts = {plane for plane, name in events if name == "probe.annotated"}
    assert hosts and all(h.startswith("/host:") for h in hosts)
    # a retroactive span is only in the recorder's own export
    assert all(name != "probe.retroactive" for _, name in events)
    assert {e["name"] for e in rec.events} >= {"probe.annotated",
                                               "probe.retroactive"}


# -------------------------------------------------------------- exports

def test_jsonl_roundtrip_schema(rec, tmp_path):
    with telemetry.span("work", n=1):
        telemetry.event("mark", k="v")
    telemetry.gauge("g").set(7)
    path = rec.export_jsonl(str(tmp_path / "t.jsonl"))
    lines = [json.loads(l) for l in open(path)]
    meta, events = lines[0], lines[1:]
    assert meta["type"] == "meta"
    assert meta["schema_version"] == telemetry.SCHEMA_VERSION
    assert {"counters", "gauges", "plan_cache",
            "n_events"} <= set(meta["snapshot"])
    assert len(events) == len(rec.events)
    for ev in events:
        assert {"type", "name", "ts"} <= set(ev)
        if ev["type"] == "span":
            assert {"dur", "sid", "depth", "tid", "attrs"} <= set(ev)
        elif ev["type"] == "gauge":
            assert "value" in ev


def test_chrome_trace_roundtrip(rec, tmp_path):
    with telemetry.span("work"):
        telemetry.event("mark")
    telemetry.gauge("g").set(7)
    telemetry.complete_span("serve.request", 0.0, 0.1, tid=0)
    base = str(tmp_path / "t")
    jsonl_path, trace_path = rec.export(base)
    assert jsonl_path.endswith(".jsonl")
    trace = json.loads(open(trace_path).read())
    assert "traceEvents" in trace
    phases = {e["ph"] for e in trace["traceEvents"]}
    assert {"M", "X", "i", "C"} <= phases
    for e in trace["traceEvents"]:
        assert {"ph", "name", "pid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0 and "ts" in e and "tid" in e
    # the explicit-tid request span got its own named track
    names = [e for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert any(e["args"]["name"] == "request 0" for e in names)


# ----------------------------------------------- kernel plan/execute

def test_plan_events_carry_modeled_traffic(rec):
    ops.plan_cache_clear()
    spec = ops.GemmSpec()
    ops.plan(spec, (64, 256, 128))
    ops.plan(spec, (64, 256, 128))          # cache hit
    plans = [e for e in rec.events if e["name"] == "gemm.plan"]
    assert [p["attrs"]["cache"] for p in plans] == ["miss", "hit"]
    for p in plans:
        a = p["attrs"]
        assert {"spec", "strategy", "tile", "hbm_bytes", "vmem_bytes",
                "flops", "t_model_us", "bound"} <= set(a)
        assert a["hbm_bytes"] > 0 and a["flops"] == 2 * 64 * 256 * 128
    assert rec.counter("gemm.plan_cache.miss").value == 1
    assert rec.counter("gemm.plan_cache.hit").value == 1


def test_execute_event_once_per_spec_shape(rec):
    ops.plan_cache_clear()
    x = jax.numpy.ones((16, 64), jax.numpy.bfloat16)
    w = jax.numpy.ones((64, 32), jax.numpy.bfloat16)
    for _ in range(3):
        ops.gemm(x, w)
    execs = [e for e in rec.events if e["name"] == "gemm.execute"]
    assert len(execs) == 1                   # deduped first-trace event
    a = execs[0]["attrs"]
    assert {"spec", "m", "k", "n", "strategy", "mode",
            "hbm_bytes", "flops"} <= set(a)
    assert (a["m"], a["k"], a["n"]) == (16, 64, 32)
    ops.plan_cache_clear()                   # clears the dedup set too
    ops.gemm(x, w)
    execs = [e for e in rec.events if e["name"] == "gemm.execute"]
    assert len(execs) == 2


# ------------------------------------------------- serve lifecycle

def test_serve_lifecycle_events(rec):
    from repro.configs.base import get_smoke_config
    from repro.models import transformer as T
    from repro.serve.engine import (ACCEPTANCE_TRACE, DecodeEngine,
                                    acceptance_requests)

    cfg = get_smoke_config("smollm-360m")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    max_len = max(p + t for p, t in ACCEPTANCE_TRACE) + 1
    engine = DecodeEngine(params, cfg, batch=2, max_len=max_len)
    reqs = acceptance_requests(cfg.vocab)
    results = {r.rid: r for r in engine.run(reqs)}

    events = [e for e in rec.events if e["type"] == "event"]
    for req in reqs:
        order = [e["name"] for e in events
                 if e["attrs"].get("rid") == req.rid]
        assert order == ["serve.request.queued",
                         "serve.request.admitted",
                         "serve.request.finished"]
        fin = next(e for e in events
                   if e["name"] == "serve.request.finished"
                   and e["attrs"]["rid"] == req.rid)
        assert fin["attrs"]["ttft"] > 0
        assert fin["attrs"]["n_tokens"] == results[req.rid].n_tokens
        # each request got its own lifecycle track with phase spans
        track = [e for e in rec.events if e["type"] == "span"
                 and e["tid"] == TRACK_TID_BASE + req.rid]
        names = {e["name"] for e in track}
        assert {"serve.request", "serve.request.prefill",
                "serve.request.decode"} <= names
        life = next(e for e in track if e["name"] == "serve.request")
        assert life["attrs"]["ttft"] == pytest.approx(
            results[req.rid].ttft, abs=1e-6)
    # engine results surface the same latency split
    for r in results.values():
        assert r.ttft > 0 and r.queue_wait >= 0
    assert rec.counter("serve.completed").value == len(reqs)
    assert rec.counter("serve.generated_tokens").value == \
        sum(r.n_tokens for r in results.values())
    # slot-occupancy gauge recorded a timeline (and ended drained)
    occ = [e for e in rec.events if e["type"] == "gauge"
           and e["name"] == "serve.slots_active"]
    assert occ and occ[-1]["value"] == 0.0


# ---------------------------------------------------- repo-wide gate

def test_no_stray_prints_in_library_code():
    """``print`` is the launch/ drivers' UI; library code must report
    through telemetry (or return values).  AST-based so docstrings and
    comments mentioning print don't false-positive."""
    offenders = []
    for path in SRC.rglob("*.py"):
        if "launch" in path.relative_to(SRC).parts:
            continue
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "print"):
                offenders.append(f"{path.relative_to(SRC)}:{node.lineno}")
    assert not offenders, f"print() outside launch/: {offenders}"
