"""The train step's named scopes reach the compiled HLO: every region
the benchmark sums device time by (``benchmarks/chip/regions.py``) is
named in some instruction's ``op_name``, every matrix product and every
op of the attention backward falls in a region, only bookkeeping is
left unscoped, and the scopes change nothing but metadata.  The serving
paths name their K/V pool update and their sampling.  Under Pallas
dispatch the attention backward is the flash backward kernels, inside
the ``attention`` region, and no blocked XLA recompute is left."""

import contextlib
import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_smoke_config
from repro.train import train_step as TS

ROOT = Path(__file__).resolve().parents[1]

#: what may run unscoped: arguments, constants, tuples and the copies
#: and control flow that carry them, and the step counter
BOOKKEEPING = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "copy", "while", "conditional", "call"}


def _regions():
    spec = importlib.util.spec_from_file_location(
        "bench_regions", ROOT / "benchmarks" / "chip" / "regions.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


R = _regions()


def _compiled_step_text(shape=(2, 64)) -> str:
    cfg = get_smoke_config("smollm-360m")
    step = TS.make_train_step(cfg)
    state = jax.eval_shape(lambda: TS.init_state(jax.random.PRNGKey(0),
                                                 cfg))
    batch = {k: jax.ShapeDtypeStruct(shape, jnp.int32)
             for k in ("tokens", "labels")}
    return jax.jit(step, donate_argnums=(0,)).lower(state, batch) \
        .compile().as_text()


@pytest.fixture(scope="module")
def text():
    return _compiled_step_text()


@pytest.fixture(scope="module")
def hlo(text):
    return R.parse(text)


@pytest.fixture(scope="module")
def table(hlo):
    return R.regions(hlo)


def _scopes(hlo) -> set:
    """Every component of every ``op_name``, with ``jvp(...)`` and
    ``transpose(...)`` unwrapped."""
    named = set()
    for ins in hlo.instrs.values():
        for part in (ins.op_name or "").split("/"):
            m = re.fullmatch(r"(?:jvp|transpose)\((.*)\)", part)
            while m:
                part = m.group(1)
                m = re.fullmatch(r"(?:jvp|transpose)\((.*)\)", part)
            named.add(part)
    return named


def test_every_scope_is_named_in_the_hlo(hlo):
    assert set(R.REGIONS) <= _scopes(hlo), set(R.REGIONS) - _scopes(hlo)


def test_matrix_products_fall_in_a_sublayer_region(hlo, table):
    sublayer = {"qkv", "attention", "attn_out", "mlp", "lm_head_xent"}
    products = [n for n, ins in hlo.instrs.items()
                if ins.opcode in ("dot", "custom-call")]
    assert products
    assert {n: table[n] for n in products
            if table[n] not in sublayer} == {}


def test_attention_backward_falls_in_attention(hlo, table):
    back = [n for n, ins in hlo.instrs.items()
            if ins.op_name and "transpose(" in ins.op_name
            and R.region_of_path(ins.op_name) == "attention"]
    assert back
    for n in R.executed(hlo):
        if n in back:
            assert table[n] == "attention", n


def test_only_bookkeeping_is_unscoped(text, hlo, table):
    lines = {m.group(1): m.group(2) for m in
             re.finditer(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$", text,
                         re.M)}
    left = []
    for n in R.executed(hlo):
        ins = hlo.instrs[n]
        if table[n] != R.UNSCOPED or ins.opcode in BOOKKEEPING:
            continue
        counter = lines[n].startswith("s32[] ") \
            and (ins.op_name or "").endswith("/add")
        if not counter:
            left.append((n, ins.opcode, ins.op_name))
    assert left == []
    assert sum(table[n] == R.UNSCOPED for n in R.executed(hlo)) \
        < 0.05 * len(R.executed(hlo))


@pytest.fixture(scope="module")
def interpret_hlo():
    """The step under Pallas (interpret) dispatch, at a length past the
    blocked path's threshold (1024): the XLA backward that the flash
    backward replaces was a blocked scan there."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_KERNELS", "interpret")
        return R.parse(_compiled_step_text((1, 1152)))


def test_flash_backward_replaces_the_blocked_recompute(interpret_hlo):
    hlo = interpret_hlo
    assert [n for n, ins in hlo.instrs.items()
            if "attention_blocked" in (ins.op_name or "")] == []
    table = R.regions(hlo)
    bwd = [n for n in R.executed(hlo)
           if "jit(flash_attention_bwd)" in (hlo.instrs[n].op_name or "")]
    assert bwd
    assert {n: table[n] for n in bwd if table[n] != "attention"} == {}


def _strip(text: str) -> str:
    """The HLO without metadata and without the source-location tables
    that metadata points into."""
    body = [ln for ln in text.splitlines()
            if ln.startswith(("HloModule", " ", "\t", "ENTRY", "%", "}"))]
    return "\n".join(re.sub(r",? metadata=\{[^}]*\}", "", ln)
                     for ln in body)


def test_scopes_change_only_metadata(text, monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_step_text()
    assert "/layers/" not in bare and "(layers)" not in bare
    assert _strip(bare) == _strip(text)


def _paged_smoke():
    from repro.models import transformer as T
    cfg = get_smoke_config("smollm-360m")
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    cache = jax.eval_shape(lambda: T.init_paged_cache(cfg, 2, 5, 16, 4))
    return T, cfg, params, cache


@pytest.mark.parametrize("path", ["decode", "prefill_chunk"])
def test_serve_paths_name_the_pool_update(path):
    T, cfg, params, cache = _paged_smoke()
    if path == "decode":
        fn = jax.jit(lambda p, tok, c: T.decode_step(p, cfg, tok, c))
        args = (params, jax.ShapeDtypeStruct((2, 1), jnp.int32), cache)
    else:
        fn = jax.jit(lambda p, tok, c, row: T.prefill_paged_chunk(
            p, cfg, tok, c, 0, row, 16))
        args = (params, jax.ShapeDtypeStruct((1, 16), jnp.int32), cache,
                jax.ShapeDtypeStruct((4,), jnp.int32))
    scopes = _scopes(R.parse(fn.lower(*args).compile().as_text()))
    assert {"kv_write", "attention", "attn_out"} <= scopes


def test_sampling_is_named():
    from repro.serve.engine import DecodeEngine
    logits = jax.ShapeDtypeStruct((2, 16), jnp.float32)
    for fn, args in ((DecodeEngine._argmax_impl, (logits,)),
                     (DecodeEngine._sample_temp_impl,
                      (logits, jax.ShapeDtypeStruct((2,), jnp.uint32),
                       jax.ShapeDtypeStruct((2,), jnp.float32)))):
        hlo = R.parse(jax.jit(fn).lower(*args).compile().as_text())
        assert "sample" in _scopes(hlo)
