"""The declarative AttnSpec operator API: spec validation, the counted
dispatch-mode-scoped plan cache, the (prefill / decode / paged) x
(causal / window) x (MHA / GQA / MQA) x (pallas / interpret / ref)
dispatch matrix with call counters, recorded fallback reasons, grads
through the ONE generic VJP, plan-explain-vs-cost-model agreement on the
decode-32k shape, measured block autotuning through the persistent
``attn|`` cache namespace, and bit-identical parity of the deprecated
legacy entrypoints against the planned path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.core import bandwidth
from repro.core.memory_model import fits_vmem_bytes
from repro.kernels import attn_api
from repro.kernels import ops as legacy
from repro.kernels import ref as _ref


@pytest.fixture(autouse=True)
def _fresh_attn_plan_cache():
    """Attention plans are global, dispatch-mode-scoped state; tests
    here flip REPRO_KERNELS and monkeypatch kernels, so stale plans must
    not leak in either direction."""
    attn_api.attn_plan_cache_clear()
    yield
    attn_api.attn_plan_cache_clear()


def _rand(shape, dtype=jnp.bfloat16, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32).astype(dtype)


def _qkv(b=1, sq=128, skv=128, hq=2, hkv=2, d=64, dtype=jnp.bfloat16):
    return (_rand((b, sq, hq, d), dtype, 0),
            _rand((b, skv, hkv, d), dtype, 1),
            _rand((b, skv, hkv, d), dtype, 2))


def _decode_ops(b=2, skv=256, hq=4, hkv=2, d=64, dtype=jnp.bfloat16):
    q = _rand((b, hq, d), dtype, 0)
    kc = _rand((b, skv, hkv, d), dtype, 1)
    vc = _rand((b, skv, hkv, d), dtype, 2)
    pos = jnp.asarray([skv // 2, skv - 1][:b], jnp.int32)
    return q, kc, vc, pos


# ---------------------------------------------------------------------------
# Spec validation — invalid combos raise at construction
# ---------------------------------------------------------------------------

def test_spec_rejects_bad_mode_window_group():
    with pytest.raises(ValueError, match="mode"):
        ops.AttnSpec(mode="chunked")
    with pytest.raises(ValueError, match="window"):
        ops.AttnSpec(window=-1)
    with pytest.raises(ValueError, match="group"):
        ops.AttnSpec(group=0)


def test_spec_rejects_noncausal_decode_and_windowed_noncausal():
    with pytest.raises(ValueError, match="causal"):
        ops.AttnSpec(mode="decode", causal=False)
    with pytest.raises(ValueError, match="causal"):
        ops.AttnSpec(mode="decode_paged", causal=False)
    with pytest.raises(ValueError, match="window"):
        ops.AttnSpec(causal=False, window=128)


def test_spec_rejects_nonfloat_dtypes_and_kv_quant_hook():
    with pytest.raises(ValueError, match="q_dtype"):
        ops.AttnSpec(q_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype"):
        ops.AttnSpec(kv_dtype="int32")
    # the forward-compat hook must never be silently ignored
    with pytest.raises(ValueError, match="kv_quant"):
        ops.AttnSpec(kv_quant=True)


def test_spec_block_override_constraints():
    with pytest.raises(ValueError, match="bq"):
        ops.AttnSpec(bq=100)            # not a multiple of 8
    with pytest.raises(ValueError, match="bkv"):
        ops.AttnSpec(bkv=64)            # not a multiple of 128
    with pytest.raises(ValueError, match="page"):
        ops.AttnSpec(mode="decode_paged", bkv=256)
    # a valid override is honored verbatim
    spec = ops.AttnSpec(bq=256, bkv=128)
    pl = ops.attn_plan(spec, (1, 2048, 2048, 2, 2, 64))
    assert (pl.bq, pl.bkv) == (256, 128)
    assert "!256x128" in spec.key


def test_spec_key_namespace_and_plan_shapes_validation():
    assert ops.AttnSpec().key.startswith("attn|")
    with pytest.raises(ValueError, match="5 ints"):
        ops.attn_plan(ops.AttnSpec(mode="decode"), (1, 2, 3, 4, 5, 6))
    with pytest.raises(ValueError, match="group"):
        # hq != hkv * group
        ops.attn_plan(ops.AttnSpec(group=2), (1, 128, 128, 2, 2, 64))


# ---------------------------------------------------------------------------
# The dispatch matrix: call counters prove which kernel family ran
# ---------------------------------------------------------------------------

_ORIG_ATTENTION_REF = _ref.attention_ref
_ORIG_XLA_DECODE = attn_api._decode_attention_xla


def _flash_dummy(q, k, v, *, causal=True, window=0, scale=None,
                 q_offset=None, **kw):
    """Stand-in for the Pallas flash kernel under REPRO_KERNELS=pallas
    on a CPU host — same math via the jnp oracle, so the dispatch can
    be asserted without a TPU."""
    return _ORIG_ATTENTION_REF(q, k, v, causal=causal, window=window,
                               scale=scale, q_offset=q_offset)


def _flash_decode_dummy(q, kc, vc, pos, *, window=0, **kw):
    return _ORIG_XLA_DECODE(q, kc, vc, pos, window=window)


def _flash_paged_dummy(q, kp, vp, tbl, pos, *, window=0, **kw):
    n, ps, hkv, d = kp.shape
    b, mp = tbl.shape
    k = kp[tbl].reshape(b, mp * ps, hkv, d)
    v = vp[tbl].reshape(b, mp * ps, hkv, d)
    return _ORIG_XLA_DECODE(q, k, v, pos, window=window)


CASES = {
    # name: (mode_kind, heads, causal, window)
    "prefill_mha": ("prefill", (2, 2), True, 0),
    "prefill_gqa_window": ("prefill", (4, 2), True, 64),
    "prefill_mqa_full": ("prefill", (4, 1), False, 0),
    "decode_gqa": ("decode", (4, 2), True, 0),
    "decode_mqa_window": ("decode", (4, 1), True, 64),
    "paged_gqa": ("decode_paged", (4, 2), True, 0),
}


@pytest.mark.parametrize("mode", ["ref", "interpret", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_dispatch_matrix(monkeypatch, mode, case):
    """Every (prefill/decode/paged x mask x head-grouping) combination
    must route to the intended kernel family in every REPRO_KERNELS
    mode, through the ONE planned dispatch path."""
    monkeypatch.setenv("REPRO_KERNELS", mode)
    kind, (hq, hkv), causal, window = CASES[case]
    calls = {}

    def count(name, fn):
        def run(*args, **kw):
            calls[name] = calls.get(name, 0) + 1
            return fn(*args, **kw)
        return run

    pallas_impl = {
        "interpret": (attn_api.flash_attention, attn_api.flash_decode,
                      attn_api.flash_decode_paged),
        "pallas": (_flash_dummy, _flash_decode_dummy, _flash_paged_dummy),
        "ref": (attn_api.flash_attention, attn_api.flash_decode,
                attn_api.flash_decode_paged),
    }[mode]
    monkeypatch.setattr(attn_api, "flash_attention",
                        count("flash", pallas_impl[0]))
    monkeypatch.setattr(attn_api, "flash_decode",
                        count("flash_decode", pallas_impl[1]))
    monkeypatch.setattr(attn_api, "flash_decode_paged",
                        count("flash_paged", pallas_impl[2]))
    monkeypatch.setattr(attn_api, "attention_blocked",
                        count("blocked", attn_api.attention_blocked))
    monkeypatch.setattr(attn_api._ref, "attention_ref",
                        count("xla_ref", _ORIG_ATTENTION_REF))
    monkeypatch.setattr(attn_api, "_decode_attention_xla",
                        count("xla_decode", _ORIG_XLA_DECODE))

    if kind == "prefill":
        q, k, v = _qkv(hq=hq, hkv=hkv)
        got = ops.attention(q, k, v, causal=causal, window=window)
        want_ref = _ORIG_ATTENTION_REF(q, k, v, causal=causal,
                                       window=window)
        want_call = "flash" if mode != "ref" else "xla_ref"
    elif kind == "decode":
        q, kc, vc, pos = _decode_ops(hq=hq, hkv=hkv)
        got = ops.decode_attention(q, kc, vc, pos, window=window)
        want_ref = _ORIG_XLA_DECODE(q, kc, vc, pos, window=window)
        want_call = "flash_decode" if mode != "ref" else "xla_decode"
    else:
        q, kc, vc, pos = _decode_ops(hq=hq, hkv=hkv, skv=256)
        kp = kc.reshape(4, 128, hkv, 64)
        vp = vc.reshape(4, 128, hkv, 64)
        tbl = jnp.arange(4, dtype=jnp.int32).reshape(2, 2)
        got = ops.decode_attention_paged(q, kp, vp, tbl, pos,
                                         window=window)
        want_ref = _ORIG_XLA_DECODE(q, kc, vc, pos, window=window)
        want_call = "flash_paged" if mode != "ref" else "xla_decode"

    assert calls.get(want_call) == 1, (calls, want_call)
    wrong = {"flash", "flash_decode", "flash_paged", "blocked",
             "xla_ref", "xla_decode"} - {want_call}
    if kind == "decode_paged" and mode == "ref":
        wrong -= {"xla_decode"}     # the gather path reuses the dense one
    assert not (wrong & calls.keys()), (calls, want_call)

    # the plan cache saw exactly this resolution
    (pl,) = ops.attn_plans()
    assert pl.dispatch == mode
    assert pl.spec.mode == kind
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want_ref, np.float32),
        rtol=3e-2, atol=3e-2)


def test_long_prefill_routes_to_blocked_in_ref_mode(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    q, k, v = _qkv(sq=128, skv=2048)
    got = ops.attention(q, k, v)
    (pl,) = ops.attn_plans()
    assert pl.kernel == "attention_blocked"
    assert pl.fallback_reason is None       # ref mode never wanted flash
    assert pl.bq is not None and pl.bkv is not None
    want = _ORIG_ATTENTION_REF(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# Satellite: the silent pallas fallback is now loud
# ---------------------------------------------------------------------------

def test_short_prefill_fallback_reason_recorded(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    pl = ops.attn_plan(ops.AttnSpec(), (1, 64, 128, 2, 2, 64))
    assert pl.kernel == "xla_ref"
    assert "sq >= 128" in pl.fallback_reason
    assert "sq=64" in pl.fallback_reason
    assert "fallback" in pl.explain()


def test_no_fallback_reason_when_flash_applies(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    pl = ops.attn_plan(ops.AttnSpec(), (1, 128, 128, 2, 2, 64))
    assert pl.kernel == "flash_attention"
    assert pl.fallback_reason is None
    assert "fallback" not in pl.explain()


# ---------------------------------------------------------------------------
# Legacy entrypoints: deprecated shims, bit-identical to the new API
# ---------------------------------------------------------------------------

@pytest.mark.filterwarnings("ignore::DeprecationWarning")
@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_legacy_entrypoints_bit_identical(monkeypatch, mode):
    monkeypatch.setenv("REPRO_KERNELS", mode)
    q, k, v = _qkv(hq=4, hkv=2)
    qd, kc, vc, pos = _decode_ops()
    kp = kc.reshape(4, 128, 2, 64)
    vp = vc.reshape(4, 128, 2, 64)
    tbl = jnp.arange(4, dtype=jnp.int32).reshape(2, 2)
    pairs = [
        (legacy.attention(q, k, v, window=64),
         ops.attention(q, k, v, window=64)),
        (legacy.decode_attention(qd, kc, vc, pos),
         ops.decode_attention(qd, kc, vc, pos)),
        (legacy.decode_attention_paged(qd, kp, vp, tbl, pos),
         ops.decode_attention_paged(qd, kp, vp, tbl, pos)),
    ]
    for old, new in pairs:
        assert old.dtype == new.dtype
        assert (np.asarray(old) == np.asarray(new)).all()


def test_legacy_attention_entrypoints_warn():
    q, k, v = _qkv()
    qd, kc, vc, pos = _decode_ops()
    kp = kc.reshape(4, 128, 2, 64)
    vp = vc.reshape(4, 128, 2, 64)
    tbl = jnp.arange(4, dtype=jnp.int32).reshape(2, 2)
    with pytest.warns(DeprecationWarning, match="repro.ops"):
        legacy.attention(q, k, v)
    with pytest.warns(DeprecationWarning, match="repro.ops"):
        legacy.decode_attention(qd, kc, vc, pos)
    with pytest.warns(DeprecationWarning, match="repro.ops"):
        legacy.decode_attention_paged(qd, kp, vp, tbl, pos)


# ---------------------------------------------------------------------------
# Grads through the ONE generic VJP, vs the ref composition
# ---------------------------------------------------------------------------

def test_prefill_grads_match_ref_composition():
    q, k, v = _qkv(sq=256, skv=256, hq=4, hkv=2, dtype=jnp.float32)
    got = jax.grad(lambda *a: ops.attention(*a, window=64).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(
        lambda *a: _ref.attention_ref(*a, window=64).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_long_prefill_grads_match_ref_composition():
    # forward = attention_blocked, backward recomputes through the
    # checkpointed blocked composition — still the ref math
    q, k, v = _qkv(sq=128, skv=2048, dtype=jnp.float32)
    got = jax.grad(lambda *a: ops.attention(*a).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: _ref.attention_ref(*a).sum(),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-3, atol=1e-3)


def test_decode_grads_with_int_pos_operand():
    # pos is an int data operand riding the VJP — float0 cotangent
    q, kc, vc, pos = _decode_ops(dtype=jnp.float32)
    got = jax.grad(
        lambda q, kc, vc: ops.decode_attention(q, kc, vc, pos).sum(),
        argnums=(0, 1, 2))(q, kc, vc)
    want = jax.grad(
        lambda q, kc, vc: attn_api._decode_attention_xla(
            q, kc, vc, pos, window=0).sum(),
        argnums=(0, 1, 2))(q, kc, vc)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_exactly_one_custom_vjp_in_attn_api():
    import inspect
    src = inspect.getsource(attn_api)
    assert src.count("functools.partial(jax.custom_vjp") == 1


# ---------------------------------------------------------------------------
# Plan cache: counted, dispatch-mode scoped
# ---------------------------------------------------------------------------

def test_plan_cache_counters_and_mode_scoping(monkeypatch):
    spec = ops.AttnSpec(mode="decode", group=2)
    shapes = (2, 256, 4, 2, 64)
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    p1 = ops.attn_plan(spec, shapes)
    p2 = ops.attn_plan(spec, shapes)
    assert p1 is p2
    info = ops.attn_plan_cache_info()
    assert (info.entries, info.hits, info.misses) == (1, 1, 1)
    # a different dispatch mode is a different plan, not a stale hit
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    p3 = ops.attn_plan(spec, shapes)
    assert p3.kernel == "flash_decode" and p1.kernel == "xla_decode"
    assert ops.attn_plan_cache_info().entries == 2
    ops.attn_plan_cache_clear()
    assert ops.attn_plan_cache_info() == (0, 0, 0)


def test_execute_rejects_operands_that_mismatch_the_plan():
    q, kc, vc, pos = _decode_ops()
    spec = ops.AttnSpec(mode="decode", group=2)
    pl = ops.attn_plan(spec, (2, 256, 4, 2, 64))
    with pytest.raises(ValueError, match="pos"):
        ops.attn_execute(pl, q, kc, vc)             # decode needs pos
    with pytest.raises(ValueError, match="q shape"):
        ops.attn_execute(pl, q[:1], kc, vc, pos=pos)
    with pytest.raises(ValueError, match="k shape"):
        ops.attn_execute(pl, q, kc[:, :128], vc, pos=pos)
    with pytest.raises(ValueError, match="dtype"):
        ops.attn_execute(pl, q.astype(jnp.float32), kc, vc, pos=pos)
    with pytest.raises(ValueError, match="prefill-only"):
        ops.attn_execute(pl, q, kc, vc, pos=pos, scale=0.5)


# ---------------------------------------------------------------------------
# Cost model: plan/explain vs bandwidth billing on the decode-32k shape
# ---------------------------------------------------------------------------

def test_decode_32k_plan_agrees_with_decode_kv_billing(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    b, skv, hq, hkv, d = 4, 32768, 15, 5, 64
    pl = ops.attn_plan(ops.AttnSpec(mode="decode", group=3),
                       (b, skv, hq, hkv, d))
    assert pl.kernel == "flash_decode"
    kv = bandwidth.decode_kv_bytes([skv - 1] * b, n_kv_heads=hkv,
                                   head_dim=d, dtype="bfloat16")
    q_o = 2 * b * hq * d * 2                # q read + o write, bf16
    assert pl.hbm_bytes == pytest.approx(kv + q_o)
    # roofline verdict is max(compute, memory) under effective rates
    from repro.core.hardware import TPU_V5E
    peak, bw = bandwidth.effective_rates(TPU_V5E, False)
    assert pl.traffic.t_model == pytest.approx(
        max(pl.flops / peak, pl.hbm_bytes / bw))
    assert pl.traffic.bound == "memory"     # decode at 32k always is
    assert "true positions" in pl.explain()


def test_paged_decode_plan_bills_page_rounded_kv(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    b, mp, ps, hq, hkv, d = 4, 256, 128, 15, 5, 64
    pl = ops.attn_plan(ops.AttnSpec(mode="decode_paged", group=3),
                       (b, mp, ps, hq, hkv, d))
    assert pl.kernel == "flash_decode_paged"
    kv = bandwidth.decode_kv_bytes([mp * ps - 1] * b, n_kv_heads=hkv,
                                   head_dim=d, dtype="bfloat16",
                                   page_size=ps)
    q_o = 2 * b * hq * d * 2
    assert pl.hbm_bytes == pytest.approx(kv + q_o)
    assert "page-rounded" in pl.explain()


def test_prefill_traffic_rewards_larger_q_blocks():
    # bigger bq -> fewer kv re-streams: the gradient the block DSE uses
    p = attn_api.AttnProblem(mode="prefill", b=1, sq=4096, skv=4096,
                             hq=8, hkv=8, d=64)
    small = attn_api.attn_traffic(p, "flash_attention", 128, 512)
    big = attn_api.attn_traffic(p, "flash_attention", 1024, 512)
    assert big.hbm_bytes < small.hbm_bytes
    assert big.flops == small.flops         # mask math is block-free


def test_solve_topk_is_vmem_feasible_and_ranked():
    spec = ops.AttnSpec()
    designs = ops.attn_solve_topk(spec, (1, 4096, 4096, 8, 8, 128), k=5)
    assert designs
    ts = [d.traffic.t_model for d in designs]
    assert ts == sorted(ts)
    for d in designs:
        assert fits_vmem_bytes(d.vmem.total, attn_api.TPU_V5E)


# ---------------------------------------------------------------------------
# Autotune: measured block winners through the persistent attn| namespace
# ---------------------------------------------------------------------------

def test_attn_autotune_roundtrip_persistent_cache(tmp_path, monkeypatch):
    from repro import tune
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    monkeypatch.setenv("REPRO_TUNE_CACHE",
                       str(tmp_path / "tune_cache.json"))
    tune.tuning_cache_reset()
    q, k, v = _qkv(sq=128, skv=2048)        # blocked path: tunable
    ops.attention(q, k, v, tune=True)
    (pl,) = ops.attn_plans()
    assert pl.source == "tuned" and not pl.tuned.from_cache
    assert pl.tuned.k_searched >= 1
    info = tune.tuning_cache_info()
    assert info.measurements == 1
    (key,) = tune.tuning_cache().entries().keys()
    assert key.startswith("attn|") and key.endswith("|ref")

    # second process over the same file: zero re-measurement
    tune.tuning_cache_reset()
    ops.attn_plan_cache_clear()
    ops.attention(q, k, v, tune=True)
    (pl2,) = ops.attn_plans()
    assert pl2.source == "tuned" and pl2.tuned.from_cache
    assert tune.tuning_cache_info().measurements == 0
    assert (pl2.bq, pl2.bkv) == (pl.bq, pl.bkv)
    assert f"{pl2.tuned.t_measured_us:.1f} us measured" in pl2.explain()
    tune.tuning_cache_reset()


def test_attn_autotune_batch_proxy_scales_down_not_out():
    from repro.tune import autotune
    p = attn_api.AttnProblem(mode="prefill", b=256, sq=4096, skv=4096,
                             hq=15, hkv=5, d=64)
    spec = ops.AttnSpec(group=3)
    shapes = (256, 4096, 4096, 15, 5, 64)
    got = autotune._attn_proxy_shapes(spec, shapes, p, 5e10)
    assert got is not None
    proxy_shapes, measured_b = got
    assert measured_b < 256 and proxy_shapes[0] == measured_b
    assert proxy_shapes[1:] == shapes[1:]
    # per-b flops above the budget: nothing measurable at all
    assert autotune._attn_proxy_shapes(spec, shapes, p, 1e7) is None


# ---------------------------------------------------------------------------
# The public surface rides repro.ops
# ---------------------------------------------------------------------------

def test_ops_exports_the_attention_api():
    for name in ("AttnSpec", "AttnPlan", "AttnProblem", "attn_plan",
                 "attn_execute", "attn_plans", "attn_plan_cache_info",
                 "attn_plan_cache_clear", "attn_solve_topk", "attention",
                 "decode_attention", "decode_attention_paged"):
        assert hasattr(ops, name), name
