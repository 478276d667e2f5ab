"""repro.dist.sharding mechanism + choose_layout DSE policy tests
(beyond the spec-level coverage in tests/test_layout.py)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.dist import layout, sharding as shd
from tests.test_layout import MESH, MESH_POD


# ---------------------------------------------------------------- mesh stack

def test_use_mesh_nesting_and_restore():
    assert shd.current_mesh() is None
    with shd.use_mesh(MESH) as outer:
        assert shd.current_mesh() is outer is MESH
        with shd.use_mesh(MESH_POD):
            assert shd.current_mesh() is MESH_POD
        assert shd.current_mesh() is MESH
    assert shd.current_mesh() is None


def test_use_mesh_restores_on_exception():
    with pytest.raises(RuntimeError):
        with shd.use_mesh(MESH):
            raise RuntimeError("boom")
    assert shd.current_mesh() is None


def test_axis_sizes_duck_typed():
    assert shd.axis_sizes(MESH_POD) == {"pod": 2, "data": 16, "model": 16}
    assert shd.axis_sizes(None) == {}


# ---------------------------------------------------------------------- act

def test_act_is_noop_without_mesh():
    x = jnp.ones((4, 8, 16))
    assert shd.act(x, ("batch", "seq", None)) is x


def test_act_is_noop_on_duck_typed_mesh():
    # spec-level FakeMesh must never reach with_sharding_constraint
    x = jnp.ones((4, 8, 16))
    with shd.use_mesh(MESH):
        assert shd.act(x, ("batch", None, "model")) is x


def test_act_is_noop_on_trivial_real_mesh():
    mesh = shd.make_mesh((1, 1), ("data", "model"))
    x = jnp.ones((4, 8))
    with shd.use_mesh(mesh):
        assert shd.act(x, ("batch", None)) is x


def test_act_rank_mismatch_is_noop():
    x = jnp.ones((4, 8))
    with shd.use_mesh(MESH):
        assert shd.act(x, ("batch", "seq", None)) is x


# ------------------------------------------------------ logical resolution

def test_logical_spec_resolution_and_relaxation():
    sizes = shd.axis_sizes(MESH_POD)
    # batch -> widest dividing combo; seq -> model; non-dividing relaxes
    assert shd.logical_spec((64, 32, 10), ("batch", "seq", None), sizes) \
        == P(("pod", "data"), "model", None)
    # rows=16: 'pod'*'data'=32 doesn't divide, suffix ('data',) does
    assert shd.logical_spec((16, 32), ("batch", "seq"), sizes) \
        == P("data", "model")
    # nothing divides -> fully replicated
    assert shd.logical_spec((3, 5), ("batch", "seq"), sizes) == P(None, None)


def test_logical_spec_never_reuses_a_mesh_axis():
    sizes = shd.axis_sizes(MESH)
    # both 'expert' and 'seq' resolve to 'model'; second claim drops
    s = shd.logical_spec((16, 16, 8), ("expert", "seq", None), sizes)
    assert s == P("model", None, None)


def test_seq_shard_toggle(monkeypatch):
    sizes = shd.axis_sizes(MESH)
    monkeypatch.setenv("REPRO_SEQ_SHARD", "0")
    assert shd.resolve_axis("seq", 32, sizes) is None
    monkeypatch.delenv("REPRO_SEQ_SHARD")
    assert shd.resolve_axis("seq", 32, sizes) == "model"


# ----------------------------------------------------------- choose_layout

def test_choose_layout_tp_over_dp_when_param_bytes_dominate():
    cfg = get_config("smollm-360m")
    scored = layout.score_layouts(cfg)
    assert scored["dp"]["feasible"] and scored["tp"]["feasible"]
    # per-device bytes dominate dp's score; tp shards them 16x
    assert scored["tp"]["score"] < scored["dp"]["score"]
    assert layout.choose_layout(cfg) == "tp"


def test_choose_layout_infeasible_tiers_fall_to_max_sharding():
    cfg = get_config("kimi-k2-1t-a32b")
    scored = layout.score_layouts(cfg)
    assert not any(v["feasible"] for v in scored.values())
    assert layout.choose_layout(cfg) == "fsdp_tp"


def test_score_layouts_memory_ordering():
    scored = layout.score_layouts(get_config("deepseek-67b"))
    mem = {s: v["mem_bytes_per_device"] for s, v in scored.items()}
    assert mem["fsdp_tp"] < mem["tp"] <= mem["dp"]
    assert mem["fsdp_tp"] < mem["fsdp"] <= mem["dp"]


def test_spec_for_rejects_unknown_strategy():
    with pytest.raises(ValueError):
        layout.spec_for("lm_head", (8, 8), "zz_not_a_strategy",
                        {"data": 2, "model": 2})


# ------------------------------------------------- end-to-end on a real mesh

_ACT_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.dist import sharding as shd

mesh = shd.make_mesh((2, 4), ("data", "model"))
x = jnp.ones((4, 8, 16))

def f(x):
    return shd.act(x, ("batch", None, "model")) * 2.0

with shd.use_mesh(mesh):
    y = jax.jit(f)(x)
assert y.shape == x.shape and float(y[0, 0, 0]) == 2.0
# the constraint must actually land: last dim sharded 4-way over 'model'
shard_shapes = {s.data.shape for s in y.addressable_shards}
assert shard_shapes == {(2, 8, 4)}, shard_shapes
print("ACT-OK", sorted(shard_shapes))
"""


def test_act_applies_constraint_under_jit_multidevice():
    """act() must emit a real sharding constraint — run on a forced
    8-device CPU mesh in a subprocess (parent stays single-device)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _ACT_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))),
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ACT-OK" in r.stdout


_PER_DEVICE_SCRIPT = r"""
import jax, jax.numpy as jnp
import numpy as np
from repro import ops
from repro.dist import sharding as shd

mesh = shd.make_mesh((2, 2), ("data", "model"))
k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 4)
a = jax.random.normal(k0, (64, 96), jnp.float32)
b = jax.random.normal(k1, (96, 256), jnp.float32)
u = jax.random.normal(k2, (96, 256), jnp.float32)
r = jax.random.normal(k3, (64, 256), jnp.float32)
q = jax.random.normal(k0, (2, 128, 4, 32), jnp.float32)
kv = jax.random.normal(k1, (2, 128, 2, 32), jnp.float32)

def f(a, b, u, r, q, kv):
    y = ops.gemm(a, b, residual=r)
    z = ops.gemm(a, b, b2=u, activation="silu")
    o = ops.attention(q, kv, kv, causal=True)
    g = jax.grad(lambda a: jnp.sum(ops.gemm(a, b) ** 2))(a)
    return y, z, o, g

args = (a, b, u, r, q, kv)
want = jax.jit(f)(*args)
with shd.use_mesh(mesh):
    # a fresh jit: the one above holds the trace made without a mesh
    sharded = jax.jit(lambda *xs: f(*xs))
    text = sharded.lower(*args).as_text()
    got = sharded(*args)
# one per-device program per Pallas call, forward and backward
assert text.count("sdy.manual_computation(") >= 4, text.count(
    "sdy.manual_computation(")
# GEMM blocks are (rows / data, cols / model); attention splits batch
assert "tensor<32x96xf32>" in text and "tensor<1x128x4x32xf32>" in text
for w, g in zip(want, got):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                               rtol=1e-5, atol=1e-4)
print("PER-DEVICE-OK")
"""


def test_pallas_kernels_run_per_device_under_a_mesh():
    """A Pallas call under a multi-device mesh runs per device through
    shard_map (GSPMD cannot partition a Mosaic kernel) and gives the
    unsharded result: forward GEMM with epilogue, gated GEMM, flash
    attention and a GEMM backward, interpret mode on 4 CPU devices."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    env["REPRO_KERNELS"] = "interpret"
    r = subprocess.run([sys.executable, "-c", _PER_DEVICE_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))),
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PER-DEVICE-OK" in r.stdout


def test_per_device_runs_as_is_without_a_mesh():
    calls = []

    def fn(x, y):
        calls.append((x.shape, y))
        return x * 2

    x = jnp.ones((4, 8))
    out = shd.per_device(fn, (x, None), (("batch", None), None),
                         ("batch", None))
    assert calls == [((4, 8), None)]
    assert float(out[0, 0]) == 2.0
