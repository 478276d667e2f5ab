"""Validation of the loop-corrected HLO cost parser.

Ground truth: ``compiled.cost_analysis()`` is exact on modules WITHOUT
while loops (fully unrolled) — the parser must agree there.  On scanned
modules XLA counts loop bodies once; the parser must recover the
trip-count-scaled totals.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.core import hlo_cost

MM = 2 * 256 ** 3      # flops of one 256^3 matmul


def _compile(f, *structs):
    return jax.jit(f).lower(*structs).compile()


def _structs(*shapes, dtype=jnp.float32):
    return [jax.ShapeDtypeStruct(s, dtype) for s in shapes]


def step(c, w):
    return jnp.tanh(c @ w), None


class TestFlops:
    def test_unrolled_matches_xla(self):
        def f(x, ws):
            y, _ = jax.lax.scan(step, x, ws, unroll=5)
            return y
        c = _compile(f, *_structs((256, 256), (5, 256, 256)))
        got = hlo_cost.analyze_text(c.as_text()).flops
        want = c.cost_analysis()["flops"]
        assert got == pytest.approx(want, rel=0.05)

    def test_scan_scales_by_trip_count(self):
        def f(x, ws):
            y, _ = jax.lax.scan(step, x, ws)
            return y
        c = _compile(f, *_structs((256, 256), (7, 256, 256)))
        cost = hlo_cost.analyze_text(c.as_text())
        assert cost.flops == pytest.approx(7 * MM, rel=0.01)
        # XLA's own count misses the loop:
        assert c.cost_analysis()["flops"] == pytest.approx(MM, rel=0.01)

    def test_nested_scan_multiplies(self):
        def inner(c, w):
            y, _ = jax.lax.scan(step, c, w)
            return y, None

        def f(x, ws):
            y, _ = jax.lax.scan(inner, x, ws)
            return y
        c = _compile(f, *_structs((256, 256), (3, 4, 256, 256)))
        cost = hlo_cost.analyze_text(c.as_text())
        assert cost.flops == pytest.approx(12 * MM, rel=0.01)

    def test_grad_scan(self):
        def loss(x, ws):
            y, _ = jax.lax.scan(step, x, ws)
            return y.sum()
        c = _compile(jax.grad(loss), *_structs((256, 256),
                                               (5, 256, 256)))
        cost = hlo_cost.analyze_text(c.as_text())
        # fwd 5 + bwd d/dx 5 (grad wrt arg0 only)
        assert cost.flops == pytest.approx(10 * MM, rel=0.05)

    def test_dot_general_contracting_dims(self):
        def f(a, b):
            return jnp.einsum("bik,bkj->bij", a, b)
        c = _compile(f, *_structs((4, 64, 128), (4, 128, 32)))
        cost = hlo_cost.analyze_text(c.as_text())
        assert cost.flops == pytest.approx(2 * 4 * 64 * 128 * 32,
                                           rel=0.01)


class TestBytes:
    def test_unrolled_within_2x_of_xla(self):
        def f(x, ws):
            y, _ = jax.lax.scan(step, x, ws, unroll=5)
            return y
        c = _compile(f, *_structs((256, 256), (5, 256, 256)))
        got = hlo_cost.analyze_text(c.as_text()).bytes_accessed
        want = c.cost_analysis()["bytes accessed"]
        assert want * 0.5 <= got <= want * 2.5

    def test_scan_weight_reads_not_overcounted(self):
        # a scan slicing one (256,256) weight per step must charge ~1
        # slice per iteration, not the whole (N,256,256) stack
        n = 16
        def f(x, ws):
            y, _ = jax.lax.scan(step, x, ws)
            return y
        c = _compile(f, *_structs((256, 256), (n, 256, 256)))
        cost = hlo_cost.analyze_text(c.as_text())
        stack_bytes = n * 256 * 256 * 4
        # each iteration touches ~7 slice-sized tensors (dot operands,
        # tanh, carry copies) = ~7/16 stack; charging the FULL stack per
        # iteration would be ~16 stacks — assert we're far below that
        assert cost.bytes_accessed < 8 * stack_bytes


class TestCollectives:
    def test_psum_in_scan_scales(self):
        if len(jax.devices()) < 1:
            pytest.skip("needs devices")
        from repro.dist import sharding as shd
        mesh = shd.make_mesh((1,), ("x",))
        from jax.sharding import NamedSharding, PartitionSpec as P

        def f(x):
            def body(c, _):
                # c + psum keeps the carry 'varying' under shard_map's
                # replication typing
                return (c + jax.lax.psum(c, "x")) * 0.5, None
            y, _ = jax.lax.scan(body, x, None, length=9)
            return y

        g = jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                          out_specs=P("x"), check_vma=True)
        c = jax.jit(g).lower(
            jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile()
        cost = hlo_cost.analyze_text(c.as_text())
        ar = cost.collective_bytes["all-reduce"]
        assert ar == pytest.approx(9 * 8 * 128 * 4, rel=0.01)

    def test_trip_counts_recovered(self):
        def f(x, ws):
            y, _ = jax.lax.scan(step, x, ws)
            return y
        c = _compile(f, *_structs((256, 256), (11, 256, 256)))
        cost = hlo_cost.analyze_text(c.as_text())
        assert 11 in cost.trip_counts.values()


class TestScopes:
    def test_named_scope_attribution(self):
        @jax.jit
        def inner_fn(a, b):
            return a @ b

        def f(a, b):
            # second matmul must differ or XLA CSEs the two dots
            return inner_fn(a, b) + a @ b.T
        c = _compile(f, *_structs((256, 256), (256, 256)))
        cost = hlo_cost.analyze_text(c.as_text())
        assert cost.flops == pytest.approx(2 * MM, rel=0.01)
        assert "inner_fn" in cost.flops_by_scope
        assert cost.flops_by_scope["inner_fn"] == pytest.approx(
            MM, rel=0.01)
