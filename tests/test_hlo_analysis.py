"""Validate the trip-count-aware HLO cost parser against XLA's own numbers
on loop-free (unrolled) modules, and its loop handling on scanned ones."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import analyze_hlo
from repro.analysis.roofline import roofline_terms


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _xla_cost(compiled) -> dict:
    """XLA's own cost numbers for a single-device module."""
    return compiled.cost_analysis()


class TestFlops:
    def test_matches_xla_on_unrolled(self):
        D, F, L = 64, 128, 4

        def f(w1, w2, x):
            for _ in range(L):
                x = jnp.tanh(x @ w1) @ w2
            return x

        w1 = jax.ShapeDtypeStruct((D, F), jnp.float32)
        w2 = jax.ShapeDtypeStruct((F, D), jnp.float32)
        x = jax.ShapeDtypeStruct((32, D), jnp.float32)
        compiled = _compile(f, w1, w2, x)
        mine = analyze_hlo(compiled.as_text())
        xla = _xla_cost(compiled)["flops"]
        # dot flops dominate; tanh etc. not counted by our parser
        expected_dots = L * (2 * 32 * D * F + 2 * 32 * F * D)
        assert mine.flops == pytest.approx(expected_dots, rel=1e-6)
        assert mine.flops == pytest.approx(xla, rel=0.05)

    def test_scan_trip_count_correction(self):
        D, F, L = 32, 64, 12

        def f(stack, x):
            def body(h, w):
                w1, w2 = w
                return jnp.tanh(h @ w1) @ w2, None
            h, _ = jax.lax.scan(body, x, stack)
            return h

        stack = (jax.ShapeDtypeStruct((L, D, F), jnp.float32),
                 jax.ShapeDtypeStruct((L, F, D), jnp.float32))
        x = jax.ShapeDtypeStruct((8, D), jnp.float32)
        compiled = _compile(f, stack, x)
        mine = analyze_hlo(compiled.as_text())
        xla_once = _xla_cost(compiled)["flops"]
        expected = L * (2 * 8 * D * F + 2 * 8 * F * D)
        assert mine.flops == pytest.approx(expected, rel=1e-6)
        # XLA counts the body once — our multiplier fixes exactly that
        assert mine.flops == pytest.approx(xla_once * L, rel=0.05)
        assert L in [t for t in mine.while_trip_counts.values()]

    def test_nested_scans_multiply(self):
        D, INNER, OUTER = 16, 3, 5

        def f(w, x):
            def outer(h, _):
                def inner(h2, _):
                    return h2 @ w, None
                h, _ = jax.lax.scan(inner, h, None, length=INNER)
                return h, None
            h, _ = jax.lax.scan(outer, x, None, length=OUTER)
            return h

        w = jax.ShapeDtypeStruct((D, D), jnp.float32)
        x = jax.ShapeDtypeStruct((4, D), jnp.float32)
        compiled = _compile(f, w, x)
        mine = analyze_hlo(compiled.as_text())
        expected = OUTER * INNER * 2 * 4 * D * D
        assert mine.flops == pytest.approx(expected, rel=1e-6)


class TestCollectives:
    def test_allreduce_bytes(self):
        import os
        n_dev = len(jax.devices())
        if n_dev < 2:
            pytest.skip("needs >1 device (dry-run env has 512)")
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((n_dev,), ("d",))
        from jax.sharding import NamedSharding, PartitionSpec as P

        def f(x):
            return jnp.sum(x)

        x = jax.ShapeDtypeStruct((n_dev * 128,), jnp.float32,
                                 sharding=NamedSharding(mesh, P("d")))
        compiled = jax.jit(f).lower(x).compile()
        mine = analyze_hlo(compiled.as_text())
        assert mine.n_collectives >= 1
        assert mine.collective_bytes > 0


class TestRoofline:
    def test_terms_and_bottleneck(self):
        t = roofline_terms(197e12, 100e9, 1e9)  # 1s compute, .12s mem, .02s coll
        assert t["bottleneck"] == "compute"
        assert t["roofline_fraction"] == pytest.approx(1.0)
        t = roofline_terms(1e12, 819e9, 0.0)
        assert t["bottleneck"] == "memory"
        assert t["step_lower_bound_s"] == pytest.approx(1.0)
