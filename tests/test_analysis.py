"""HLO analyzer + logical-sharding-rule units (roofline correctness)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import analyze_hlo, shape_bytes, shape_elems
from repro.distributed.logical import logical_rules, spec_for, constrain


class TestShapeParsing:
    def test_shape_bytes(self):
        assert shape_bytes("f32[256,512]") == 256 * 512 * 4
        assert shape_bytes("bf16[8,128]{1,0}") == 8 * 128 * 2
        assert shape_bytes("(f32[4], s32[2,2])") == 16 + 16
        assert shape_bytes("pred[]") == 1

    def test_shape_elems(self):
        assert shape_elems("f32[3,5,7]") == 105


class TestAnalyzeHLO:
    def test_scan_flops_scale_with_trip_count(self):
        """The core roofline fix: while bodies × known_trip_count."""
        def f(x, ws):
            def step(c, w):
                return c @ w, None
            return jax.lax.scan(step, x, ws)[0]

        B, D = 64, 32
        for L in (2, 4, 8):
            c = jax.jit(f).lower(jnp.zeros((B, D)),
                                 jnp.zeros((L, D, D))).compile()
            res = analyze_hlo(c.as_text())
            analytic = L * 2 * B * D * D
            assert res.dot_flops == pytest.approx(analytic, rel=0.01), L

    def test_plain_matmul_flops_exact(self):
        c = jax.jit(lambda a, b: a @ b).lower(
            jnp.zeros((128, 64)), jnp.zeros((64, 32))).compile()
        res = analyze_hlo(c.as_text())
        assert res.dot_flops == pytest.approx(2 * 128 * 64 * 32, rel=0.01)

    def test_nested_scan_multiplies(self):
        def f(x, ws):
            def outer(c, w):
                def inner(ci, _):
                    return ci @ w, None
                return jax.lax.scan(inner, c, None, length=3)[0], None
            return jax.lax.scan(outer, x, ws)[0]

        B, D, L = 16, 8, 4
        c = jax.jit(f).lower(jnp.zeros((B, D)),
                             jnp.zeros((L, D, D))).compile()
        res = analyze_hlo(c.as_text())
        assert res.dot_flops == pytest.approx(L * 3 * 2 * B * D * D, rel=0.01)

    def test_no_collectives_on_single_device(self):
        c = jax.jit(lambda a: a @ a.T).lower(jnp.zeros((32, 32))).compile()
        res = analyze_hlo(c.as_text())
        assert res.collective_bytes == 0.0


class TestLogicalRules:
    def _mesh(self):
        return jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)

    def test_noop_without_policy(self):
        x = jnp.ones((4, 8))
        assert constrain(x, "batch", "embed") is x

    def test_divisibility_drops_axis(self):
        mesh = jax.make_mesh(
            (1, 1), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with logical_rules(mesh, {"heads": "model", "batch": "data"}):
            # heads=24 % model size 1 == 0 -> kept (size-1 axis trivially ok)
            spec = spec_for((2, 24), ("batch", "heads"))
            assert spec is not None

    def test_duplicate_axis_never_emitted(self):
        """The deepseek DuplicateSpecError regression."""
        mesh = self._mesh()
        with logical_rules(mesh, {"experts": ("model", "data"),
                                  "moe_ff": "model"}):
            spec = spec_for((4, 8, 16), ("experts", "capacity", "moe_ff"))
            flat = []
            for s in spec:
                flat.extend(s if isinstance(s, tuple) else [s])
            named = [a for a in flat if a]
            assert len(named) == len(set(named))

    def test_wrong_rank_is_noop(self):
        mesh = self._mesh()
        with logical_rules(mesh, {"batch": "data"}):
            x = jnp.ones((4, 8, 2))
            assert constrain(x, "batch", "embed") is x
