"""Loop-aware HLO cost model: validated against XLA + hand counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze


def _compile(fn, *shapes):
    return jax.jit(fn).lower(
        *[jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    ).compile()


def test_loop_free_matches_xla_exactly():
    def f(a, b):
        return jax.nn.relu(a @ b) @ b.T

    comp = _compile(f, (256, 512), (512, 512))
    mine = analyze(comp.as_text())["flops"]
    ca = comp.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax: one dict per partition
        ca = ca[0]
    xla = ca["flops"]
    assert mine == pytest.approx(xla, rel=1e-6)


def test_scan_multiplied_by_trip_count():
    def g(x):
        def body(c, _):
            return c @ jnp.ones((128, 128)), None
        c, _ = jax.lax.scan(body, x, None, length=10)
        return c

    comp = _compile(g, (128, 128))
    flops = analyze(comp.as_text())["flops"]
    # 10 × 2·128³ plus epsilon of elementwise
    assert flops == pytest.approx(10 * 2 * 128**3, rel=0.01)


def test_nested_scan():
    def nested(x):
        def outer(c, _):
            def inner(d, _):
                return d @ jnp.ones((128, 128)), None
            d, _ = jax.lax.scan(inner, c, None, length=5)
            return d, None
        c, _ = jax.lax.scan(outer, x, None, length=4)
        return c

    comp = _compile(nested, (128, 128))
    flops = analyze(comp.as_text())["flops"]
    assert flops == pytest.approx(20 * 2 * 128**3, rel=0.01)


def test_hbm_fusion_internals_not_charged():
    """A fused chain of k elementwise ops touches HBM ~once, not k times."""
    def f(a):
        x = a * 2 + 1
        x = jnp.tanh(x) * a
        return x + 3

    comp = _compile(f, (1 << 16,))
    hbm = analyze(comp.as_text())["hbm_bytes"]
    nbytes = (1 << 16) * 4
    # in + out (+ slack for any unfused remainder): well under 5 ops' worth
    assert hbm <= 4 * nbytes


def test_collective_accounting():
    import os
    import subprocess
    import sys
    import textwrap
    from helpers import run_with_devices

    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import analyze
        mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
        sh = NamedSharding(mesh, P("data"))
        def f(a):
            return jnp.sum(a)  # all-reduce of a scalar across 4 devices
        comp = jax.jit(f, in_shardings=(sh,)).lower(
            jax.ShapeDtypeStruct((64, 32), jnp.float32)).compile()
        a = analyze(comp.as_text())
        ar = a["collective_wire_bytes"]["all-reduce"]
        # ring all-reduce of a 4-byte scalar over 4 devices: 2·4·(3/4) = 6 B
        assert 0 < ar <= 64, ar
        print("collective ok", ar)
    """, num_devices=4)
    assert "collective ok" in out


def test_transcendental_counting():
    def f(a):
        return jnp.sum(jnp.exp(a))

    comp = _compile(f, (1024,))
    t = analyze(comp.as_text())["transcendentals"]
    assert t == pytest.approx(1024, rel=0.05)


def test_overlap_stats_window_vs_tail():
    """A permute consumed by real compute gets a measured hidden window; a
    permute that only escapes through the ROOT tuple is a tail permute."""
    from repro.launch.hlo_analysis import overlap_stats

    hlo = """
ENTRY %main (p0: f32[8,8], p1: f32[8,8]) -> (f32[8,8], f32[8,8]) {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %cp.0 = f32[8,8]{1,0} collective-permute(f32[8,8]{1,0} %p0), source_target_pairs={{0,1},{1,0}}
  %dot.0 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %p1, f32[8,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %dot.1 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %cp.0, f32[8,8]{1,0} %dot.0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %cp.1 = f32[8,8]{1,0} collective-permute(f32[8,8]{1,0} %dot.1), source_target_pairs={{0,1},{1,0}}
  %dot.2 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %dot.0, f32[8,8]{1,0} %dot.0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %out = (f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(f32[8,8]{1,0} %cp.1, f32[8,8]{1,0} %dot.2)
}
"""
    o = overlap_stats(hlo)
    assert o["collective_permutes"] == 2
    # cp.0's window hides dot.0 (2·8³ flops) before dot.1 consumes it
    assert o["hidden_flops"] == pytest.approx(2 * 8**3)
    # cp.1 only reaches the ROOT tuple → tail, its window is NOT measured
    assert o["tail_permutes"] == 1
