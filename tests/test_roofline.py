"""The loop-aware HLO analyzer behind the roofline deliverable."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import roofline as R


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_matches_xla_cost_analysis_on_scanfree_graph():
    f = lambda x, w: (jnp.tanh(x @ w) @ w).sum()  # noqa: E731
    c = _compile(f, jax.ShapeDtypeStruct((256, 128), jnp.float32),
                 jax.ShapeDtypeStruct((128, 128), jnp.float32))
    ours = R.analyze_hlo(c.as_text())["flops"]
    xla = c.cost_analysis()["flops"]
    assert abs(ours - xla) / xla < 0.01, (ours, xla)


@pytest.mark.parametrize("n", [2, 8, 32])
def test_scales_with_scan_trip_count(n):
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=n)
        return out.sum()
    c = _compile(f, jax.ShapeDtypeStruct((128, 128), jnp.float32),
                 jax.ShapeDtypeStruct((128, 128), jnp.float32))
    got = R.analyze_hlo(c.as_text())["flops"]
    expect = 2 * 128 ** 3 * n
    assert abs(got - expect) / expect < 0.05, (got, expect, n)


def test_nested_scans_multiply():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=4)
            return ci, None
        out, _ = jax.lax.scan(outer, x, None, length=3)
        return out.sum()
    c = _compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                 jax.ShapeDtypeStruct((64, 64), jnp.float32))
    got = R.analyze_hlo(c.as_text())["flops"]
    expect = 2 * 64 ** 3 * 12
    assert abs(got - expect) / expect < 0.05, (got, expect)


def test_scan_sliced_reads_not_charged_full_buffer():
    """A scan that dynamic-slices one row per step from a big stacked input
    must charge ~row bytes per step, not the whole buffer."""
    T, D = 512, 256

    def f(xs, w):
        def body(c, i):
            row = jax.lax.dynamic_slice_in_dim(xs, i * D // D, 1, 0)
            return c + (row[0] * w), None
        c, _ = jax.lax.scan(body, jnp.zeros((D,), jnp.float32),
                            jnp.arange(T))
        return c.sum()
    c = _compile(f, jax.ShapeDtypeStruct((T, D), jnp.float32),
                 jax.ShapeDtypeStruct((D,), jnp.float32))
    hbm = R.analyze_hlo(c.as_text())["hbm_bytes"]
    full_buffer_everystep = T * (T * D * 4)
    assert hbm < full_buffer_everystep / 20, hbm


def test_hardware_constants():
    assert R.PEAK_FLOPS == 197e12
    assert R.HBM_BW == 819e9
    assert R.ICI_BW == 50e9
    t = {"compute_s": 1.0, "memory_s": 2.0, "collective_s": 0.5}
    assert R.dominant_term(t) == "memory_s"


def test_parse_replica_groups():
    assert R._group_size("replica_groups=[2,8]<=[16]", 99) == 8
    assert R._group_size("replica_groups={{0,1,2,3}}", 99) == 4
    assert R._group_size("no groups here", 7) == 7
