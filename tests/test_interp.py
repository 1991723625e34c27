"""Scattered-data interpolation (paper §2.3.1): the XLA oracle path."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import grid as G
from repro.core import interp as I

SHAPE = (16, 12, 8)


@pytest.mark.parametrize("method", I.METHODS)
def test_exact_at_grid_points(method, rng):
    f = jax.random.normal(rng, SHAPE, jnp.float32)
    q = G.index_coords(SHAPE)
    out = I.interp_field(f, q, method)
    # 5e-4: the cubic-bspline prefilter accumulates float32 roundoff whose
    # exact magnitude varies with the XLA backend's reduction order.
    np.testing.assert_allclose(out, f, rtol=5e-4, atol=5e-4)


def test_trilinear_reproduces_linear_field():
    """Trilinear interpolation is exact on (locally) linear functions."""
    n = 16
    f = jnp.arange(n, dtype=jnp.float32).reshape(n, 1, 1) * jnp.ones((n, n, n))
    q = G.index_coords((n, n, n)) + 0.3
    q = q.at[0].set(jnp.clip(q[0], 0, n - 1.5))  # stay off the wrap seam
    out = I.interp_linear(f, q)
    expect = jnp.clip(jnp.arange(n, dtype=jnp.float32) + 0.3, 0, n - 1.5)
    expect = expect.reshape(n, 1, 1) * jnp.ones((n, n, n))
    np.testing.assert_allclose(out, expect, atol=1e-4)


def test_prefilter_fir_matches_fft():
    """The 15-point finite convolution ~ exact spectral prefilter (the
    paper's Champagnat & Le Sant truncation; |h_7/h_0| ~ 1e-4)."""
    f = jax.random.normal(jax.random.PRNGKey(2), (24, 16, 12), jnp.float32)
    a = I.prefilter_fir(f)
    b = I.prefilter_fft(f)
    rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    assert rel < 5e-4


def test_bspline_interpolates_after_prefilter():
    """B-spline with prefiltered coefficients reproduces grid values."""
    f = jax.random.normal(jax.random.PRNGKey(3), SHAPE, jnp.float32)
    q = G.index_coords(SHAPE)
    out = I.interp_cubic_bspline(f, q, prefiltered=False)
    np.testing.assert_allclose(out, f, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("method,tol", [
    ("linear", 2.5e-2), ("cubic_lagrange", 2e-3), ("cubic_bspline", 1.5e-3)])
def test_smooth_function_accuracy_ordering(method, tol):
    """Cubic methods beat trilinear on a smooth synthetic field (paper
    Table 4); B-spline ~2x more accurate than Lagrange on real-ish data."""
    shape = (32, 32, 32)
    x = G.coords(shape)
    f = (jnp.sin(2 * x[0]) ** 2 + jnp.sin(1 * x[1]) ** 2
         + jnp.sin(2 * x[2]) ** 2) / 3.0
    key = jax.random.PRNGKey(4)
    q = G.index_coords(shape) + jax.random.uniform(key, (3,) + shape,
                                                   minval=-0.5, maxval=0.5)
    h = G.spacing(shape)
    xq = jnp.stack([q[i] * h[i] for i in range(3)])
    expect = (jnp.sin(2 * xq[0]) ** 2 + jnp.sin(1 * xq[1]) ** 2
              + jnp.sin(2 * xq[2]) ** 2) / 3.0
    out = I.interp_field(f, q, method)
    err = float(jnp.sqrt(jnp.mean((out - expect) ** 2))
                / jnp.sqrt(jnp.mean(expect ** 2)))
    assert err < tol, f"{method}: {err}"


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_periodic_wrap_consistency(seed):
    """Shifting queries by a full period leaves results unchanged."""
    f = jax.random.normal(jax.random.PRNGKey(seed), SHAPE, jnp.float32)
    q = G.index_coords(SHAPE) + 0.37
    out1 = I.interp_field(f, q, "cubic_bspline")
    q_shift = q + jnp.asarray(SHAPE, jnp.float32).reshape(3, 1, 1, 1)
    out2 = I.interp_field(f, q_shift, "cubic_bspline")
    np.testing.assert_allclose(out1, out2, rtol=1e-4, atol=1e-4)


def test_vector_interp_matches_per_component():
    w = jax.random.normal(jax.random.PRNGKey(9), (3,) + SHAPE, jnp.float32)
    q = G.index_coords(SHAPE) - 0.25
    out = I.interp_vector(w, q, "linear")
    for a in range(3):
        np.testing.assert_allclose(out[a], I.interp_linear(w[a], q),
                                   atol=1e-6)


def test_vector_interp_bspline_matches_per_component():
    """The fused (one plan + batched prefilter) vector path reproduces the
    per-component scalar path, including the B-spline prefilter."""
    w = jax.random.normal(jax.random.PRNGKey(10), (3,) + SHAPE, jnp.float32)
    q = G.index_coords(SHAPE) + 0.4
    out = I.interp_vector(w, q, "cubic_bspline")
    for a in range(3):
        np.testing.assert_allclose(
            out[a], I.interp_cubic_bspline(w[a], q), atol=1e-5)


# ---------------------------------------------------------------------------
# Interpolation plans (build once / apply many)
# ---------------------------------------------------------------------------


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), method=st.sampled_from(I.METHODS))
def test_plan_matches_interp_field_fp32(seed, method):
    """apply_plan(build_plan(q), c) == interp_field(c, q) in fp32: the plan
    precomputes exactly the indices/weights the direct path derives per call,
    so the results must agree bitwise-tolerantly."""
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    coef = jax.random.normal(k1, SHAPE, jnp.float32)
    q = G.index_coords(SHAPE) + jax.random.uniform(
        k2, (3,) + SHAPE, minval=-4.0, maxval=4.0)
    ref = I.interp_field(coef, q, method, prefiltered=True)
    out = I.apply_plan(I.build_plan(q, method=method), coef)
    np.testing.assert_allclose(out, ref, rtol=1e-7, atol=1e-7)


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), method=st.sampled_from(I.METHODS))
def test_plan_bf16_weights_close_to_fp32(seed, method):
    """bf16 *weight* downcast (data stays fp32, accumulation fp32) keeps the
    result within bf16 resolution of the full-precision path."""
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    coef = jax.random.normal(k1, SHAPE, jnp.float32)
    q = G.index_coords(SHAPE) + jax.random.uniform(
        k2, (3,) + SHAPE, minval=-2.0, maxval=2.0)
    ref = I.apply_plan(I.build_plan(q, method=method), coef)
    out = I.apply_plan(I.build_plan(q, method=method,
                                    weight_dtype=jnp.bfloat16), coef)
    rel = float(jnp.max(jnp.abs(out - ref)) / (jnp.max(jnp.abs(ref)) + 1e-12))
    assert rel < 3e-2, f"{method}: bf16 weight error {rel}"


def test_plan_batched_apply_matches_per_field():
    """Stacked fields through one plan == one apply per field."""
    w = jax.random.normal(jax.random.PRNGKey(11), (4,) + SHAPE, jnp.float32)
    q = G.index_coords(SHAPE) - 0.6
    plan = I.build_plan(q, method="cubic_lagrange")
    out = I.apply_plan(plan, w)
    assert out.shape == (4,) + SHAPE
    for k in range(4):
        np.testing.assert_allclose(out[k], I.apply_plan(plan, w[k]), atol=0.0)


def test_plan_periodic_wrap_baked_in():
    """Plans bake the periodic wrap into the gather base: shifting queries by
    a full period yields the identical plan application."""
    f = jax.random.normal(jax.random.PRNGKey(12), SHAPE, jnp.float32)
    q = G.index_coords(SHAPE) + 0.37
    shift = jnp.asarray(SHAPE, jnp.float32).reshape(3, 1, 1, 1)
    out1 = I.apply_plan(I.build_plan(q, method="cubic_bspline"), f)
    out2 = I.apply_plan(I.build_plan(q + shift, method="cubic_bspline"), f)
    np.testing.assert_allclose(out1, out2, rtol=1e-5, atol=1e-5)


WINDOW_SHAPE = (16, 12, 20)


def _scalar_tap_oracle(plan, coef):
    """The plan's taps through one scalar ``take`` each, by ``plan.idx``
    (periodic wrap or clamp baked in): what the windowed gather replaces."""
    i1, i2, i3 = plan.idx
    w1, w2, w3 = plan.weights
    f_flat = coef.reshape(coef.shape[:-3] + (-1,))
    acc = 0.0
    for a in range(plan.support):
        for b in range(plan.support):
            for c in range(plan.support):
                vals = jnp.take(f_flat, i1[a] + i2[b] + i3[c], axis=-1)
                acc = acc + (w1[a] * w2[b] * w3[c] * vals).astype(jnp.float32)
    return acc


def _assert_rel(out, ref, tol=1e-6):
    err = float(jnp.max(jnp.abs(out - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    assert err <= tol * scale, f"rel err {err / scale}"


@pytest.mark.parametrize("case", ["seam", "clamped", "bf16", "vmap"])
@pytest.mark.parametrize("lead", [(), (2,), (3,)])
@pytest.mark.parametrize("method", I.METHODS)
def test_windowed_plan_matches_oracle(method, lead, case):
    """The windowed-gather ``apply_plan`` == the plan-free oracle
    (``interp_field``, one scalar gather per tap) to 1e-6 relative, on a
    non-cubic grid, with footpoints several voxels off it across the
    periodic seam, on a clamped (halo) x1 axis, with bf16 weights, and
    vmapped over two plans."""
    shape = WINDOW_SHAPE
    seed = zlib.crc32(f"{method}{lead}{case}".encode())
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    coef = jax.random.normal(k1, (2,) + lead + shape, jnp.float32)
    q = G.index_coords(shape) + jax.random.uniform(
        k2, (2, 3) + shape, minval=-5.0, maxval=5.0)
    wdt = jnp.bfloat16 if case == "bf16" else None
    wrap = (True, True, True)
    if case == "clamped":
        # x1 inside the CFL bound: every tap in range, clamping is a no-op.
        lo, hi = (1.0, shape[0] - 3.0) if method != "linear" else (0.0, shape[0] - 2.0)
        q = q.at[:, 0].set(jnp.clip(q[:, 0], lo, hi - 1e-3))
        wrap = (False, True, True)

    def oracle(f, qq):
        fields = f.reshape((-1,) + shape)
        out = jnp.stack([I.interp_field(g, qq, method, prefiltered=True,
                                        weight_dtype=wdt) for g in fields])
        return out.reshape(f.shape)

    build = lambda qq: I.build_plan(qq, method=method, weight_dtype=wdt,
                                    wrap=wrap)
    if case == "vmap":
        out = jax.vmap(lambda qq, f: I.apply_plan(build(qq), f))(q, coef)
        for k in range(2):
            _assert_rel(out[k], oracle(coef[k], q[k]))
        return
    plan = build(q[0])
    out = I.apply_plan(plan, coef[0])
    assert out.shape == lead + shape and out.dtype == jnp.float32
    _assert_rel(out, oracle(coef[0], q[0]))
    if case == "clamped":
        # Beyond the CFL bound the clamped taps stay those ``idx`` names.
        wide = q[1].at[0].multiply(1.5).at[0].add(-4.0)
        plan = build(wide)
        _assert_rel(I.apply_plan(plan, coef[1]),
                    _scalar_tap_oracle(plan, coef[1]))


@pytest.mark.parametrize("method", I.METHODS)
def test_apply_plan_is_one_gather(method):
    """Structural counter of the windowed gather: the compiled plan
    application at 16^3 with two stacked fields holds one HLO gather (the
    scalar-tap body held support**3, 64 for a cubic plan), and so does its
    vmap over two plans."""
    shape = (16, 16, 16)
    q = G.index_coords(shape) + 0.3
    plan = I.build_plan(q, method=method)
    coef = jnp.zeros((2,) + shape, jnp.float32)

    def count(fn, *args):
        text = jax.jit(fn).lower(*args).compile().as_text()
        return sum(1 for line in text.splitlines() if " gather(" in line)

    assert count(I.apply_plan, plan, coef) == 1
    plans = jax.tree.map(lambda x: jnp.stack([x, x]), plan)
    assert count(jax.vmap(I.apply_plan), plans, jnp.stack([coef, coef])) == 1


def test_prefilter_fir_batched_matches_per_field():
    """The prefilter operates on trailing axes: stacked fields in one pass."""
    w = jax.random.normal(jax.random.PRNGKey(13), (3,) + SHAPE, jnp.float32)
    out = I.prefilter_for(w, "cubic_bspline")
    for a in range(3):
        np.testing.assert_allclose(out[a], I.prefilter_fir(w[a]), atol=1e-6)
