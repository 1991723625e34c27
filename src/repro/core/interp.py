"""Scattered-data interpolation on periodic 3D grids (pure-XLA path).

This mirrors the paper's interpolation kernel family:
  * ``linear``         -> GPU-TXTLIN   (trilinear, 8 taps)
  * ``cubic_lagrange`` -> GPU-LAG      (cubic Lagrange, 64 taps, c_ijk = f_ijk)
  * ``cubic_bspline``  -> GPU-TXTSPL   (cubic B-spline, 64 taps on *prefiltered*
                                        coefficients; the prefilter is the
                                        15-point finite convolution of the paper)

GPU texture hardware does not exist on TPU; this module is the XLA-gather
implementation. The plan path (:func:`build_plan` / :func:`apply_plan`)
fetches each query point's whole tap window -- ``support**3`` taps of every
stacked field -- with one ``lax.gather`` of one row of a tap block, built
per application from the padded coefficient block. The plan-free
:func:`interp_field` gathers one scalar per tap and is the tests' oracle.
The Pallas halo-tile kernels live in ``repro.kernels.interp3d``.

Query points ``q`` have shape (3, *out_shape) and are measured in *index
units* (physical coordinate / h). Periodic wrap is applied.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs

# ---------------------------------------------------------------------------
# B-spline prefilter
# ---------------------------------------------------------------------------

# The cubic B-spline interpolation coefficients c solve B c = f with the
# tridiagonal (periodic) filter B = [1/6, 4/6, 1/6]. The paper replaces the
# recursive/IIR prefilter with a *finite convolution* (15-point axis-aligned
# stencil; Champagnat & Le Sant). The exact two-sided impulse response is
#   h_n = -6 * z1^{|n|+1} / (1 - z1^2),  z1 = sqrt(3) - 2,
# truncated to |n| <= 7 (|h_7/h_0| ~ 1e-4, below fp32 interp error).
_Z1 = math.sqrt(3.0) - 2.0
PREFILTER_RADIUS = 7
PREFILTER_TAPS = tuple(
    -6.0 * _Z1 ** (abs(n) + 1) / (1.0 - _Z1 * _Z1)
    for n in range(-PREFILTER_RADIUS, PREFILTER_RADIUS + 1)
)


def prefilter_fir(f: jnp.ndarray) -> jnp.ndarray:
    """15-point separable finite-convolution prefilter (the paper's scheme).

    Applied axis by axis with periodic wrap. This is an axis-aligned stencil
    exactly like the FD8 kernel (and is implemented as a Pallas pencil kernel
    in ``repro.kernels.prefilter``). Operates on the trailing three axes, so
    stacked fields ``(..., N1, N2, N3)`` are filtered in one traced pass.
    """
    out = f
    for axis in range(f.ndim - 3, f.ndim):
        acc = PREFILTER_TAPS[PREFILTER_RADIUS] * out
        for k in range(1, PREFILTER_RADIUS + 1):
            c = PREFILTER_TAPS[PREFILTER_RADIUS + k]
            acc = acc + c * (jnp.roll(out, -k, axis=axis) + jnp.roll(out, k, axis=axis))
        out = acc
    return out


def prefilter_fft(f: jnp.ndarray) -> jnp.ndarray:
    """Exact periodic prefilter (spectral division by the B-spline symbol).

    Used as the oracle for the truncated FIR variant.
    """
    shape = f.shape
    sym = []
    for n in shape:
        k = np.fft.fftfreq(n, d=1.0 / n)
        sym.append((4.0 + 2.0 * np.cos(2.0 * np.pi * k / n)) / 6.0)
    s1 = jnp.asarray(sym[0], dtype=jnp.float32).reshape(-1, 1, 1)
    s2 = jnp.asarray(sym[1], dtype=jnp.float32).reshape(1, -1, 1)
    s3 = jnp.asarray(sym[2][: shape[2] // 2 + 1], dtype=jnp.float32).reshape(1, 1, -1)
    fh = jnp.fft.rfftn(f)
    return jnp.fft.irfftn(fh / (s1 * s2 * s3), s=shape).astype(f.dtype)


# ---------------------------------------------------------------------------
# Basis weights
# ---------------------------------------------------------------------------


def lagrange_weights(t: jnp.ndarray):
    """Cubic Lagrange basis at nodes {-1, 0, 1, 2} evaluated at t in [0,1)."""
    w0 = -t * (t - 1.0) * (t - 2.0) / 6.0
    w1 = (t + 1.0) * (t - 1.0) * (t - 2.0) / 2.0
    w2 = -(t + 1.0) * t * (t - 2.0) / 2.0
    w3 = (t + 1.0) * t * (t - 1.0) / 6.0
    return (w0, w1, w2, w3)


def bspline_weights(t: jnp.ndarray):
    """Uniform cubic B-spline basis at offsets {-1, 0, 1, 2} for t in [0,1)."""
    t2 = t * t
    t3 = t2 * t
    w0 = (1.0 - 3.0 * t + 3.0 * t2 - t3) / 6.0
    w1 = (4.0 - 6.0 * t2 + 3.0 * t3) / 6.0
    w2 = (1.0 + 3.0 * t + 3.0 * t2 - 3.0 * t3) / 6.0
    w3 = t3 / 6.0
    return (w0, w1, w2, w3)


def linear_weights(t: jnp.ndarray):
    return (1.0 - t, t)


# ---------------------------------------------------------------------------
# Gather-based evaluation
# ---------------------------------------------------------------------------

#: method -> (weight_fn, taps per axis, base index offset from floor(q))
_METHOD_TABLE = {
    "linear": (linear_weights, 2, 0),
    "cubic_lagrange": (lagrange_weights, 4, -1),
    "cubic_bspline": (bspline_weights, 4, -1),
}


def _gather(f_flat: jnp.ndarray, shape, i1, i2, i3):
    n1, n2, n3 = shape
    idx = (jnp.mod(i1, n1) * (n2 * n3) + jnp.mod(i2, n2) * n3 + jnp.mod(i3, n3))
    return jnp.take(f_flat, idx)


def _interp_separable(f: jnp.ndarray, q: jnp.ndarray, weight_fn, support: int,
                      base_offset: int, weight_dtype=None):
    """Generic tensor-product interpolation with ``support`` taps per axis.

    Mixed precision follows the paper's texture scheme: only the basis
    *weights* are downcast (``weight_dtype``); the field data stays at its
    native precision and accumulation is fp32.
    """
    shape = f.shape
    out_shape = q.shape[1:]
    qf = jnp.floor(q)
    t = q - qf
    base = qf.astype(jnp.int32) + base_offset
    w1 = weight_fn(t[0])
    w2 = weight_fn(t[1])
    w3 = weight_fn(t[2])
    if weight_dtype is not None:
        w1 = tuple(w.astype(weight_dtype) for w in w1)
        w2 = tuple(w.astype(weight_dtype) for w in w2)
        w3 = tuple(w.astype(weight_dtype) for w in w3)
    f_flat = f.reshape(-1)
    acc = jnp.zeros(out_shape, dtype=jnp.float32)
    for a in range(support):
        i1 = base[0] + a
        for b in range(support):
            i2 = base[1] + b
            wab = w1[a] * w2[b]
            for c in range(support):
                i3 = base[2] + c
                vals = _gather(f_flat, shape, i1, i2, i3)
                acc = acc + (wab * w3[c] * vals).astype(jnp.float32)
    return acc


def interp_linear(f, q, weight_dtype=None):
    return _interp_separable(f, q, linear_weights, 2, 0, weight_dtype)


def interp_cubic_lagrange(f, q, weight_dtype=None):
    return _interp_separable(f, q, lagrange_weights, 4, -1, weight_dtype)


def interp_cubic_bspline(f, q, prefiltered: bool = False, weight_dtype=None,
                         prefilter: str = "fir"):
    if not prefiltered:
        f = prefilter_fir(f) if prefilter == "fir" else prefilter_fft(f)
    return _interp_separable(f, q, bspline_weights, 4, -1, weight_dtype)


METHODS = ("linear", "cubic_lagrange", "cubic_bspline")


def interp_field(f: jnp.ndarray, q: jnp.ndarray, method: str = "cubic_bspline",
                 prefiltered: bool = False, weight_dtype=None) -> jnp.ndarray:
    """Interpolate scalar field ``f`` at index-unit query points ``q``.

    ``prefiltered`` marks that ``f`` already holds B-spline coefficients
    (lets callers hoist the prefilter out of time loops).
    """
    if method == "linear":
        return interp_linear(f, q, weight_dtype)
    if method == "cubic_lagrange":
        return interp_cubic_lagrange(f, q, weight_dtype)
    if method == "cubic_bspline":
        return interp_cubic_bspline(f, q, prefiltered, weight_dtype)
    raise ValueError(f"unknown interpolation method: {method}")


def interp_vector(w: jnp.ndarray, q: jnp.ndarray, method: str = "cubic_bspline",
                  prefiltered: bool = False, weight_dtype=None) -> jnp.ndarray:
    """Interpolate a vector field in one batched pass; output (3, *q.shape[1:]).

    All components share one interpolation plan (floor/mod/weights computed
    once) and one batched gather instead of three traced copies.
    """
    coef = w if prefiltered else prefilter_for(w, method)
    plan = build_plan(q, method=method, weight_dtype=weight_dtype,
                      shape=w.shape[-3:])
    return apply_plan(plan, coef)


@obs.scoped(obs.INTERP_PREFILTER)
def prefilter_for(f: jnp.ndarray, method: str) -> jnp.ndarray:
    """Return interpolation coefficients for ``method`` (identity unless
    B-spline). Leading batch axes are filtered in the same traced pass."""
    if method == "cubic_bspline":
        return prefilter_fir(f)
    return f


# ---------------------------------------------------------------------------
# Interpolation plans: build once per velocity iterate, apply many times.
#
# For a stationary velocity the SL footpoints — and therefore the gather
# indices and basis weights — are identical for every transport step and
# every PCG Hessian matvec inside one Newton step (the paper's Table 1
# accounting). A plan precomputes each point's tap-window start and the
# per-axis weight stacks so each application is one gather of every point's
# window and a multiply-accumulate over it.
#
# Padding rule: the coefficient block is padded by ``support - 1`` so that
# every window lies inside it. A periodic axis is padded on the high side
# with wrapped values and its start is ``base mod N``. A clamped axis is
# edge-padded on both sides and its start is ``clip(base, 1 - support, N - 1)
# + support - 1``; the window then holds ``clip(base + tap, 0, N - 1)`` for
# every base, as the plan's ``idx`` does.
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class InterpPlan:
    """Precomputed tensor-product interpolation plan.

    start   : int32 array (3, *out_shape) — per point and axis, the start of
              the point's tap window in the padded coefficient block that
              :func:`apply_plan` gathers from (see the padding rule above).
    idx     : 3-tuple of int32 arrays (support, *out_shape) — per-axis flat
              index contributions, periodic wrap (or clamp) and row strides
              baked in (idx[0] premultiplied by N2*N3, idx[1] by N3); read by
              the Pallas kernels of ``repro.kernels.interp3d``.
    weights : 3-tuple of arrays (support, *out_shape) — per-axis basis
              weights, optionally downcast (bf16 mixed-precision path).
    method / field_shape / wrap are static metadata (pytree aux), so plans
    pass through jit/scan/vmap with the basis baked into the trace.
    """

    def __init__(self, start, idx, weights, method, field_shape, wrap):
        self.start = start
        self.idx = tuple(idx)
        self.weights = tuple(weights)
        self.method = method
        self.field_shape = tuple(field_shape)
        self.wrap = tuple(bool(w) for w in wrap)

    @property
    def support(self) -> int:
        return _METHOD_TABLE[self.method][1]

    @property
    def out_shape(self):
        return self.idx[0].shape[1:]

    def tree_flatten(self):
        return ((self.start, self.idx, self.weights),
                (self.method, self.field_shape, self.wrap))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


@obs.scoped(obs.INTERP_PLAN)
def build_plan(q: jnp.ndarray, method: str = "cubic_bspline",
               weight_dtype=None, shape=None,
               wrap=(True, True, True)) -> InterpPlan:
    """Build an :class:`InterpPlan` for query points ``q`` (index units).

    ``shape`` is the source-field shape; defaults to ``q.shape[1:]`` (the SL
    solver interpolates fields on the same grid the footpoints live on).
    ``weight_dtype`` downcasts the *weights only* (data precision and fp32
    accumulation are unaffected — the paper's mixed-precision scheme).
    ``wrap`` selects per-axis periodic index wrap; a non-wrapped axis clamps
    tap indices into the field instead — used by the distributed halo path,
    where the x1 axis of the source is a halo-extended (non-periodic) slab
    and the CFL contract keeps in-range queries exact. The window starts
    follow the padding rule above, so :func:`apply_plan` reads the same taps
    as ``idx`` names.
    """
    if method not in _METHOD_TABLE:
        raise ValueError(f"unknown interpolation method: {method}")
    weight_fn, support, base_offset = _METHOD_TABLE[method]
    shape = tuple(int(n) for n in (shape if shape is not None else q.shape[1:]))
    n1, n2, n3 = shape
    qf = jnp.floor(q)
    t = q - qf
    base = qf.astype(jnp.int32) + base_offset
    tap = jnp.arange(support, dtype=jnp.int32).reshape(
        (support,) + (1,) * (q.ndim - 1))

    def _tap_idx(b, n, do_wrap):
        i = b[None] + tap
        return jnp.mod(i, n) if do_wrap else jnp.clip(i, 0, n - 1)

    def _start(b, n, do_wrap):
        if do_wrap:
            return jnp.mod(b, n)
        return jnp.clip(b, 1 - support, n - 1) + (support - 1)

    start = jnp.stack([_start(base[k], shape[k], wrap[k]) for k in range(3)])
    idx1 = _tap_idx(base[0], n1, wrap[0]) * (n2 * n3)
    idx2 = _tap_idx(base[1], n2, wrap[1]) * n3
    idx3 = _tap_idx(base[2], n3, wrap[2])
    w1 = jnp.stack(weight_fn(t[0]), axis=0)
    w2 = jnp.stack(weight_fn(t[1]), axis=0)
    w3 = jnp.stack(weight_fn(t[2]), axis=0)
    if weight_dtype is not None:
        w1 = w1.astype(weight_dtype)
        w2 = w2.astype(weight_dtype)
        w3 = w3.astype(weight_dtype)
    return InterpPlan(start, (idx1, idx2, idx3), (w1, w2, w3), method, shape,
                      wrap)


def _padded_block(coef: jnp.ndarray, support: int, wrap) -> jnp.ndarray:
    """``(L, N1, N2, N3)`` padded on its three grid axes by the padding rule,
    so that every plan window of ``support**3`` taps lies inside."""
    block = coef
    for axis, periodic in enumerate(wrap, start=1):
        width = [(0, 0)] * 4
        if periodic:
            width[axis] = (0, support - 1)
            block = jnp.pad(block, width, mode="wrap")
        else:
            width[axis] = (support - 1, support - 1)
            block = jnp.pad(block, width, mode="edge")
    return block


def _tap_block(coef: jnp.ndarray, support: int, wrap) -> jnp.ndarray:
    """``(L, N1, N2, N3)`` -> ``(M1, M2, M3, L * support**3)``, the tap block.

    Row ``(i, j, k)`` holds the whole window of the padded block that starts
    there: for each field, taps ``(a, b, c)`` in row-major order. A plan's
    window start then names one row, and one row gather per point fetches
    every tap of every field. The taps are sliced from the field-major
    padded block and moved to the minor axis only when stacked, so no
    intermediate carries a minor axis of size ``L`` (on the TPU such an axis
    is padded to a full tile).
    """
    block = _padded_block(coef, support, wrap)
    n_fields = block.shape[0]
    m1, m2, m3 = (n - support + 1 for n in block.shape[1:])
    taps = jnp.stack([block[:, a:a + m1, b:b + m2, c:c + m3]
                      for a in range(support) for b in range(support)
                      for c in range(support)], axis=-1)
    return jnp.moveaxis(taps, 0, 3).reshape(m1, m2, m3, n_fields * support**3)


@obs.scoped(obs.INTERP_APPLY)
def apply_plan(plan: InterpPlan, coef: jnp.ndarray) -> jnp.ndarray:
    """Evaluate interpolation ``coef`` through a prebuilt plan (fp32 accum).

    ``coef`` may carry arbitrary leading batch axes (``(..., N1, N2, N3)``);
    they are flattened to ``L`` fields and laid out as the tap block of
    :func:`_tap_block`, and one ``lax.gather`` fetches each point's row of
    ``support**3 * L`` values: every tap of every field. The window is then
    contracted with ``w1 ⊗ w2 ⊗ w3`` as the same ``support**3`` float32
    products, summed in the same order as :func:`interp_field`. Returns
    ``coef.shape[:-3] + plan.out_shape`` in float32.
    """
    if tuple(coef.shape[-3:]) != plan.field_shape:
        raise ValueError(
            f"field shape {coef.shape[-3:]} != plan field shape {plan.field_shape}")
    support = plan.support
    lead = coef.shape[:-3]
    out_shape = tuple(plan.out_shape)
    n_fields = math.prod(lead)
    block = _tap_block(coef.reshape((n_fields,) + plan.field_shape),
                       support, plan.wrap)
    dnums = lax.GatherDimensionNumbers(offset_dims=(0,),
                                       collapsed_slice_dims=(0, 1, 2),
                                       start_index_map=(0, 1, 2))
    window = lax.gather(block, jnp.moveaxis(plan.start, 0, -1), dnums,
                        (1, 1, 1, block.shape[-1]),
                        mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    window = window.reshape((n_fields,) + (support,) * 3 + out_shape)
    w1, w2, w3 = plan.weights
    acc = jnp.zeros((n_fields,) + out_shape, dtype=jnp.float32)
    for a in range(support):
        for b in range(support):
            wab = w1[a] * w2[b]
            for c in range(support):
                acc = acc + (wab * w3[c] * window[:, a, b, c]).astype(jnp.float32)
    return acc.reshape(lead + out_shape)
