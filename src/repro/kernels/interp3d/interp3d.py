"""Scattered-data interpolation as a Pallas halo-tile gather kernel.

This is the TPU adaptation of the paper's main kernel (§2.3.1). The CUDA
version leans on texture hardware (trilinear fetch units + texture cache);
TPUs have neither, so the *algorithmic* insight is re-expressed:

  * the semi-Lagrangian query points are a displacement-bounded perturbation
    of the regular grid (|q - x| <= D voxels, D set by the CFL number of the
    SL step), so locality is *structural*, not cache-lottery: each output
    tile's queries live inside the tile's bounding box + halo H = D + S
    (S = stencil support margin: 1 for trilinear, 2 for cubic);
  * the source field is periodically pre-padded by H (one XLA pad; fuses with
    the producer), so the kernel needs no wrap logic and no out-of-bounds
    handling — the CUDA version's thread-divergence problem disappears;
  * each kernel invocation reads its (B1+2H, B2+2H, B3+2H) source tile via an
    overlapping ``pl.Element`` BlockSpec (HBM -> VMEM once — the job the
    texture cache did implicitly) and evaluates the tensor-product basis
    with an in-VMEM flat gather.

Weights: trilinear (2 taps/axis) or cubic (4 taps/axis, B-spline or Lagrange
— the basis only changes the weight polynomials; for B-spline the input must
be prefiltered coefficients, see ``repro.kernels.prefilter``).

The in-VMEM gather is expressed with a flat ``jnp.take``, which runs in
interpret mode only: Mosaic refuses it for the TPU ("Only 2D gather is
supported"), and it refuses ``interp3d_pallas``'s halo block because its last
two dims are not multiples of (8, 128). The XLA path (``repro.core.interp``)
is the default on every platform; ``core.registration.make_transport_config``
rejects the kernels on the TPU.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pencil as _pencil


# ---------------------------------------------------------------------------
# Basis weights (match repro.core.interp).
# ---------------------------------------------------------------------------


def linear_weights(t):
    return (1.0 - t, t)


def bspline_weights(t):
    t2 = t * t
    t3 = t2 * t
    return (
        (1.0 - 3.0 * t + 3.0 * t2 - t3) / 6.0,
        (4.0 - 6.0 * t2 + 3.0 * t3) / 6.0,
        (1.0 + 3.0 * t + 3.0 * t2 - 3.0 * t3) / 6.0,
        t3 / 6.0,
    )


def lagrange_weights(t):
    return (
        -t * (t - 1.0) * (t - 2.0) / 6.0,
        (t + 1.0) * (t - 1.0) * (t - 2.0) / 2.0,
        -(t + 1.0) * t * (t - 2.0) / 2.0,
        (t + 1.0) * t * (t - 1.0) / 6.0,
    )


_BASES = {
    "linear": (linear_weights, 2, 0),
    "cubic_bspline": (bspline_weights, 4, -1),
    "cubic_lagrange": (lagrange_weights, 4, -1),
}


# ---------------------------------------------------------------------------
# Kernel body
# ---------------------------------------------------------------------------


def _interp_body(q1_ref, q2_ref, q3_ref, fpad_ref, o_ref, *,
                 basis, halo, block, weight_dtype):
    """One output tile: gather + tensor-product basis evaluation."""
    weight_fn, support, base_off = _BASES[basis]
    b1, b2, b3 = block
    h = halo

    tile = fpad_ref[...]  # (b1+2h, b2+2h, b3+2h) in VMEM
    t1, t2, t3 = tile.shape
    # Mixed precision is weights-only (paper's scheme): the field keeps its
    # native precision, weights are downcast below, accumulation is fp32.
    tile_flat = tile.reshape(-1)

    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)
    # Local (tile-frame) query coordinates. Global padded coordinate of a
    # query q is q + h; this tile starts at element offset (i*b1, j*b2, k*b3).
    l1 = q1_ref[...] + (h - i * b1)
    l2 = q2_ref[...] + (h - j * b2)
    l3 = q3_ref[...] + (h - k * b3)

    f1 = jnp.floor(l1)
    f2 = jnp.floor(l2)
    f3 = jnp.floor(l3)
    w1 = weight_fn(l1 - f1)
    w2 = weight_fn(l2 - f2)
    w3 = weight_fn(l3 - f3)
    if weight_dtype is not None:
        w1 = tuple(w.astype(weight_dtype) for w in w1)
        w2 = tuple(w.astype(weight_dtype) for w in w2)
        w3 = tuple(w.astype(weight_dtype) for w in w3)
    i1 = f1.astype(jnp.int32) + base_off
    i2 = f2.astype(jnp.int32) + base_off
    i3 = f3.astype(jnp.int32) + base_off

    acc = jnp.zeros(l1.shape, dtype=jnp.float32)
    for a in range(support):
        row1 = (i1 + a) * (t2 * t3)
        for b in range(support):
            row12 = row1 + (i2 + b) * t3
            wab = w1[a] * w2[b]
            for c in range(support):
                idx = row12 + (i3 + c)
                vals = jnp.take(tile_flat, idx.reshape(-1), axis=0).reshape(idx.shape)
                acc = acc + (wab * w3[c] * vals).astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call driver
# ---------------------------------------------------------------------------


def _pick_block(shape, targets=(8, 16, 128)) -> Tuple[int, int, int]:
    return tuple(
        _pencil.largest_divisor(n, t) for n, t in zip(shape, targets)
    )


def interp3d_pallas(
    f: jnp.ndarray,
    q: jnp.ndarray,
    basis: str = "cubic_bspline",
    displacement_bound: int = 6,
    weight_dtype=None,
    interpret: bool | None = None,
    block: Tuple[int, int, int] | None = None,
) -> jnp.ndarray:
    """Interpolate ``f`` at query points ``q`` (index units, shape (3, *f.shape)).

    ``q`` must satisfy |q - x_idx| <= displacement_bound per axis (the SL CFL
    bound); this is what makes tile+halo locality structural. For
    ``cubic_bspline`` the caller passes *prefiltered* coefficients as ``f``.
    """
    if basis not in _BASES:
        raise ValueError(f"unknown basis {basis!r}")
    if interpret is None:
        interpret = _pencil.interpret_default()
    _, support, base_off = _BASES[basis]
    # stencil margin: lowest tap at floor(q)+base_off, highest at +support-1
    halo = displacement_bound + max(support - 1 + base_off, -base_off) + 1
    shape = f.shape
    if block is None:
        block = _pick_block(shape)
    b1, b2, b3 = block
    grid = (shape[0] // b1, shape[1] // b2, shape[2] // b3)

    fpad = jnp.pad(f, halo, mode="wrap")

    q_spec = pl.BlockSpec((b1, b2, b3), lambda i, j, k: (i, j, k))
    # Overlapping halo tiles: element-indexed BlockSpec with stride = block.
    f_spec = pl.BlockSpec(
        (pl.Element(b1 + 2 * halo), pl.Element(b2 + 2 * halo), pl.Element(b3 + 2 * halo)),
        lambda i, j, k: (i * b1, j * b2, k * b3),
    )
    body = functools.partial(
        _interp_body, basis=basis, halo=halo, block=block,
        weight_dtype=weight_dtype,
    )
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[q_spec, q_spec, q_spec, f_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(shape, f.dtype),
        interpret=interpret,
    )(q[0], q[1], q[2], fpad)


# ---------------------------------------------------------------------------
# Fused plan-apply kernel: consume a prebuilt interpolation plan
# (flattened periodic gather bases + per-axis weight stacks, see
# ``repro.core.interp.build_plan``) so the per-query floor / wrap / weight
# polynomials are NOT recomputed — the kernel is a pure
# gather-multiply-accumulate. This is the paper's build-once/apply-many
# amortization: one plan serves every transport step and every PCG Hessian
# matvec of a Newton step.
# ---------------------------------------------------------------------------


def _plan_body(i1_ref, i2_ref, i3_ref, w1_ref, w2_ref, w3_ref, f_ref, o_ref, *,
               support):
    """One output tile: apply-plan gather-multiply-accumulate.

    Plan indices are *global* flat indices into the unpadded source field
    (periodic wrap already baked in at build time), so the kernel needs no
    halo, no padding and no wrap logic at all.
    """
    f_flat = f_ref[...].reshape(-1)
    i1 = i1_ref[...]
    i2 = i2_ref[...]
    i3 = i3_ref[...]
    w1 = w1_ref[...]
    w2 = w2_ref[...]
    w3 = w3_ref[...]
    acc = jnp.zeros(i1.shape[1:], dtype=jnp.float32)
    for a in range(support):
        ia = i1[a]
        for b in range(support):
            iab = ia + i2[b]
            wab = w1[a] * w2[b]
            for c in range(support):
                idx = iab + i3[c]
                vals = jnp.take(f_flat, idx.reshape(-1), axis=0).reshape(idx.shape)
                acc = acc + (wab * w3[c] * vals).astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def apply_plan_pallas(
    coef: jnp.ndarray,
    plan,
    interpret: bool | None = None,
    block: Tuple[int, int, int] | None = None,
) -> jnp.ndarray:
    """Evaluate coefficients ``coef`` through an ``InterpPlan`` (Pallas path).

    The whole (unpadded) field is handed to each program as a VMEM block and
    gathered with the plan's global flat indices; output, index and weight
    arrays are tiled. Mosaic refuses the flat 1D ``jnp.take`` ("Only 2D
    gather is supported"), so this kernel runs in interpret mode only.
    """
    support = plan.support
    if tuple(coef.shape[-3:]) != plan.field_shape:
        raise ValueError(
            f"field shape {coef.shape[-3:]} != plan field shape {plan.field_shape}")
    if interpret is None:
        interpret = _pencil.interpret_default()
    out_shape = tuple(plan.out_shape)
    if block is None:
        block = _pick_block(out_shape)
    b1, b2, b3 = block
    grid = (out_shape[0] // b1, out_shape[1] // b2, out_shape[2] // b3)

    plan_spec = pl.BlockSpec((support, b1, b2, b3), lambda i, j, k: (0, i, j, k))
    f_spec = pl.BlockSpec(coef.shape[-3:], lambda i, j, k: (0, 0, 0))
    o_spec = pl.BlockSpec((b1, b2, b3), lambda i, j, k: (i, j, k))
    call = pl.pallas_call(
        functools.partial(_plan_body, support=support),
        grid=grid,
        in_specs=[plan_spec] * 3 + [plan_spec] * 3 + [f_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
    )
    i1, i2, i3 = plan.idx
    w1, w2, w3 = plan.weights

    def one(field):
        return call(i1, i2, i3, w1, w2, w3, field)

    if coef.ndim == 3:
        return one(coef)
    lead = coef.shape[:-3]
    stacked = jax.vmap(one)(coef.reshape((-1,) + coef.shape[-3:]))
    return stacked.reshape(lead + out_shape)


# ---------------------------------------------------------------------------
# Fused gather + epilogue kernel: the PCG Hessian matvec hot loop.
#
# Each transport step of the Gauss-Newton matvec is "advect a small stack of
# fields through the (fixed) plan, then combine them pointwise" (the RK2
# update of the incremental state / adjoint). Doing the gather and the
# combine in ONE kernel reads the coefficient stack from HBM exactly once
# and never materializes the advected intermediates — per matvec, the
# velocity-sized fields cross HBM once instead of three times.
# ---------------------------------------------------------------------------


def _fused_body(i1_ref, i2_ref, i3_ref, w1_ref, w2_ref, w3_ref, f_ref, *rest,
                support, n_fields, n_extra, epilogue):
    """One output tile: gather ``n_fields`` stacked coefficient fields through
    the plan, then apply ``epilogue(accs, extras)`` pointwise in VMEM."""
    extras = [rest[e][...] for e in range(n_extra)]
    o_ref = rest[n_extra]
    stack = f_ref[...]                       # (K, *field) in VMEM
    flat = stack.reshape(stack.shape[0], -1)
    i1 = i1_ref[...]
    i2 = i2_ref[...]
    i3 = i3_ref[...]
    w1 = w1_ref[...]
    w2 = w2_ref[...]
    w3 = w3_ref[...]
    accs = [jnp.zeros(i1.shape[1:], dtype=jnp.float32)
            for _ in range(n_fields)]
    for a in range(support):
        ia = i1[a]
        for b in range(support):
            iab = ia + i2[b]
            wab = w1[a] * w2[b]
            for c in range(support):
                idx = (iab + i3[c]).reshape(-1)
                wabc = wab * w3[c]
                for k in range(n_fields):
                    vals = jnp.take(flat[k], idx, axis=0).reshape(wabc.shape)
                    accs[k] = accs[k] + (wabc * vals).astype(jnp.float32)
    o_ref[...] = epilogue(accs, extras).astype(o_ref.dtype)


def apply_plan_fused(
    coefs: jnp.ndarray,
    plan,
    extras,
    epilogue,
    interpret: bool | None = None,
    block: Tuple[int, int, int] | None = None,
) -> jnp.ndarray:
    """Gather stacked coefficients ``(K, *field)`` through ``plan`` and fuse a
    pointwise epilogue: returns ``epilogue([adv_0..adv_{K-1}], extras)``.

    ``extras`` are pointwise fields of the plan's *output* shape (tiled like
    the output); ``epilogue(accs, extras) -> array`` runs inside the kernel
    on fp32 accumulators. Block layout matches :func:`apply_plan_pallas`.
    """
    support = plan.support
    if coefs.ndim != 4:
        raise ValueError(f"expected stacked coefficients (K, N1, N2, N3), "
                         f"got shape {coefs.shape}")
    if tuple(coefs.shape[-3:]) != plan.field_shape:
        raise ValueError(
            f"field shape {coefs.shape[-3:]} != plan field shape {plan.field_shape}")
    if interpret is None:
        interpret = _pencil.interpret_default()
    out_shape = tuple(plan.out_shape)
    if block is None:
        block = _pick_block(out_shape)
    b1, b2, b3 = block
    grid = (out_shape[0] // b1, out_shape[1] // b2, out_shape[2] // b3)

    plan_spec = pl.BlockSpec((support, b1, b2, b3), lambda i, j, k: (0, i, j, k))
    f_spec = pl.BlockSpec(coefs.shape, lambda i, j, k: (0, 0, 0, 0))
    o_spec = pl.BlockSpec((b1, b2, b3), lambda i, j, k: (i, j, k))
    body = functools.partial(
        _fused_body, support=support, n_fields=coefs.shape[0],
        n_extra=len(extras), epilogue=epilogue,
    )
    call = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[plan_spec] * 6 + [f_spec] + [o_spec] * len(extras),
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
    )
    i1, i2, i3 = plan.idx
    w1, w2, w3 = plan.weights
    return call(i1, i2, i3, w1, w2, w3, coefs, *extras)
