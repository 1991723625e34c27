"""Semi-Lagrangian machinery: backward characteristic tracing (RK2) and the
single transport step used by all four PDE solves (state, adjoint, incremental
state, incremental adjoint).

Because CLAIRE uses a *stationary* velocity, the characteristic footpoints X
are identical for every time step of a solve — they are computed once per
velocity iterate and reused (this is the paper's #IP accounting in Table 1).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro import obs

from . import grid as _grid
from . import interp as _interp

#: Static CFL bound (voxels) assumed by the Pallas halo-tile interpolation
#: kernel: per-step footpoint displacement |q - x| must stay below this.
#: dt = 1/Nt and the solver's velocity regime keep SL displacements at a few
#: voxels; the pure-XLA path has no such bound and is the fallback.
PALLAS_DISPLACEMENT_BOUND = 6

_METHOD_TO_BASIS = {
    "linear": "linear",
    "cubic_lagrange": "cubic_lagrange",
    "cubic_bspline": "cubic_bspline",
}


@obs.scoped(obs.INTERP_PREFILTER)
def _prefilter_dispatch(f, method, backend):
    """Interpolation coefficients for ``method`` (B-spline prefilter or id).

    Stacked fields ``(..., N1, N2, N3)`` are filtered in one batched pass
    (single traced stencil for the XLA path, vmapped pencil kernel for
    Pallas) instead of one traced copy per component.
    """
    if method != "cubic_bspline":
        return f
    if backend == "pallas":
        from repro.kernels.prefilter import prefilter as _pk

        if f.ndim > 3:
            lead = f.shape[:-3]
            flat = jax.vmap(_pk.prefilter3d_pallas)(f.reshape((-1,) + f.shape[-3:]))
            return flat.reshape(lead + f.shape[-3:])
        return _pk.prefilter3d_pallas(f)
    return _interp.prefilter_for(f, method)


@obs.scoped(obs.INTERP_APPLY)
def _interp_dispatch(coef, q, method, weight_dtype, backend):
    """Interpolate prefiltered coefficients at q via XLA or Pallas kernel."""
    if backend == "pallas":
        from repro.kernels.interp3d import interp3d as _k

        return _k.interp3d_pallas(
            coef, q, basis=_METHOD_TO_BASIS[method],
            displacement_bound=PALLAS_DISPLACEMENT_BOUND,
            weight_dtype=weight_dtype,
        )
    return _interp.interp_field(coef, q, method, prefiltered=True,
                                weight_dtype=weight_dtype)


@obs.scoped(obs.INTERP_PLAN)
def build_plan(foot: jnp.ndarray, method: str, weight_dtype=None,
               shape=None) -> _interp.InterpPlan:
    """Precompute the interpolation plan for footpoints ``foot``.

    For a stationary velocity the footpoints are fixed for an entire solve
    (and an entire Newton step), so the gather indices and basis weights are
    built once here and reused by every SL step and every Hessian matvec
    (see ``repro.core.interp.build_plan``).
    """
    return _interp.build_plan(foot, method=method, weight_dtype=weight_dtype,
                              shape=shape)


@obs.scoped(obs.INTERP_APPLY)
def _apply_plan_dispatch(plan, coef, backend):
    """Apply a prebuilt plan to (stacked) coefficients via XLA or Pallas."""
    if backend == "pallas":
        from repro.kernels.interp3d import interp3d as _k

        return _k.apply_plan_pallas(coef, plan)
    return _interp.apply_plan(plan, coef)


def trace_characteristic(
    v: jnp.ndarray,
    dt: float,
    method: str = "cubic_bspline",
    sign: float = 1.0,
    weight_dtype=None,
    backend: str = "jnp",
    shard=None,
) -> jnp.ndarray:
    """RK2 (midpoint) backward trace of the characteristic.

        X(x) = x - sign * dt * v(x - sign * (dt/2) * v(x))

    ``sign=+1`` traces along +v (state equation); ``sign=-1`` traces along -v
    (adjoint equation in reversed pseudo-time). Returns footpoints in *index
    units*, shape (3, N1, N2, N3). With ``shard`` (inside ``shard_map``),
    ``v`` is an x1 slab and the returned footpoints are global coordinates of
    the local grid points (halo-local midpoint interpolation).
    """
    if shard is not None:
        from repro.distributed import halo as _halo

        return _halo.trace_characteristic(v, dt, method, sign, weight_dtype,
                                          shard)
    shape = v.shape[-3:]
    h = jnp.asarray(_grid.spacing(shape), dtype=v.dtype).reshape(3, 1, 1, 1)
    x_idx = _grid.index_coords(shape, dtype=v.dtype)

    # midpoint (index units): x - sign*dt/2*v, converted by /h
    q_mid = x_idx - sign * (0.5 * dt) * v / h
    v_coef = _prefilter_dispatch(v, method, backend)
    # One plan shared by all three components: a single batched
    # gather-multiply-accumulate instead of three traced copies.
    plan_mid = build_plan(q_mid, method, weight_dtype, shape=shape)
    v_mid = _apply_plan_dispatch(plan_mid, v_coef, backend)
    return x_idx - sign * dt * v_mid / h


def sl_step(
    f: jnp.ndarray,
    foot: jnp.ndarray,
    method: str = "cubic_bspline",
    weight_dtype=None,
    backend: str = "jnp",
    plan: _interp.InterpPlan | None = None,
    shard=None,
) -> jnp.ndarray:
    """One semi-Lagrangian advection step: f_new(x) = f(X(x)).

    ``f`` is the *raw* field; prefiltering (if the method needs it) happens
    here because f changes every step. When a prebuilt ``plan`` (built from
    ``foot``) is given, the footpoints are not re-processed: the step is a
    pure gather-multiply-accumulate through the plan. With ``shard`` the
    step is slab-local: CFL-bounded halo exchange of the (prefiltered)
    coefficients, then a local plan application (see ``distributed.halo``).
    """
    if shard is not None:
        from repro.distributed import halo as _halo

        if plan is None:
            plan = _halo.build_plan(foot, method, weight_dtype, shard)
        return _halo.apply_plan(plan, f, method, shard)
    coef = _prefilter_dispatch(f, method, backend)
    if plan is not None:
        return _apply_plan_dispatch(plan, coef, backend)
    return _interp_dispatch(coef, foot, method, weight_dtype, backend)


def sl_step_many(
    fs: jnp.ndarray,
    foot: jnp.ndarray,
    method: str = "cubic_bspline",
    weight_dtype=None,
    backend: str = "jnp",
    plan: _interp.InterpPlan | None = None,
    shard=None,
) -> jnp.ndarray:
    """Advect stacked scalar fields ``(K, N1, N2, N3)`` in one fused pass.

    All fields share the same footpoints, so with a plan the whole stack is
    one batched gather; without one, the components fall back to per-field
    interpolation (the weights are still recomputed only once per call by
    the XLA CSE, but not shared across calls).
    """
    if shard is not None:
        from repro.distributed import halo as _halo

        if plan is None:
            plan = _halo.build_plan(foot, method, weight_dtype, shard)
        return _halo.apply_plan(plan, fs, method, shard)
    coef = _prefilter_dispatch(fs, method, backend)
    if plan is not None:
        return _apply_plan_dispatch(plan, coef, backend)
    return jnp.stack(
        [_interp_dispatch(coef[k], foot, method, weight_dtype, backend)
         for k in range(fs.shape[0])], axis=0)


def sl_step_with_source(
    f: jnp.ndarray,
    source_t0: jnp.ndarray,
    source_coeff_t1: jnp.ndarray,
    foot: jnp.ndarray,
    dt: float,
    method: str = "cubic_bspline",
    weight_dtype=None,
    backend: str = "jnp",
    plan: _interp.InterpPlan | None = None,
    shard=None,
) -> jnp.ndarray:
    """SL step for  d f / dt = s  along characteristics (Heun / RK2):

        f_adv = f(X),   k1 = s_t0(X),
        k2    = s_t1 applied to the predictor at the arrival point,
        f_new = f_adv + dt/2 * (k1 + k2)

    ``source_t0`` is the source field at the departure time (interpolated at
    the footpoints); ``source_coeff_t1`` is a *pointwise multiplier* c(x) such
    that s_t1(f) = c * f at the arrival point (this covers both the adjoint
    equation, where s = -f * div v, and lets callers pass c = 0 for plain
    advection). With a ``plan``, f and the source are advected through one
    batched plan application.
    """
    if plan is not None or shard is not None:
        f_adv, k1 = sl_step_many(jnp.stack([f, source_t0]), foot, method,
                                 weight_dtype, backend, plan=plan, shard=shard)
    else:
        f_adv = sl_step(f, foot, method, weight_dtype, backend)
        k1 = sl_step(source_t0, foot, method, weight_dtype, backend)
    f_pred = f_adv + dt * k1
    k2 = source_coeff_t1 * f_pred
    return f_adv + 0.5 * dt * (k1 + k2)
