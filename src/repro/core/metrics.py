"""Registration quality metrics: deformation map, det(grad y), Dice.

The deformation map y (with m(x,1) = m0(y(x))) is the Nt-fold composition of
the per-step SL footpoint map X. We track the periodic displacement
u(x) = y(x) - x, updated per step as

    u_{j+1}(x) = u_j(X(x)) + (X(x) - x),

then F = I + grad(u) (FD8) and det F pointwise (the paper's quality metric:
min/mean/max of det F; diffeomorphic iff det F > 0 everywhere).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro import obs

from . import derivatives as _deriv
from . import grid as _grid
from . import interp as _interp
from . import transport as _tr


def deformation_displacement(v: jnp.ndarray, cfg: _tr.TransportConfig) -> jnp.ndarray:
    """Displacement field u = y - x in physical units, shape (3, N1,N2,N3)."""
    shape = v.shape[-3:]
    foot = _tr.footpoints(v, cfg, sign=1.0)  # index units
    h = jnp.asarray(_grid.spacing(shape), dtype=v.dtype).reshape(3, 1, 1, 1)
    x_idx = _grid.index_coords(shape, dtype=v.dtype)
    step_disp = (foot - x_idx) * h  # X(x) - x, physical

    def step(u, _):
        u_coef = _interp.prefilter_for(u, cfg.interp)
        u_at_X = _interp.interp_vector(
            u_coef, foot, cfg.interp, prefiltered=True, weight_dtype=cfg.weight_dtype
        )
        return u_at_X + step_disp, None

    u0 = jnp.zeros_like(v)
    u, _ = jax.lax.scan(step, u0, None, length=cfg.nt)
    return u


def det_deformation_gradient(
    v: jnp.ndarray, cfg: _tr.TransportConfig
) -> jnp.ndarray:
    """det(F) with F = I + grad(u), evaluated pointwise on the grid."""
    u = deformation_displacement(v, cfg)
    # J[i][j] = d u_i / d x_j
    J = [
        [_deriv.fd8_partial(u[i], j, backend=cfg.backend) for j in range(3)]
        for i in range(3)
    ]
    f00, f01, f02 = 1.0 + J[0][0], J[0][1], J[0][2]
    f10, f11, f12 = J[1][0], 1.0 + J[1][1], J[1][2]
    f20, f21, f22 = J[2][0], J[2][1], 1.0 + J[2][2]
    return (
        f00 * (f11 * f22 - f12 * f21)
        - f01 * (f10 * f22 - f12 * f20)
        + f02 * (f10 * f21 - f11 * f20)
    )


@obs.scoped(obs.SCORE)
def detF_stats(v: jnp.ndarray, cfg: _tr.TransportConfig) -> Dict[str, jnp.ndarray]:
    d = det_deformation_gradient(v, cfg)
    return dict(min=jnp.min(d), mean=jnp.mean(d), max=jnp.max(d))


@obs.scoped(obs.SCORE)
def warp_image(
    m0: jnp.ndarray, v: jnp.ndarray, cfg: _tr.TransportConfig
) -> jnp.ndarray:
    """Apply the transformation: m(x,1) = m0(y(x)) via the SL state solve."""
    return _tr.solve_state(m0, v, cfg)[-1]


@obs.scoped(obs.SCORE)
def warp_labels(
    labels: jnp.ndarray, v: jnp.ndarray, cfg: _tr.TransportConfig
) -> jnp.ndarray:
    """Warp a binary label mask with *linear* interpolation of the
    displacement composition and 0.5-thresholding (nearest-neighbor-like,
    matching the paper's label handling)."""
    u = deformation_displacement(v, cfg)
    shape = labels.shape
    h = jnp.asarray(_grid.spacing(shape), dtype=u.dtype).reshape(3, 1, 1, 1)
    q = _grid.index_coords(shape, dtype=u.dtype) + u / h
    warped = _interp.interp_linear(labels.astype(jnp.float32), q)
    return (warped >= 0.5).astype(labels.dtype)


@obs.scoped(obs.SCORE)
def dice(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Dice overlap of two binary masks."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    inter = jnp.sum(a * b)
    return 2.0 * inter / jnp.maximum(jnp.sum(a) + jnp.sum(b), 1.0)
