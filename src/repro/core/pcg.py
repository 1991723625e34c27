"""Preconditioned conjugate gradient for the Newton system  H vt = -g.

Preconditioner: the spectral inverse of the regularization operator,
M^-1 = (beta*A)^-1 (identity on the zero mode) — CLAIRE's default. Because A
is diagonal in Fourier space the preconditioner is two FFT sweeps.

The loop is a ``lax.while_loop`` so the whole Newton step stays inside one
jitted computation. Tolerance follows the superlinear Eisenstat-Walker
forcing sequence chosen by the caller.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro import obs

from . import grid as _grid
from . import spectral as _spec


class PCGResult(NamedTuple):
    x: jnp.ndarray
    iters: jnp.ndarray       # number of matvecs performed
    rel_residual: jnp.ndarray


@obs.scoped(obs.PCG)
def solve(
    matvec: Callable[[jnp.ndarray], jnp.ndarray],
    b: jnp.ndarray,
    precond: Callable[[jnp.ndarray], jnp.ndarray],
    tol: jnp.ndarray | float,
    max_iters: int = 500,
    shard=None,
) -> PCGResult:
    """Solve  M^-1 H x = M^-1 b  to  ||r|| <= tol * ||b||  (L2 on the grid).

    With ``shard`` (slab-distributed solve inside ``shard_map``) every inner
    product is psum-reduced over the slab axis, so alpha/beta and the
    stopping test are identical replicated scalars on every shard and all
    shards run the same trip count.
    """

    shape = b.shape[-3:]
    inner = partial(_grid.inner, shape=shape, shard=shard)

    x0 = jnp.zeros_like(b)
    r0 = b  # r = b - H x, x0 = 0
    z0 = precond(r0)
    p0 = z0
    rz0 = inner(r0, z0)
    bnorm = jnp.sqrt(inner(b, b))

    def cond(state):
        _, r, _, _, k, _ = state
        rnorm = jnp.sqrt(inner(r, r))
        return jnp.logical_and(rnorm > tol * bnorm, k < max_iters)

    def body(state):
        x, r, z, p, k, rz = state
        hp = matvec(p)
        php = inner(p, hp)
        # Guard against breakdown (H is SPD up to roundoff; clamp tiny curvature).
        alpha = rz / jnp.where(php > 0, php, 1.0)
        alpha = jnp.where(php > 0, alpha, 0.0)
        x = x + alpha * p
        r = r - alpha * hp
        z = precond(r)
        rz_new = inner(r, z)
        beta_cg = rz_new / jnp.where(rz != 0.0, rz, 1.0)
        p = z + beta_cg * p
        return (x, r, z, p, k + 1, rz_new)

    state = (x0, r0, z0, p0, jnp.asarray(0, dtype=jnp.int32), rz0)
    x, r, _, _, k, _ = jax.lax.while_loop(cond, body, state)
    rel = jnp.sqrt(inner(r, r)) / jnp.where(bnorm > 0, bnorm, 1.0)
    return PCGResult(x=x, iters=k, rel_residual=rel)


def make_reg_preconditioner(beta: float, gamma: float,
                            shard=None) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """(beta*A)^-1 spectral preconditioner (Algorithm 2.1 'Preconditioner')."""

    @obs.scoped(obs.PRECOND)
    def precond(r: jnp.ndarray) -> jnp.ndarray:
        return _spec.apply_inv_regop(r, beta, gamma, zero_mean_identity=True,
                                     shard=shard)

    return precond
