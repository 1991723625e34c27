"""Gauss-Newton-Krylov driver (Algorithm 2.1).

One Newton step = one fully-jitted computation:
  gradient evaluation (state + adjoint solves)
  -> PCG on  H vt = -g   (preconditioner (beta*A)^-1, Eisenstat-Walker forcing)
  -> Armijo backtracking line search
  -> v update.
The outer iteration (stopping test, beta-continuation, logging) runs in
Python; the jitted step is compiled once per (grid shape, numeric config)
and reused across iterations and continuation levels (beta, gamma are traced
scalars).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import gradient as _grad
from . import grid as _grid
from . import hessian as _hess
from . import objective as _obj
from . import pcg as _pcg
from . import transport as _tr


class NewtonStepStats(NamedTuple):
    v_new: jnp.ndarray
    gnorm: jnp.ndarray          # ||g(v)||_L2 at the *incoming* iterate
    j_total: jnp.ndarray        # J(v) at the incoming iterate
    j_mismatch: jnp.ndarray
    j_reg: jnp.ndarray
    pcg_iters: jnp.ndarray      # Hessian matvecs spent in PCG
    pcg_residual: jnp.ndarray
    alpha: jnp.ndarray          # accepted line-search step
    ls_evals: jnp.ndarray       # objective evaluations in the line search


class GNConfig(NamedTuple):
    beta: float = 5e-4          # target regularization weight (paper default)
    gamma: float = 1e-4         # divergence penalty (paper default)
    tol_rel_grad: float = 5e-2  # relative gradient stopping tolerance
    max_newton: int = 50
    max_pcg: int = 500
    forcing_max: float = 0.5    # Eisenstat-Walker cap
    ls_max: int = 12            # Armijo backtracking trials
    ls_c1: float = 1e-4
    continuation: bool = False  # beta-continuation ladder (decade steps)
    beta_init: float = 1.0      # ladder start when continuation is on
    cont_reduce: float = 10.0   # ladder ratio
    cont_tol: float = 2.5e-1    # per-level relative-gradient tolerance


def _build_step(cfg: _tr.TransportConfig, gn: GNConfig):
    """Build the (untransformed) Newton step for a fixed numeric config."""

    def step(m0, m1, v, beta, gamma, eta):
        # One gradient evaluation builds the per-Newton-step invariants
        # (footpoints, interpolation plans, grad(m_traj), div v) that every
        # PCG Hessian matvec below consumes through ``gs`` — the paper's
        # build-once/apply-many amortization.
        gs = _grad.evaluate(m0, m1, v, beta, gamma, cfg)
        gnorm = _grid.norm_l2(gs.g, shard=cfg.shard)

        mv = partial(_hess.matvec, gs=gs, v=v, beta=beta, gamma=gamma, cfg=cfg)
        precond = _pcg.make_reg_preconditioner(beta, gamma, shard=cfg.shard)
        sol = _pcg.solve(mv, -gs.g, precond, tol=eta, max_iters=gn.max_pcg,
                         shard=cfg.shard)
        vt = sol.x

        # Armijo backtracking: J(v + a*vt) <= J(v) + c1*a*<g, vt>.
        j0 = gs.j_mismatch + gs.j_reg
        gdotp = _grid.inner(gs.g, vt, shard=cfg.shard)

        @obs.scoped(obs.LINESEARCH)
        def trial_obj(a):
            # The trial velocity moves the footpoints, so the Newton-step
            # plans cannot be reused here; solve_state still builds one plan
            # per trial, shared by its Nt SL steps.
            return _obj.objective(m0, m1, v + a * vt, beta, gamma, cfg)

        def ls_cond(state):
            a, j_trial, k = state
            insufficient = j_trial > j0 + gn.ls_c1 * a * gdotp
            return jnp.logical_and(insufficient, k < gn.ls_max)

        def ls_body(state):
            a, _, k = state
            a = 0.5 * a
            return (a, trial_obj(a), k + 1)

        a0 = jnp.asarray(1.0, dtype=v.dtype)
        state = (a0, trial_obj(a0), jnp.asarray(0, jnp.int32))
        a, _, ls_evals = jax.lax.while_loop(ls_cond, ls_body, state)
        # If the search direction failed entirely, fall back to a small
        # preconditioned gradient step (keeps the iteration alive).
        ok = ls_evals < gn.ls_max
        v_new = jnp.where(ok, v + a * vt, v - 0.1 * precond(gs.g))

        return NewtonStepStats(
            v_new=v_new,
            gnorm=gnorm,
            j_total=j0,
            j_mismatch=gs.j_mismatch,
            j_reg=gs.j_reg,
            pcg_iters=sol.iters,
            pcg_residual=sol.rel_residual,
            alpha=a,
            ls_evals=ls_evals + 1,
        )

    return step


@obs.span(obs.BUILD)
def _make_step(cfg: _tr.TransportConfig, gn: GNConfig):
    """Jitted Newton step for one image pair."""
    body = _build_step(cfg, gn)

    def step(m0, m1, v, beta, gamma, eta):
        obs.count_trace("newton_step")
        return body(m0, m1, v, beta, gamma, eta)

    return jax.jit(step)


@obs.span(obs.BUILD)
def _make_batch_step(cfg: _tr.TransportConfig, gn: GNConfig,
                     donate: bool = False):
    """Jitted Newton step vmapped over a leading batch axis.

    ``m0, m1, v, eta`` carry a batch axis; ``beta, gamma`` are shared. The
    inner ``while_loop``s (PCG, line search) are batched by JAX with masked
    carries, so each pair runs exactly its own iteration counts and the
    per-pair stats match the unbatched step.

    ``donate=True`` builds the buffer-donating variant used by the serving
    path: the velocity wave — the dominant live buffer, ``(B, 3, N1, N2,
    N3)`` per bucket — is donated to the step (``donate_argnums``) so XLA
    aliases it into ``stats.v_new`` instead of double-buffering every padded
    wave. Because donation consumes the input, the convergence mask can no
    longer be applied on the host after the fact; the step takes two extra
    arguments ``(gnorm_ref, active)``, evaluates the relative-gradient test
    on device, and returns ``(stats, advance)`` with ``stats.v_new`` already
    frozen for non-advancing pairs. ``gnorm_ref`` entries that are
    non-finite or ``<= 0`` fall back to the observed gradient norm of this
    step (the cold-start first iteration).
    """
    vstep = jax.vmap(_build_step(cfg, gn), in_axes=(0, 0, 0, None, None, 0))
    if not donate:
        def step(m0, m1, v, beta, gamma, eta):
            obs.count_trace("newton_step_batch")
            return vstep(m0, m1, v, beta, gamma, eta)

        return jax.jit(step)

    def step(m0, m1, v, beta, gamma, eta, gnorm_ref, active):
        obs.count_trace("newton_step_batch")
        stats = vstep(m0, m1, v, beta, gamma, eta)
        use_ref = jnp.isfinite(gnorm_ref) & (gnorm_ref > 0)
        gnorm0 = jnp.where(use_ref, gnorm_ref, stats.gnorm)
        rel = jnp.where(gnorm0 > 0, stats.gnorm / gnorm0, 0.0)
        advance = active & (rel > gn.tol_rel_grad)
        mask = advance.reshape(advance.shape + (1,) * (v.ndim - 1))
        return stats._replace(v_new=jnp.where(mask, stats.v_new, v)), advance

    return jax.jit(step, donate_argnums=(2,))


class GNResult(NamedTuple):
    """``wall_time_s``: seconds from the start of the first Newton
    evaluation to the end of the last (their ``claire.newton`` spans),
    tracing and compiling the step included."""

    v: jnp.ndarray
    iters: int
    matvecs: int
    gnorm0: float
    gnorm: float
    rel_grad: float
    converged: bool
    history: List[Dict[str, float]]
    wall_time_s: float


def solve(
    m0: jnp.ndarray,
    m1: jnp.ndarray,
    cfg: _tr.TransportConfig,
    gn: GNConfig = GNConfig(),
    v0: jnp.ndarray | None = None,
    gnorm_ref: float | None = None,
    eta0: float | None = None,
    verbose: bool = False,
    step_fn=None,
) -> GNResult:
    """Run the Gauss-Newton-Krylov solver  g(v) = 0  for v.

    ``gnorm_ref`` fixes the reference for the relative-gradient stopping test
    instead of the gradient norm at the incoming iterate. Warm-started solves
    (grid continuation) need this: the prolonged coarse solution already has a
    small gradient, and measuring convergence relative to *it* would demand
    far more accuracy than the cold-started solve delivers.

    ``eta0`` overrides the PCG forcing term of the *first* Newton step (the
    Eisenstat-Walker sequence needs one observed gradient before it can
    adapt). Grid continuation passes the coarse level's final relative
    gradient here so the first warm-started step is solved tightly instead
    of at the loose cold-start cap.

    ``step_fn`` injects a pre-built jitted Newton step with the signature of
    :func:`_make_step` — the slab-distributed driver passes its
    ``shard_map``-wrapped step here so the whole outer iteration (stopping
    test, continuation ladder, Eisenstat-Walker forcing, logging) is shared
    between the single-device and the sharded solve.
    """
    shape = m0.shape
    v = v0 if v0 is not None else jnp.zeros((3,) + shape, dtype=m0.dtype)
    if step_fn is None:
        step_fn = _make_step(cfg, gn)

    # beta-continuation ladder (decade steps down to the target beta).
    if gn.continuation and gn.beta_init > gn.beta:
        betas = []
        b = gn.beta_init
        while b > gn.beta * (1.0 + 1e-12):
            betas.append(b)
            b /= gn.cont_reduce
        betas.append(gn.beta)
    else:
        betas = [gn.beta]

    history: List[Dict[str, float]] = []
    total_matvecs = 0
    total_iters = 0
    gnorm0_global = gnorm_ref
    gnorm_last = None
    first = last = None

    for level, beta in enumerate(betas):
        is_target = level == len(betas) - 1
        tol = gn.tol_rel_grad if is_target else gn.cont_tol
        budget = gn.max_newton - total_iters if is_target else max(
            2, (gn.max_newton - total_iters) // 4
        )
        gnorm0_level = gnorm_ref
        prev_gnorm = None
        for _ in range(max(budget, 1)):
            with obs.span(obs.NEWTON, step_num=len(history)) as last:
                first = first or last
                # Eisenstat-Walker superlinear forcing: eta = min(cap, sqrt(g/g0)).
                if gnorm0_level is None or prev_gnorm is None:
                    eta = min(gn.forcing_max, eta0) if eta0 is not None else gn.forcing_max
                else:
                    eta = float(
                        min(gn.forcing_max, (prev_gnorm / gnorm0_level) ** 0.5)
                    )
                with obs.span(obs.DISPATCH):
                    stats = step_fn(m0, m1, v, jnp.float32(beta),
                                    jnp.float32(gn.gamma), jnp.float32(eta))
                with obs.span(obs.SYNC):
                    gnorm = float(stats.gnorm)
                    h = dict(
                        j=float(stats.j_total),
                        j_mismatch=float(stats.j_mismatch),
                        j_reg=float(stats.j_reg),
                        pcg_iters=int(stats.pcg_iters),
                        alpha=float(stats.alpha),
                        ls_evals=int(stats.ls_evals),
                    )
                if gnorm0_level is None:
                    gnorm0_level = gnorm
                if gnorm0_global is None:
                    gnorm0_global = gnorm
                rel = gnorm / gnorm0_level if gnorm0_level > 0 else 0.0
                history.append(dict(level=level, beta=beta, gnorm=gnorm,
                                    rel_grad=rel, **h))
                if verbose:
                    print(
                        f"[GN] lvl={level} beta={beta:.1e} it={total_iters:3d} "
                        f"J={h['j']:.4e} mis={h['j_mismatch']:.4e} |g|rel={rel:.3e} "
                        f"pcg={h['pcg_iters']} a={h['alpha']:.3f}"
                    )
                gnorm_last = gnorm
                # The step's PCG solve ran whether or not we accept the update,
                # so its matvecs count toward the Table-1 work accounting even on
                # the final (converged) step.
                total_matvecs += h["pcg_iters"]
                if rel <= tol:
                    # converged at this level -- do not apply the (already
                    # computed) step past the tolerance; keep v as-is.
                    break
                v = stats.v_new
                prev_gnorm = gnorm
                total_iters += 1
                if total_iters >= gn.max_newton:
                    break
        if total_iters >= gn.max_newton:
            break

    rel_final = (
        gnorm_last / gnorm0_global if (gnorm0_global and gnorm0_global > 0) else 0.0
    )
    return GNResult(
        v=v,
        iters=total_iters,
        matvecs=total_matvecs,
        gnorm0=gnorm0_global or 0.0,
        gnorm=gnorm_last or 0.0,
        rel_grad=rel_final,
        converged=rel_final <= gn.tol_rel_grad,
        history=history,
        wall_time_s=obs.elapsed_s(first, last),
    )


# ---------------------------------------------------------------------------
# Batched driver: many image pairs, one vmapped Newton step (the multi-GPU
# follow-up's "many registrations concurrently" workload, on one device).
# ---------------------------------------------------------------------------


class BatchGNResult(NamedTuple):
    v: jnp.ndarray            # (B, 3, N1, N2, N3)
    iters: np.ndarray         # (B,) accepted Newton steps per pair
    matvecs: np.ndarray       # (B,) Hessian matvecs per pair
    gnorm0: np.ndarray        # (B,)
    gnorm: np.ndarray         # (B,) at the last evaluated iterate
    rel_grad: np.ndarray      # (B,)
    converged: np.ndarray     # (B,) bool
    history: List[Dict[str, np.ndarray]]   # per evaluation, per-pair arrays
    wall_time_s: float        # as GNResult's: Newton spans, compiling included


def solve_batch(
    m0: jnp.ndarray,
    m1: jnp.ndarray,
    cfg: _tr.TransportConfig,
    gn: GNConfig = GNConfig(),
    v0: jnp.ndarray | None = None,
    gnorm_ref: Any | None = None,
    verbose: bool = False,
    step_fn=None,
    donate: bool = False,
) -> BatchGNResult:
    """Solve ``B`` independent registrations with one vmapped Newton step.

    ``m0, m1`` carry a leading batch axis ``(B, N1, N2, N3)``. The outer loop
    mirrors :func:`solve` (Eisenstat-Walker forcing, relative-gradient stop)
    with *per-pair* state; converged pairs are frozen with masked updates
    while the rest keep iterating, so the returned per-pair results match the
    unbatched solver.

    ``v0`` optionally warm-starts the iteration, ``(B, 3, N1, N2, N3)``.
    ``gnorm_ref`` is the per-pair counterpart of :func:`solve`'s argument: a
    ``(B,)`` array fixing the reference of the relative-gradient stopping
    test. Warm-started pairs (longitudinal re-registrations of the same
    subject) need this — their incoming gradient is already small, and
    measuring convergence relative to *it* would demand far more accuracy
    than the cold solve delivered. Entries that are non-finite or ``<= 0``
    fall back to the observed initial gradient norm of that pair.

    ``donate=True`` switches to the buffer-donating step (see
    :func:`_make_batch_step`): the velocity buffer is aliased through the
    compiled step instead of double-buffered, and the convergence mask is
    applied on device — the step's (fp32) relative-gradient test then drives
    the bookkeeping, so pair freezing and the device update can never
    disagree. A caller-supplied ``step_fn`` must match the chosen calling
    convention, i.e. be built with the same ``donate`` flag; a caller-
    supplied ``v0`` buffer is consumed (donated on the first step) — pass a
    copy if you still need it.
    """
    if gn.continuation:
        raise ValueError("solve_batch does not support beta-continuation")
    if m0.ndim != 4:
        raise ValueError(f"expected batched images (B, N1, N2, N3), got {m0.shape}")
    bsz = m0.shape[0]
    shape = m0.shape[1:]
    v = v0 if v0 is not None else jnp.zeros((bsz, 3) + shape, dtype=m0.dtype)
    bstep = step_fn if step_fn is not None else _make_batch_step(cfg, gn,
                                                                 donate=donate)

    active = np.ones(bsz, dtype=bool)
    ever_converged = np.zeros(bsz, dtype=bool)
    iters = np.zeros(bsz, dtype=np.int64)
    matvecs = np.zeros(bsz, dtype=np.int64)
    gnorm0 = None
    gnorm_last = np.zeros(bsz, dtype=np.float64)
    eta = np.full(bsz, gn.forcing_max, dtype=np.float64)
    history: List[Dict[str, np.ndarray]] = []
    first = last = None

    for _ in range(gn.max_newton):
        with obs.span(obs.NEWTON, step_num=len(history)) as last:
            first = first or last
            with obs.span(obs.DISPATCH):
                if donate:
                    # First step: pass the caller's reference (NaN where
                    # absent) and let the device fall back to the observed
                    # gnorm — the same resolution the host bookkeeping below
                    # applies to gnorm0.
                    if gnorm0 is not None:
                        ref_arg = gnorm0
                    elif gnorm_ref is not None:
                        ref_arg = np.broadcast_to(
                            np.asarray(gnorm_ref, dtype=np.float64), (bsz,))
                    else:
                        ref_arg = np.full(bsz, np.nan)
                    stats, adv_dev = bstep(
                        m0, m1, v,
                        jnp.float32(gn.beta), jnp.float32(gn.gamma),
                        jnp.asarray(eta, dtype=jnp.float32),
                        jnp.asarray(ref_arg, dtype=jnp.float32),
                        jnp.asarray(active),
                    )
                else:
                    stats = bstep(
                        m0, m1, v,
                        jnp.float32(gn.beta), jnp.float32(gn.gamma),
                        jnp.asarray(eta, dtype=jnp.float32),
                    )
            with obs.span(obs.SYNC):
                gnorm = np.asarray(stats.gnorm, dtype=np.float64)
                pcg = np.asarray(stats.pcg_iters, dtype=np.int64)
                adv = np.asarray(adv_dev, dtype=bool) if donate else None
                h = dict(
                    j=np.asarray(stats.j_total, dtype=np.float64),
                    j_mismatch=np.asarray(stats.j_mismatch, dtype=np.float64),
                    pcg_iters=pcg,
                    alpha=np.asarray(stats.alpha, dtype=np.float64),
                    ls_evals=np.asarray(stats.ls_evals, dtype=np.int64),
                )
            if gnorm0 is None:
                gnorm0 = gnorm.copy()
                if gnorm_ref is not None:
                    ref = np.broadcast_to(
                        np.asarray(gnorm_ref, dtype=np.float64), (bsz,)).copy()
                    use_ref = np.isfinite(ref) & (ref > 0)
                    gnorm0 = np.where(use_ref, ref, gnorm0)
            rel = np.where(gnorm0 > 0, gnorm / gnorm0, 0.0)
            gnorm_last = np.where(active, gnorm, gnorm_last)
            # Final-step PCG work counts, matching the unbatched accounting.
            matvecs += np.where(active, pcg, 0)
            if donate:
                # The device already applied the freeze mask to v_new; mirror
                # its decision so host bookkeeping and the update cannot
                # diverge.
                advance = adv & active
                just_conv = active & ~advance
                v = stats.v_new
            else:
                just_conv = active & (rel <= gn.tol_rel_grad)
                advance = active & ~just_conv
                mask = jnp.asarray(advance).reshape((bsz,) + (1,) * (v.ndim - 1))
                v = jnp.where(mask, stats.v_new, v)
            ever_converged |= just_conv
            iters += advance
            eta = np.where(
                advance,
                np.minimum(gn.forcing_max,
                           np.sqrt(np.maximum(gnorm, 0.0) / np.maximum(gnorm0, 1e-30))),
                eta,
            )
            history.append(dict(gnorm=gnorm, rel_grad=rel, active=active.copy(), **h))
            if verbose:
                print(
                    f"[GN-batch] it={len(history) - 1:3d} active={int(active.sum())} "
                    f"|g|rel={np.array2string(rel, precision=3)} pcg={pcg}"
                )
            active = advance
            if not active.any():
                break

    rel_final = np.where(gnorm0 > 0, gnorm_last / gnorm0, 0.0) if gnorm0 is not None \
        else np.zeros(bsz)
    return BatchGNResult(
        v=v,
        iters=iters,
        matvecs=matvecs,
        gnorm0=gnorm0 if gnorm0 is not None else np.zeros(bsz),
        gnorm=gnorm_last,
        rel_grad=rel_final,
        converged=ever_converged | (rel_final <= gn.tol_rel_grad),
        history=history,
        wall_time_s=obs.elapsed_s(first, last),
    )
