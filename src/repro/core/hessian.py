"""Gauss-Newton Hessian matvec:

    H vt = beta*A vt + int_0^1 lt grad(m) dt,

where (per Algorithm 2.1)
    inc. state  :  d mt/dt + v.grad mt + vt.grad m = 0,  mt(0) = 0
    inc. adjoint: -d lt/dt - div(lt v) = 0,  lt(1) = -H_D mt(1),

with H_D the Gauss-Newton (PSD) approximation of the distance measure's
second variation — ``lt(1) = -mt(1)`` for SSD; NCC/NGF supply their own
terminal through ``measures.gn_terminal``, consuming the per-Newton-step
cache stored in ``GradientState.measure_cache``.

The matvec reuses everything precomputed during the gradient evaluation
(``GradientState``): the state trajectory, the footpoints, div(v), the
interpolation plans, the trajectory gradients and the measure cache. With
plans on, each matvec is therefore pure gather-multiply-accumulate (plan
applications), pointwise algebra, and the spectral regularizer — no
footpoint reprocessing, no basis weight recomputation and no transport
re-tracing; exactly the paper's Table 1 accounting of per-matvec vs
per-Newton-step work. (The NGF terminal adds one FD8/FFT grad+div sweep per
matvec — pointwise-stencil work, still no transport.)

With ``cfg.use_fused_matvec`` the incremental state and adjoint solves run
through the fused gather+epilogue Pallas kernel
(``kernels.interp3d.apply_plan_fused``): each transport step gathers the
stacked [field, source] coefficients through the plan AND applies the RK2
pointwise update inside one kernel, so the velocity-sized fields cross HBM
once per step instead of three times. The time loop is statically unrolled
(``nt`` is a trace-time constant) and the source/body-force contractions
collapse to single einsums over the cached trajectory gradients. The
scan-based XLA path above stays the reference the fused path is tested
against (<= 1e-5 at fp32).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro import obs

from . import gradient as _grad
from . import interp as _interp
from . import measures as _meas
from . import spectral as _spec
from . import transport as _tr


def _fused_coefficients(stack: jnp.ndarray, cfg: _tr.TransportConfig):
    """Interpolation coefficients of a stacked field, in the plan's frame
    (halo-extended slab when sharded)."""
    if cfg.shard is not None:
        from repro.distributed import halo as _halo

        return _halo.sl_coefficients(stack, cfg.interp, cfg.shard)
    return _interp.prefilter_for(stack, cfg.interp)


@obs.scoped(obs.MATVEC)
def _matvec_fused(
    vt: jnp.ndarray,
    gs: _grad.GradientState,
    v: jnp.ndarray,
    beta: float,
    gamma: float,
    cfg: _tr.TransportConfig,
) -> jnp.ndarray:
    from repro.kernels.interp3d import interp3d as _k

    nt = int(cfg.nt)
    dt = 1.0 / nt
    # Sources of the incremental state equation, -vt.grad(m_j) for all time
    # steps in one contraction over the cached trajectory gradients.
    sources = -jnp.einsum("c...,tc...->t...", vt, gs.grad_m_traj)

    def inc_epilogue(accs, extras):
        mt_adv, s_adv = accs
        (s1,) = extras
        return mt_adv + 0.5 * dt * (s_adv + s1)

    mt = jnp.zeros_like(gs.m_traj[0])
    for j in range(nt):
        coefs = _fused_coefficients(jnp.stack([mt, sources[j]]), cfg)
        mt = _k.apply_plan_fused(coefs, gs.plan_fwd, [sources[j + 1]],
                                 inc_epilogue)

    meas = _meas.resolve(cfg.measure)
    lt1 = meas.gn_terminal(mt, gs.m_traj[-1], None, cfg,
                           cache=gs.measure_cache)

    # Incremental adjoint: RK2 with source s = (div v) * lam. The predictor
    # substitution lam_new = f_adv + dt/2*(k1 + divv*(f_adv + dt*k1)) fuses
    # the whole update into the kernel epilogue.
    divv = gs.divv

    def adj_epilogue(accs, extras):
        f_adv, k1 = accs
        (dv,) = extras
        return f_adv + 0.5 * dt * (k1 + dv * (f_adv + dt * k1))

    lam = lt1
    traj = [lt1]
    for j in range(nt):
        coefs = _fused_coefficients(jnp.stack([lam, divv * lam]), cfg)
        lam = _k.apply_plan_fused(coefs, gs.plan_adj, [divv], adj_epilogue)
        traj.append(lam)
    lam_traj = jnp.stack(traj[::-1], axis=0)

    # Trapezoid body force as one contraction (cf. transport.body_force).
    w = jnp.full((nt + 1,), dt, dtype=lam_traj.dtype)
    w = w.at[0].set(0.5 * dt).at[-1].set(0.5 * dt)
    body = jnp.einsum("t,t...,tc...->c...", w, lam_traj, gs.grad_m_traj)
    return _spec.apply_regop(vt, beta, gamma, shard=cfg.shard) + body


@obs.scoped(obs.MATVEC)
def matvec(
    vt: jnp.ndarray,
    gs: _grad.GradientState,
    v: jnp.ndarray,
    beta: float,
    gamma: float,
    cfg: _tr.TransportConfig,
) -> jnp.ndarray:
    if (cfg.use_fused_matvec and gs.plan_fwd is not None
            and gs.plan_adj is not None and gs.grad_m_traj is not None):
        return _matvec_fused(vt, gs, v, beta, gamma, cfg)
    mt1 = _tr.solve_inc_state(vt, v, gs.m_traj, cfg, foot=gs.foot_fwd,
                              plan=gs.plan_fwd, grad_m_traj=gs.grad_m_traj)
    meas = _meas.resolve(cfg.measure)
    lt1 = meas.gn_terminal(mt1, gs.m_traj[-1], None, cfg,
                           cache=gs.measure_cache)
    lt_traj = _tr.solve_adjoint(lt1, v, cfg, foot_adj=gs.foot_adj,
                                divv=gs.divv, plan_adj=gs.plan_adj)
    body = _tr.body_force(lt_traj, gs.m_traj, cfg, grad_m_traj=gs.grad_m_traj)
    return _spec.apply_regop(vt, beta, gamma, shard=cfg.shard) + body
