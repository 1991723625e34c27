"""Objective functional (1a): distance measure + H1-div regularization.

The mismatch term dispatches on ``cfg.measure`` (SSD/NCC/NGF — see
``core.measures``); ``mismatch`` below is the SSD special case kept for the
reported-metric helpers and direct callers.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro import obs

from . import grid as _grid
from . import measures as _meas
from . import spectral as _spec
from . import transport as _tr


def mismatch(m_final: jnp.ndarray, m1: jnp.ndarray, shard=None) -> jnp.ndarray:
    """0.5 * || m(.,1) - m1 ||_L2^2 (global; psum-reduced when sharded)."""
    r = m_final - m1
    return 0.5 * _grid.inner(r, r, shard=shard)


@obs.scoped(obs.SCORE)
def relative_mismatch(m_final: jnp.ndarray, m1: jnp.ndarray, m0: jnp.ndarray) -> jnp.ndarray:
    """The paper's reported metric: ||m(.,1)-m1||_2 / ||m1 - m0||_2.

    An identical pair (``m1 == m0``) is already matched: return 0.0 instead
    of propagating the 0/0 NaN into results and serve metrics.
    """
    num = _grid.norm_l2(m_final - m1)
    den = _grid.norm_l2(m1 - m0)
    return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)


def objective(
    m0: jnp.ndarray,
    m1: jnp.ndarray,
    v: jnp.ndarray,
    beta: float,
    gamma: float,
    cfg: _tr.TransportConfig,
    foot: jnp.ndarray | None = None,
    plan=None,
) -> jnp.ndarray:
    """J(v) per eq. (1a); solves the state equation internally.

    ``foot`` / ``plan`` let callers reuse footpoints (and their
    interpolation plan) when ``v`` matches the iterate they were traced for;
    otherwise ``solve_state`` traces footpoints for this ``v`` and builds
    one plan that is shared by all Nt SL steps of the evaluation.
    """
    m_traj = _tr.solve_state(m0, v, cfg, foot=foot, plan=plan)
    meas = _meas.resolve(cfg.measure)
    return (meas.value(m_traj[-1], m1, cfg)
            + _spec.reg_energy(v, beta, gamma, shard=cfg.shard))
