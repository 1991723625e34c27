"""The comparison that decides ``correct``.

Every pair the window completed is checked against the plain reference
(``bench.reference``) evaluated at the velocity the program returned, on the
images the program was given. The numbers, each the worst over the pairs:

``warp_rel``  ||m_warped - m0 o y^-1|| / ||m0 o y^-1||: the program's warped
              image against the reference's transport of m0 by the returned
              velocity (semi-Lagrangian transport, B-spline interpolation).
``detF_rel``  largest relative gap of det F's min, mean and max (FD8
              derivatives of the composed displacement).
``obj_rel``   |J - J_ref| / J_ref at the returned velocity: the program's
              objective at its last evaluation (distance + spectral
              regularizer).
``grad_gap``  |r - r_ref| / r_ref, r the relative gradient the program
              reports and r_ref = ||g_ref(v)|| / ||g_ref(0)|| (state and
              adjoint solves, body force, regularizer).
``rel_grad``  r_ref itself: the returned velocity meets the configuration's
              ``tol_rel_grad`` by the reference's own gradient.
``detF_min``  the reference's det F minimum: the map is a diffeomorphism.

The first four take their limits from ``bench/limits/<cell>.json``; the last
two are the guarantees the configuration states.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref


@partial(jax.jit, static_argnames=("nt",))
def reference_readings(m0, m1, v, beta, gamma, nt):
    """Reference warped image, det F stats, J(v), ||g(v)|| and ||g(0)||."""
    warp = ref.state_solve(m0, v, nt)[-1]
    det = ref.det_f(v, nt)
    r = warp - m1
    j = 0.5 * ref.inner(r, r) + 0.5 * ref.inner(ref.reg_apply(v, beta, gamma), v)
    g_v = ref.gradient(m0, m1, v, beta, gamma, nt)
    g_0 = ref.gradient(m0, m1, jnp.zeros_like(v), beta, gamma, nt)
    return dict(warp=warp, detF=jnp.stack([jnp.min(det), jnp.mean(det), jnp.max(det)]),
                j=j, gnorm_v=jnp.sqrt(ref.inner(g_v, g_v)),
                gnorm_0=jnp.sqrt(ref.inner(g_0, g_0)))


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b != 0 else math.inf


def pair_numbers(answer: Dict, m0, m1, solver: Dict) -> Dict[str, float]:
    """Compared numbers of one pair. ``answer`` holds what the program
    returned for it: v, m_warped, detF (min/mean/max), rel_grad and j."""
    r = reference_readings(m0, m1, answer["v"], jnp.float32(solver["beta"]),
                           jnp.float32(solver["gamma"]), nt=int(solver["nt"]))
    warp = r["warp"]
    num = float(jnp.sqrt(jnp.sum((answer["m_warped"] - warp) ** 2)))
    den = float(jnp.sqrt(jnp.sum(warp ** 2)))
    det_ref = [float(x) for x in np.asarray(r["detF"])]
    det_prog = [float(answer["detF"][k]) for k in ("min", "mean", "max")]
    rel_ref = float(r["gnorm_v"]) / float(r["gnorm_0"])
    return dict(
        warp_rel=num / den,
        detF_rel=max(_rel(p, q) for p, q in zip(det_prog, det_ref)),
        obj_rel=_rel(float(answer["j"]), float(r["j"])),
        grad_gap=_rel(float(answer["rel_grad"]), rel_ref),
        rel_grad=rel_ref,
        detF_min=det_ref[0],
    )


def limits_for(limits: Dict, solver: Dict) -> Dict[str, Dict]:
    """Each compared number's bound: ``{"max": x}`` or ``{"min": x}``."""
    out = {k: {"max": float(v["max"])} for k, v in limits.items()
           if isinstance(v, dict) and "max" in v}
    out["rel_grad"] = {"max": float(solver["tol_rel_grad"])}
    out["detF_min"] = {"min": 0.0}
    return out


def worst(per_pair: List[Dict[str, float]], bounds: Dict[str, Dict]) -> Dict[str, float]:
    """Worst reading of each number over the pairs (NaN wins)."""
    out = {}
    for name, b in bounds.items():
        vals = [p[name] for p in per_pair]
        if any(math.isnan(x) for x in vals):
            out[name] = math.nan
        elif "max" in b:
            out[name] = max(vals)
        else:
            out[name] = min(vals)
    return out


def within(value: float, bound: Dict) -> bool:
    if "max" in bound:
        return value <= bound["max"]
    return value > bound["min"]
