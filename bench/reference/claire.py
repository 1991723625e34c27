"""The reference operators, one plain function each (float32, jnp).

Conventions: scalar fields ``(N1, N2, N3)``, vector fields ``(3, N1, N2,
N3)``, query points in index units (physical coordinate / h). Every function
works on one pair; callers loop over pairs.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

TWO_PI = 2.0 * math.pi

#: FD8 first-derivative coefficients c_k, k = 1..4:
#: f'(x_i) = (1/h) * sum_k c_k (f_{i+k} - f_{i-k}).
FD8 = (4.0 / 5.0, -1.0 / 5.0, 4.0 / 105.0, -1.0 / 280.0)

#: Cubic B-spline prefilter as a finite convolution: the exact periodic
#: inverse of [1/6, 4/6, 1/6] has the impulse response
#: h_n = -6 z^{|n|+1} / (1 - z^2), z = sqrt(3) - 2, cut at |n| <= 7.
_Z = math.sqrt(3.0) - 2.0
PREFILTER_RADIUS = 7
PREFILTER_TAPS = [-6.0 * _Z ** (abs(n) + 1) / (1.0 - _Z * _Z)
                  for n in range(-PREFILTER_RADIUS, PREFILTER_RADIUS + 1)]


def spacing(n: int) -> float:
    return TWO_PI / n


def inner(a, b):
    """L2 inner product on the grid (quadrature weight h^3)."""
    n = a.shape[-1]
    return spacing(n) ** 3 * jnp.sum(a * b)


def index_grid(shape):
    axes = [jnp.arange(n, dtype=jnp.float32) for n in shape]
    return jnp.stack(jnp.meshgrid(*axes, indexing="ij"), axis=0)


# --- derivatives -----------------------------------------------------------

def fd8_partial(f, axis):
    h = spacing(f.shape[axis])
    out = jnp.zeros_like(f)
    for k, c in enumerate(FD8, start=1):
        out = out + c * (jnp.roll(f, -k, axis=axis) - jnp.roll(f, k, axis=axis))
    return out / h


def fd8_grad(f):
    return jnp.stack([fd8_partial(f, a) for a in range(3)], axis=0)


def fd8_div(w):
    return fd8_partial(w[0], 0) + fd8_partial(w[1], 1) + fd8_partial(w[2], 2)


# --- interpolation -----------------------------------------------------------

def prefilter(f):
    """B-spline coefficients of a scalar field (separable, periodic)."""
    out = f
    for axis in range(3):
        acc = PREFILTER_TAPS[PREFILTER_RADIUS] * out
        for k in range(1, PREFILTER_RADIUS + 1):
            c = PREFILTER_TAPS[PREFILTER_RADIUS + k]
            acc = acc + c * (jnp.roll(out, -k, axis=axis) + jnp.roll(out, k, axis=axis))
        out = acc
    return out


def _bspline(t):
    t2 = t * t
    t3 = t2 * t
    return jnp.stack([(1.0 - 3.0 * t + 3.0 * t2 - t3) / 6.0,
                      (4.0 - 6.0 * t2 + 3.0 * t3) / 6.0,
                      (1.0 + 3.0 * t + 3.0 * t2 - 3.0 * t3) / 6.0,
                      t3 / 6.0], axis=0)


def interp(coef, q):
    """Cubic B-spline value of coefficients ``coef`` at points ``q``.

    The 64 taps are visited one by one: each gathers its coefficient from
    the periodic grid and adds it with its tensor-product weight.
    """
    n1, n2, n3 = coef.shape
    qf = jnp.floor(q)
    base = qf.astype(jnp.int32) - 1
    w = [_bspline(q[d] - qf[d]) for d in range(3)]
    flat = coef.reshape(-1)

    def tap(k, acc):
        a, b, c = k // 16, (k // 4) % 4, k % 4
        i1 = jnp.mod(base[0] + a, n1)
        i2 = jnp.mod(base[1] + b, n2)
        i3 = jnp.mod(base[2] + c, n3)
        vals = jnp.take(flat, (i1 * n2 + i2) * n3 + i3)
        return acc + w[0][a] * w[1][b] * w[2][c] * vals

    return jax.lax.fori_loop(0, 64, tap, jnp.zeros(q.shape[1:], jnp.float32))


# --- semi-Lagrangian transport -------------------------------------------------

def footpoints(v, dt, sign):
    """RK2 characteristic footpoints X = x - s dt v(x - s dt/2 v(x)), index units."""
    h = spacing(v.shape[-1])
    x = index_grid(v.shape[1:])
    q_mid = x - sign * 0.5 * dt * v / h
    v_mid = jnp.stack([interp(prefilter(v[d]), q_mid) for d in range(3)], axis=0)
    return x - sign * dt * v_mid / h


def state_solve(m0, v, nt):
    """State equation dm/dt + v.grad m = 0: trajectory (nt+1, N1, N2, N3)."""
    foot = footpoints(v, 1.0 / nt, 1.0)
    traj = [m0]
    for _ in range(nt):
        traj.append(interp(prefilter(traj[-1]), foot))
    return jnp.stack(traj, axis=0)


def adjoint_solve(lam1, v, nt):
    """Adjoint -dl/dt - div(l v) = 0 from l(1) = lam1, by RK2 along -v with
    the source (div v) l. Trajectory in forward time order."""
    dt = 1.0 / nt
    foot = footpoints(v, dt, -1.0)
    divv = fd8_div(v)
    traj = [lam1]
    lam = lam1
    for _ in range(nt):
        f_adv = interp(prefilter(lam), foot)
        k1 = interp(prefilter(divv * lam), foot)
        k2 = divv * (f_adv + dt * k1)
        lam = f_adv + 0.5 * dt * (k1 + k2)
        traj.append(lam)
    return jnp.stack(traj[::-1], axis=0)


# --- regularizer, objective, gradient ----------------------------------------

def _wavenumbers(n):
    k = jnp.fft.fftfreq(n, d=1.0 / n).astype(jnp.float32)
    kr = jnp.fft.rfftfreq(n, d=1.0 / n).astype(jnp.float32)
    nyq = lambda kk: jnp.where(jnp.abs(kk) == n // 2, 0.0, kk)  # noqa: E731
    ks = (k.reshape(-1, 1, 1), k.reshape(1, -1, 1), kr.reshape(1, 1, -1))
    kt = tuple(nyq(kk) for kk in ks)
    return ks, kt


def reg_apply(v, beta, gamma):
    """A v = beta (-Lap) v + gamma grad(div v), spectral. The grad-div part
    uses wavenumbers with the Nyquist modes zeroed (sign-ambiguous there)."""
    n = v.shape[-1]
    ks, kt = _wavenumbers(n)
    k2 = ks[0] ** 2 + ks[1] ** 2 + ks[2] ** 2
    vh = [jnp.fft.rfftn(v[d]) for d in range(3)]
    kdotv = kt[0] * vh[0] + kt[1] * vh[1] + kt[2] * vh[2]
    return jnp.stack([jnp.fft.irfftn(beta * k2 * vh[d] + gamma * kt[d] * kdotv,
                                     s=v.shape[1:]).astype(jnp.float32)
                      for d in range(3)], axis=0)


def objective(m0, m1, v, beta, gamma, nt):
    """J(v) = 0.5 ||m(1) - m1||^2 + 0.5 <A v, v>."""
    m_end = state_solve(m0, v, nt)[-1]
    r = m_end - m1
    return 0.5 * inner(r, r) + 0.5 * inner(reg_apply(v, beta, gamma), v)


def gradient(m0, m1, v, beta, gamma, nt):
    """Reduced gradient g = A v + int_0^1 lam grad(m) dt (trapezoid in time)."""
    dt = 1.0 / nt
    m_traj = state_solve(m0, v, nt)
    lam_traj = adjoint_solve(m1 - m_traj[-1], v, nt)
    body = jnp.zeros_like(v)
    for j in range(nt + 1):
        wj = 0.5 * dt if j in (0, nt) else dt
        body = body + wj * lam_traj[j][None] * fd8_grad(m_traj[j])
    return reg_apply(v, beta, gamma) + body


def relative_mismatch(m_warped, m1, m0):
    return jnp.sqrt(inner(m_warped - m1, m_warped - m1) / inner(m1 - m0, m1 - m0))


def det_f(v, nt):
    """det(I + grad u), u = y - x composed from the nt SL footpoint maps."""
    n = v.shape[-1]
    h = spacing(n)
    foot = footpoints(v, 1.0 / nt, 1.0)
    step = (foot - index_grid(v.shape[1:])) * h
    u = jnp.zeros_like(v)
    for _ in range(nt):
        u = jnp.stack([interp(prefilter(u[d]), foot) for d in range(3)], axis=0) + step
    J = [[fd8_partial(u[i], j) for j in range(3)] for i in range(3)]
    f = [[J[i][j] + (1.0 if i == j else 0.0) for j in range(3)] for i in range(3)]
    return (f[0][0] * (f[1][1] * f[2][2] - f[1][2] * f[2][1])
            - f[0][1] * (f[1][0] * f[2][2] - f[1][2] * f[2][0])
            + f[0][2] * (f[1][0] * f[2][1] - f[1][1] * f[2][0]))


def gauss_smooth(f, sigma_vox):
    """Spectral Gaussian filter of a scalar field (sigma in voxels)."""
    n = f.shape[-1]
    ks, _ = _wavenumbers(n)
    sig = sigma_vox * spacing(n)
    filt = jnp.exp(-0.5 * sig ** 2 * (ks[0] ** 2 + ks[1] ** 2 + ks[2] ** 2))
    return jnp.fft.irfftn(filt * jnp.fft.rfftn(f), s=f.shape).astype(jnp.float32)
