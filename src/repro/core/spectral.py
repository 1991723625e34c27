"""Spectral operators retained from CLAIRE: the H1-div regularization operator
``A``, its inverse (preconditioner), and the Leray projection.

These are *kept* as FFT-based operators — the paper replaces only first-order
derivatives with FD8, because these high-order operators must be *inverted*,
which is trivial in the spectral domain (diagonal / 3x3-block-diagonal per
wavenumber) but would require global linear solves for FD discretizations.

Operator (H1-div regularization, CLAIRE default):
    A(beta, gamma) v  :=  beta * (-Lap) v  +  gamma * grad(div v)_penalty
in Fourier space, per wavenumber k:
    Ahat(k) = beta*|k|^2 * I3  +  gamma * k k^T
Its inverse follows from Sherman–Morrison:
    Ahat(k)^-1 = 1/(beta*|k|^2) * ( I3 - gamma k k^T / (beta*|k|^2 + gamma*|k|^2) )
The k=0 mode (constant velocities, null space of A) is treated as identity for
the inverse (preconditioner must be invertible) and as zero for the forward
operator.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs

from . import grid as _grid


def _khat(shape):
    """Wavenumbers for spectral vector operators.

    Returns (ktilde, k2sum, kt2sum): ``ktilde`` are *Nyquist-masked*
    wavenumbers — the k k^T off-diagonal couplings are sign-ambiguous at the
    Nyquist planes under aliasing (k and -k map to the same index), which
    breaks Hermitian symmetry. Masking the Nyquist modes in the vector part
    (consistent with the masked first-derivative operators) restores it.
    ``k2sum`` (= |k|^2, unmasked) is even-symmetric and safe for the
    Laplacian part; ``kt2sum`` = |ktilde|^2 is used where consistency with
    ktilde matters (Sherman–Morrison denominator, Leray).
    """
    k1, k2, k3 = _grid.wavenumbers(shape, rfft=True)
    m1, m2, m3 = _grid.zero_nyquist_mask(shape, rfft=True)
    kt = (k1 * m1, k2 * m2, k3 * m3)
    k2sum = k1 * k1 + k2 * k2 + k3 * k3
    kt2sum = kt[0] ** 2 + kt[1] ** 2 + kt[2] ** 2
    return kt, k2sum, kt2sum


def _vec_rfftn(v: jnp.ndarray):
    return jnp.stack([jnp.fft.rfftn(v[a]) for a in range(3)], axis=0)


def _vec_irfftn(vh: jnp.ndarray, shape, dtype):
    return jnp.stack(
        [jnp.fft.irfftn(vh[a], s=tuple(shape)).astype(dtype) for a in range(3)], axis=0
    )


@obs.scoped(obs.SPECTRAL)
def apply_regop(v: jnp.ndarray, beta: float, gamma: float, shard=None) -> jnp.ndarray:
    """A v = beta*(-Lap) v + gamma * k (k . vhat)  (vector field -> vector field).

    With ``shard`` (inside ``shard_map``), ``v`` is an x1 slab and the
    operator runs on the all-gathered field and returns the local slab — the
    distributed-FFT fallback (see ROADMAP open items).
    """
    if shard is not None:
        from repro.distributed import halo as _halo

        return _halo.spectral_op(lambda f: apply_regop(f, beta, gamma), v, shard)
    shape = v.shape[-3:]
    ks, k2, _ = _khat(shape)
    vh = _vec_rfftn(v)
    kdotv = ks[0] * vh[0] + ks[1] * vh[1] + ks[2] * vh[2]
    out = jnp.stack([beta * k2 * vh[a] + gamma * ks[a] * kdotv for a in range(3)], axis=0)
    return _vec_irfftn(out, shape, v.dtype)


@obs.scoped(obs.SPECTRAL)
def apply_inv_regop(
    v: jnp.ndarray, beta: float, gamma: float, zero_mean_identity: bool = True,
    shard=None
) -> jnp.ndarray:
    """A^-1 v via the Sherman–Morrison closed form (see module docstring).

    The k=0 mode is mapped by the identity so that the operator is invertible
    (A is singular on constants); this matches using A + P0 where P0 projects
    onto the mean — the standard CLAIRE preconditioner treatment.
    """
    if shard is not None:
        from repro.distributed import halo as _halo

        return _halo.spectral_op(
            lambda f: apply_inv_regop(f, beta, gamma, zero_mean_identity),
            v, shard)
    shape = v.shape[-3:]
    ks, k2, kt2 = _khat(shape)
    vh = _vec_rfftn(v)
    kdotv = ks[0] * vh[0] + ks[1] * vh[1] + ks[2] * vh[2]
    denom_lap = beta * k2
    safe_lap = jnp.where(denom_lap > 0, denom_lap, 1.0)
    corr = gamma / jnp.where(k2 > 0, beta * k2 + gamma * kt2, 1.0)
    outs = []
    for a in range(3):
        t = (vh[a] - corr * ks[a] * kdotv) / safe_lap
        if zero_mean_identity:
            t = jnp.where(denom_lap > 0, t, vh[a])
        else:
            t = jnp.where(denom_lap > 0, t, 0.0)
        outs.append(t)
    return _vec_irfftn(jnp.stack(outs, axis=0), shape, v.dtype)


@obs.scoped(obs.SPECTRAL)
def leray_project(v: jnp.ndarray) -> jnp.ndarray:
    """Leray projection onto divergence-free fields:
    P v = v - grad Lap^-1 div v   <=>   vhat - k (k.vhat) / |k|^2.
    """
    shape = v.shape[-3:]
    ks, _, kt2 = _khat(shape)
    vh = _vec_rfftn(v)
    kdotv = ks[0] * vh[0] + ks[1] * vh[1] + ks[2] * vh[2]
    inv_k2 = jnp.where(kt2 > 0, 1.0 / jnp.where(kt2 > 0, kt2, 1.0), 0.0)
    out = jnp.stack([vh[a] - ks[a] * kdotv * inv_k2 for a in range(3)], axis=0)
    return _vec_irfftn(out, shape, v.dtype)


@obs.scoped(obs.SPECTRAL)
def reg_energy(v: jnp.ndarray, beta: float, gamma: float, shard=None) -> jnp.ndarray:
    """0.5 * <A v, v>  =  0.5*beta*|grad v|^2 + 0.5*gamma*|div v|^2 (spectral).

    Sharded: evaluated on the all-gathered field (the gather is needed for
    the spectral operator anyway), so the scalar is replicated per shard."""
    if shard is not None:
        from repro.distributed import halo as _halo

        full = _halo.gather_full(v, shard)
        return reg_energy(full, beta, gamma)
    av = apply_regop(v, beta, gamma)
    return 0.5 * _grid.inner(av, v, v.shape[-3:])


def gauss_smooth(f: jnp.ndarray, sigma_vox: float) -> jnp.ndarray:
    """Spectral Gaussian smoothing (used for synthetic data generation and
    multi-scale/continuation schemes). sigma is in voxel units of axis 0.

    Uses *unmasked* wavenumbers: the Gaussian filter is even in k, so the
    Nyquist sign ambiguity that forces masking in the odd-order derivative
    operators does not arise — and masking here would leave the filter at
    exp(0) = 1 on the Nyquist planes, passing high-frequency noise through
    unattenuated instead of suppressing it.
    """
    shape = f.shape[-3:]
    k1, k2, k3 = _grid.wavenumbers(shape, rfft=True)
    h = _grid.spacing(shape)
    sig = sigma_vox * h[0]
    filt = jnp.exp(-0.5 * (sig ** 2) * (k1 * k1 + k2 * k2 + k3 * k3))
    if f.ndim == 3:
        return jnp.fft.irfftn(filt * jnp.fft.rfftn(f), s=shape).astype(f.dtype)
    return jnp.stack(
        [
            jnp.fft.irfftn(filt * jnp.fft.rfftn(f[a]), s=shape).astype(f.dtype)
            for a in range(f.shape[0])
        ],
        axis=0,
    )
