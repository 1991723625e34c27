"""First-order differential operators: FD8 (the paper's contribution) and FFT.

The paper replaces FFT-based spectral first derivatives (gradient, divergence)
with 8th-order central finite differences (FD8), keeping FFTs only for
high-order operators whose *inverses* are required (see ``spectral.py``).

Two implementation backends are provided:
  * ``backend="jnp"``    : pure jnp.roll stencils (reference; also the XLA path
                           used by the sharded/distributed solver where GSPMD
                           turns rolls into halo collective-permutes).
  * ``backend="pallas"`` : the Pallas TPU pencil kernels in ``repro.kernels.fd8``
                           (validated in interpret mode on CPU).
"""

from __future__ import annotations

from functools import partial
from typing import Literal, Sequence

import jax
import jax.numpy as jnp

from repro import obs

from . import grid as _grid

# 8th-order central-difference coefficients for the first derivative:
#   f'(x_i) ~ (1/h) * sum_k c_k (f_{i+k} - f_{i-k}),  k = 1..4
FD8_COEFFS = (4.0 / 5.0, -1.0 / 5.0, 4.0 / 105.0, -1.0 / 280.0)

Backend = Literal["jnp", "pallas"]


def _fd8_axis_jnp(f: jnp.ndarray, axis: int, h: float) -> jnp.ndarray:
    """d f / d x_axis with periodic BC via jnp.roll (reference path)."""
    out = jnp.zeros_like(f)
    for k, c in enumerate(FD8_COEFFS, start=1):
        out = out + c * (jnp.roll(f, -k, axis=axis) - jnp.roll(f, k, axis=axis))
    return out / h


@obs.scoped(obs.FD8)
def fd8_partial(f: jnp.ndarray, axis: int, backend: Backend = "jnp") -> jnp.ndarray:
    """Partial derivative of a scalar field along ``axis`` (0, 1 or 2)."""
    h = _grid.spacing(f.shape)[axis]
    if backend == "pallas":
        from repro.kernels.fd8 import ops as _k

        return _k.fd8_partial(f, axis)
    return _fd8_axis_jnp(f, axis, h)


@obs.scoped(obs.FD8)
def fd8_grad(f: jnp.ndarray, backend: Backend = "jnp") -> jnp.ndarray:
    """Gradient of a scalar field, output shape (3, N1, N2, N3)."""
    if backend == "pallas":
        from repro.kernels.fd8 import ops as _k

        return _k.fd8_grad(f)
    return jnp.stack([fd8_partial(f, a) for a in range(3)], axis=0)


@obs.scoped(obs.FD8)
def fd8_div(w: jnp.ndarray, backend: Backend = "jnp") -> jnp.ndarray:
    """Divergence of a vector field (3, N1, N2, N3) -> (N1, N2, N3)."""
    if backend == "pallas":
        from repro.kernels.fd8 import ops as _k

        return _k.fd8_div(w)
    return sum(fd8_partial(w[a], a) for a in range(3))


# ---------------------------------------------------------------------------
# Spectral (FFT) first derivatives — the original CLAIRE path, kept as the
# faithful baseline variant (``deriv="fft"``).
# ---------------------------------------------------------------------------


def spectral_partial(f: jnp.ndarray, axis: int) -> jnp.ndarray:
    shape = f.shape
    ks = _grid.wavenumbers(shape, rfft=True)
    masks = _grid.zero_nyquist_mask(shape, rfft=True)
    fh = jnp.fft.rfftn(f)
    out = jnp.fft.irfftn(1j * ks[axis] * masks[axis] * fh, s=shape)
    return out.astype(f.dtype)


def spectral_grad(f: jnp.ndarray) -> jnp.ndarray:
    shape = f.shape
    ks = _grid.wavenumbers(shape, rfft=True)
    masks = _grid.zero_nyquist_mask(shape, rfft=True)
    fh = jnp.fft.rfftn(f)
    outs = [
        jnp.fft.irfftn(1j * ks[a] * masks[a] * fh, s=shape).astype(f.dtype)
        for a in range(3)
    ]
    return jnp.stack(outs, axis=0)


def spectral_div(w: jnp.ndarray) -> jnp.ndarray:
    shape = w.shape[-3:]
    ks = _grid.wavenumbers(shape, rfft=True)
    masks = _grid.zero_nyquist_mask(shape, rfft=True)
    acc = jnp.zeros((shape[0], shape[1], shape[2] // 2 + 1), dtype=jnp.complex64)
    for a in range(3):
        acc = acc + 1j * ks[a] * masks[a] * jnp.fft.rfftn(w[a])
    return jnp.fft.irfftn(acc, s=shape).astype(w.dtype)


# ---------------------------------------------------------------------------
# Dispatch helpers used by the solver (select FD8 vs FFT per config).
# ---------------------------------------------------------------------------


def grad(f: jnp.ndarray, scheme: str = "fd8", backend: Backend = "jnp",
         shard=None) -> jnp.ndarray:
    """``shard`` (a ``halo.ShardInfo``, inside ``shard_map``) switches to the
    slab-distributed operators: FD8 becomes a width-4 halo exchange + local
    stencil, FFT becomes all-gather + local transform + slice."""
    if shard is not None:
        from repro.distributed import halo as _halo

        if scheme == "fd8":
            with jax.named_scope(obs.FD8):
                return _halo.fd8_grad(f, shard)
        if scheme == "fft":
            return _halo.spectral_grad(f, shard)
        raise ValueError(f"unknown derivative scheme: {scheme}")
    if scheme == "fd8":
        return fd8_grad(f, backend=backend)
    if scheme == "fft":
        return spectral_grad(f)
    raise ValueError(f"unknown derivative scheme: {scheme}")


def div(w: jnp.ndarray, scheme: str = "fd8", backend: Backend = "jnp",
        shard=None) -> jnp.ndarray:
    if shard is not None:
        from repro.distributed import halo as _halo

        if scheme == "fd8":
            with jax.named_scope(obs.FD8):
                return _halo.fd8_div(w, shard)
        if scheme == "fft":
            return _halo.spectral_div(w, shard)
        raise ValueError(f"unknown derivative scheme: {scheme}")
    if scheme == "fd8":
        return fd8_div(w, backend=backend)
    if scheme == "fft":
        return spectral_div(w)
    raise ValueError(f"unknown derivative scheme: {scheme}")
