"""Multi-resolution (grid continuation) machinery: spectral restriction /
prolongation and the coarse-to-fine Gauss-Newton driver.

CLAIRE's grid continuation solves the registration on a pyramid of grids:
solve cheaply on a coarse grid, spectrally prolong the velocity to the next
finer grid, and warm-start the solver there. Most Newton iterations then
happen where they are cheap; the fine grid only polishes.

Restriction/prolongation are *spectral* (FFT truncation / zero padding),
which is exact for band-limited fields on the periodic domain and matches
the solver's spectral regularization. Nyquist planes are zeroed on both
transfers: under coarsening the Nyquist mode of an even grid aliases two
fine-grid modes (sign-ambiguous), and keeping it would break the Hermitian
symmetry that guarantees a real result. Consequence: ``restrict(prolong(f))``
is the identity for coarse fields without Nyquist content, and
``prolong(restrict(f))`` reproduces any field band-limited to the coarse
grid.

The stopping test at warm-started levels is measured against the *coarsest*
level's initial gradient norm (``gnorm_ref``): the discrete L2 norms are
grid-consistent for smooth fields, so this approximates the fine-grid
cold-start gradient without paying an extra fine-grid gradient evaluation.

The distance measure (``cfg.measure`` — SSD/NCC/NGF, see ``core.measures``)
rides in the transport config, so every pyramid level optimizes the same
measure without extra plumbing; NCC/NGF values are grid-consistent (global
correlation / domain-mean density), so the coarse-level solution warm-starts
the fine level exactly as with SSD. Per-level configs built here (including
``coarse_variant`` overrides in ``registration.register_multires``) must
preserve ``cfg.measure``.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro import obs

from . import gauss_newton as _gn
from . import spectral as _spec
from . import transport as _tr

GridShape = Tuple[int, int, int]


# ---------------------------------------------------------------------------
# Spectral resampling
# ---------------------------------------------------------------------------


def _resample_full_axis(fh: jnp.ndarray, n_out: int, axis: int) -> jnp.ndarray:
    """Crop/zero-pad one full-FFT axis of a spectrum to ``n_out`` samples.

    Keeps the low-frequency block, drops (crop) or leaves zero (pad) the
    rest, and zeroes the Nyquist plane of the *smaller* grid so the result
    stays Hermitian.
    """
    n_in = fh.shape[axis]
    if n_out == n_in:
        return fh
    n_small = min(n_in, n_out)
    # Retained one-sided bandwidth: positive freqs 0..kpos-1, negative
    # freqs -kneg..-1. For even n_small the Nyquist plane is excluded.
    kpos = (n_small + 1) // 2
    kneg = (n_small - 1) // 2

    def take(start, stop):
        idx = [slice(None)] * fh.ndim
        idx[axis] = slice(start, stop)
        return fh[tuple(idx)]

    pos = take(0, kpos)
    neg = take(n_in - kneg, n_in) if kneg > 0 else None
    mid_shape = list(fh.shape)
    mid_shape[axis] = n_out - kpos - kneg
    mid = jnp.zeros(mid_shape, dtype=fh.dtype)
    parts = [pos, mid] + ([neg] if neg is not None else [])
    return jnp.concatenate(parts, axis=axis)


def _resample_rfft_axis(fh: jnp.ndarray, n_out: int, n_in: int, axis: int = -1) -> jnp.ndarray:
    """Crop/zero-pad the rfft (last) axis to the spectrum of ``n_out`` samples."""
    if n_out == n_in:
        return fh
    n_small = min(n_in, n_out)
    kpos = (n_small + 1) // 2  # modes 0..kpos-1 survive; Nyquist dropped
    idx = [slice(None)] * fh.ndim
    idx[axis] = slice(0, min(kpos, fh.shape[axis]))
    kept = fh[tuple(idx)]
    out_len = n_out // 2 + 1
    pad_shape = list(fh.shape)
    pad_shape[axis] = out_len - kept.shape[axis]
    if pad_shape[axis] == 0:
        return kept
    return jnp.concatenate([kept, jnp.zeros(pad_shape, dtype=fh.dtype)], axis=axis)


def fourier_resample(f: jnp.ndarray, shape_out: Sequence[int]) -> jnp.ndarray:
    """Resample the trailing 3 axes of ``f`` to ``shape_out`` spectrally.

    Works for scalar fields ``(N1,N2,N3)``, vector fields ``(3,N1,N2,N3)``
    and arbitrary leading batch axes. Amplitude-preserving (trigonometric
    interpolation), so field *values* — not integrals — are preserved.
    """
    shape_in = tuple(int(n) for n in f.shape[-3:])
    shape_out = tuple(int(n) for n in shape_out)
    if shape_in == shape_out:
        return f
    fh = jnp.fft.rfftn(f, axes=(-3, -2, -1))
    fh = _resample_full_axis(fh, shape_out[0], axis=f.ndim - 3)
    fh = _resample_full_axis(fh, shape_out[1], axis=f.ndim - 2)
    fh = _resample_rfft_axis(fh, shape_out[2], shape_in[2], axis=f.ndim - 1)
    scale = (shape_out[0] * shape_out[1] * shape_out[2]) / float(
        shape_in[0] * shape_in[1] * shape_in[2]
    )
    out = jnp.fft.irfftn(fh * scale, s=shape_out, axes=(-3, -2, -1))
    return out.astype(f.dtype)


def restrict(f: jnp.ndarray, shape_coarse: Sequence[int]) -> jnp.ndarray:
    """Spectral restriction (ideal low-pass + subsample) to a coarser grid."""
    return fourier_resample(f, shape_coarse)


def prolong(f: jnp.ndarray, shape_fine: Sequence[int]) -> jnp.ndarray:
    """Spectral prolongation (zero-padded FFT interpolation) to a finer grid."""
    return fourier_resample(f, shape_fine)


def default_level_shapes(
    shape: Sequence[int], n_levels: Optional[int] = None, min_size: int = 8
) -> List[GridShape]:
    """Halving pyramid, coarsest first, finest == ``shape``.

    Stops when any axis would drop below ``min_size`` (or after ``n_levels``
    levels). Axes are halved to even sizes so the spectral transfers stay
    exact on the retained band.
    """
    shape = tuple(int(n) for n in shape)
    levels: List[GridShape] = [shape]
    while (n_levels is None or len(levels) < n_levels) and \
            min(levels[-1]) // 2 >= min_size:
        levels.append(tuple(n // 2 for n in levels[-1]))
    levels.reverse()
    return levels


# ---------------------------------------------------------------------------
# Coarse-to-fine driver
# ---------------------------------------------------------------------------


class LevelResult(NamedTuple):
    shape: GridShape
    iters: int
    matvecs: int
    rel_grad: float
    converged: bool
    wall_time_s: float


class MultiresResult(NamedTuple):
    v: jnp.ndarray                  # velocity on the finest grid
    levels: List[GridShape]
    level_results: List[LevelResult]
    iters: int                      # total Newton iterations (all levels)
    fine_iters: int                 # Newton iterations on the finest grid
    matvecs: int                    # total Hessian matvecs (all levels)
    rel_grad: float                 # final relative gradient (finest level)
    converged: bool
    history: List[Dict[str, float]]  # per-iteration records tagged with shape
    wall_time_s: float              # first level's start to last level's end
    #                                 (claire.level spans), compiling included


def solve_multires(
    m0: jnp.ndarray,
    m1: jnp.ndarray,
    cfg: _tr.TransportConfig,
    gn: _gn.GNConfig = _gn.GNConfig(),
    levels: Optional[Sequence[GridShape]] = None,
    coarse_tol: Optional[float] = None,
    level_newton: Optional[Sequence[int]] = None,
    level_cfgs: Optional[Sequence[_tr.TransportConfig]] = None,
    level_weight_dtypes: Optional[Sequence] = None,
    presmooth_sigma: float = 0.0,
    v0: Optional[jnp.ndarray] = None,
    gnorm_ref: Optional[float] = None,
    verbose: bool = False,
    solve_fn=None,
) -> MultiresResult:
    """Coarse-to-fine Gauss-Newton: solve each pyramid level, prolong, refine.

    levels        : grid shapes, coarsest first; default halving pyramid.
    v0            : optional initial velocity at the *finest* grid; it is
                    spectrally restricted to warm-start the coarsest level
                    (longitudinal re-registration: start the whole pyramid
                    from a prior visit's solution instead of zero).
    gnorm_ref     : optional external reference for the relative-gradient
                    stopping test (see ``gauss_newton.solve``); default is
                    the coarsest level's observed initial gradient norm.
                    Warm starts via ``v0`` should pass the cold-start
                    reference here, else the already-small warm gradient
                    becomes the yardstick.
    coarse_tol    : relative-gradient tolerance on non-final levels; default
                    ``gn.tol_rel_grad`` — coarse iterations are cheap, and a
                    tightly solved coarse level is what lets the fine level
                    stop after very few (or zero) Newton steps.
    level_newton  : per-level Newton budgets (default: ``gn.max_newton`` each).
    level_cfgs    : per-level transport configs (e.g. cheap trilinear interp
                    on coarse levels, cubic on the finest).
    level_weight_dtypes : per-level interpolation *weight* dtypes layered on
                    top of ``cfg``/``level_cfgs`` — e.g. ``jnp.bfloat16`` on
                    coarse levels (the paper's reduced-precision texture
                    weights, harmless where the solve is only a warm start)
                    and ``None`` (fp32) on the finest. The downcast applies
                    to the plan weights only; data stays full precision.
    presmooth_sigma : optional Gaussian smoothing (voxels, finest grid) of the
                    *images* before restriction; the spectral truncation is
                    already an ideal low-pass, so this is off by default.
    solve_fn      : per-level solver with the keyword signature of
                    ``gauss_newton.solve(m0, m1, cfg, gn, v0=, gnorm_ref=,
                    eta0=, verbose=)``; defaults to it. The slab-distributed
                    driver injects a closure that re-shards each level's
                    images and warm-start velocity onto the mesh, so the
                    restrict/prolong ladder preserves slab shardings across
                    levels.
    """
    shape = tuple(int(n) for n in m0.shape)
    levels = [tuple(int(n) for n in s) for s in (levels or default_level_shapes(shape))]
    if levels[-1] != shape:
        raise ValueError(f"finest level {levels[-1]} must equal image shape {shape}")
    if level_newton is not None and len(level_newton) != len(levels):
        raise ValueError("level_newton must have one entry per level")
    if level_cfgs is not None and len(level_cfgs) != len(levels):
        raise ValueError("level_cfgs must have one entry per level")
    if level_weight_dtypes is not None:
        if len(level_weight_dtypes) != len(levels):
            raise ValueError("level_weight_dtypes must have one entry per level")
        base = list(level_cfgs) if level_cfgs is not None else [cfg] * len(levels)
        level_cfgs = [c._replace(weight_dtype=wd)
                      for c, wd in zip(base, level_weight_dtypes)]

    m0_s = _spec.gauss_smooth(m0, presmooth_sigma) if presmooth_sigma > 0 else m0
    m1_s = _spec.gauss_smooth(m1, presmooth_sigma) if presmooth_sigma > 0 else m1

    v = None
    level_results: List[LevelResult] = []
    history: List[Dict[str, float]] = []
    total_iters = 0
    total_matvecs = 0
    last: _gn.GNResult | None = None
    first_span = last_span = None

    for li, lev in enumerate(levels):
        with obs.span(obs.LEVEL, level=li, grid=lev) as last_span:
            first_span = first_span or last_span
            is_finest = li == len(levels) - 1
            if is_finest:
                m0_l, m1_l = m0, m1
            else:
                m0_l, m1_l = restrict(m0_s, lev), restrict(m1_s, lev)
            cfg_l = level_cfgs[li] if level_cfgs is not None else cfg
            tol_l = gn.tol_rel_grad if (is_finest or coarse_tol is None) else coarse_tol
            gn_l = gn._replace(
                tol_rel_grad=tol_l,
                max_newton=int(level_newton[li]) if level_newton is not None else gn.max_newton,
                continuation=gn.continuation and li == 0,
            )
            if v is not None:
                v0_l = prolong(v, lev)
            elif v0 is not None:
                # Caller-provided start (finest-grid field): restrict onto the
                # coarsest level instead of silently dropping it.
                v0_l = fourier_resample(v0, lev)
            else:
                v0_l = None
            # First-step PCG forcing at warm levels: the coarse level's final
            # relative gradient is the best available Eisenstat-Walker estimate.
            eta0 = None
            if level_results:
                eta0 = min(gn.forcing_max, level_results[-1].rel_grad ** 0.5)
            if verbose:
                print(f"[multires] level {li}: {lev} (warm={'yes' if v0_l is not None else 'no'})")
            _solve = solve_fn if solve_fn is not None else _gn.solve
            res = _solve(m0_l, m1_l, cfg_l, gn_l, v0=v0_l, gnorm_ref=gnorm_ref,
                         eta0=eta0, verbose=verbose)
            if gnorm_ref is None and res.gnorm0 > 0:
                gnorm_ref = res.gnorm0
            v = res.v
            last = res
            total_iters += res.iters
            total_matvecs += res.matvecs
            level_results.append(
                LevelResult(
                    shape=lev,
                    iters=res.iters,
                    matvecs=res.matvecs,
                    rel_grad=res.rel_grad,
                    converged=res.converged,
                    wall_time_s=res.wall_time_s,
                )
            )
            history.extend(dict(h, grid=lev) for h in res.history)

    return MultiresResult(
        v=v,
        levels=levels,
        level_results=level_results,
        iters=total_iters,
        fine_iters=level_results[-1].iters,
        matvecs=total_matvecs,
        rel_grad=last.rel_grad if last is not None else 0.0,
        converged=last.converged if last is not None else False,
        history=history,
        wall_time_s=obs.elapsed_s(first_span, last_span),
    )
