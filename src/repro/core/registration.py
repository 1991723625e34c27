"""Public registration API: ``register(m0, m1, ...)``.

This is the user-facing entry point of the paper's system. It wires together
the Gauss-Newton-Krylov solver, the transport configuration (interpolation /
derivative variant selection — the paper's Table 6 variants), and the quality
metrics reported in the paper (relative mismatch, det(F) statistics, Dice).

Variant tags follow the paper:
    fft-cubic   : FFT first derivatives + cubic interpolation  (CPU-CLAIRE baseline)
    fd8-cubic   : FD8 first derivatives + cubic B-spline interpolation
    fd8-linear  : FD8 first derivatives + trilinear interpolation (fastest)
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import gauss_newton as _gn
from . import measures as _meas
from . import metrics as _metrics
from . import multires as _mr
from . import objective as _obj
from . import transport as _tr

#: The paper's Table 6 variant tags -> (deriv scheme, interpolation method).
VARIANTS: Dict[str, Dict[str, str]] = {
    "fft-cubic": dict(deriv="fft", interp="cubic_lagrange"),
    "fft-bspline": dict(deriv="fft", interp="cubic_bspline"),
    "fd8-cubic": dict(deriv="fd8", interp="cubic_bspline"),
    "fd8-lagrange": dict(deriv="fd8", interp="cubic_lagrange"),
    "fd8-linear": dict(deriv="fd8", interp="linear"),
}


def _unshard(v, mesh):
    """Replicate a slab-sharded velocity for post-solve scoring.

    ``device_put`` to the fully-replicated sharding gathers in place (and,
    unlike a host round trip, stays valid for non-fully-addressable arrays
    on multi-process meshes).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(v, NamedSharding(mesh, PartitionSpec()))


def _score_single(m0, m1, v, cfg):
    """Post-solve quality metrics (warped image, rel. mismatch, det F)."""
    m_warped = _metrics.warp_image(m0, v, cfg)
    mis = float(_obj.relative_mismatch(m_warped, m1, m0))
    detf = {k: float(val) for k, val in _metrics.detF_stats(v, cfg).items()}
    return m_warped, mis, detf


def _score_batch(m0, m1, v, cfg):
    """Batched post-solve scoring: one dispatch for all pairs."""
    bsz = m0.shape[0]
    m_warped = jax.vmap(lambda m, w: _metrics.warp_image(m, w, cfg))(m0, v)
    mis = [
        float(_obj.relative_mismatch(m_warped[b], m1[b], m0[b])) for b in range(bsz)
    ]
    detf_b = jax.vmap(lambda w: _metrics.detF_stats(w, cfg))(v)
    detf = [
        {k: float(detf_b[k][b]) for k in detf_b} for b in range(bsz)
    ]
    return m_warped, mis, detf


class RegistrationResult(NamedTuple):
    v: jnp.ndarray                 # stationary velocity field (3, N1, N2, N3)
    m_warped: jnp.ndarray          # m0 transported to t=1
    mismatch_rel: float            # ||m(1)-m1|| / ||m1-m0||
    detF: Dict[str, float]         # min / mean / max of det(grad y)
    iters: int
    matvecs: int
    rel_grad: float
    converged: bool
    wall_time_s: float
    history: list


def make_transport_config(
    variant: str = "fd8-cubic",
    nt: int = 4,
    backend: str = "jnp",
    mixed_precision: bool = False,
    use_plan: bool = True,
    measure: object = "ssd",
    use_fused_matvec: bool = False,
) -> _tr.TransportConfig:
    """``use_plan=False`` disables the build-once/apply-many interpolation
    plans (per-step weight recomputation; the pre-plan reference path, kept
    for benchmarking and regression tests). ``measure`` selects the distance
    measure (``"ssd" | "ncc" | "ngf"`` or a ``measures.DistanceMeasure``
    instance). ``use_fused_matvec`` routes the PCG Hessian matvec through
    the fused gather+epilogue Pallas kernel (requires ``use_plan``).

    On the TPU, ``backend="pallas"`` and ``use_fused_matvec=True`` raise
    ``NotImplementedError``: both interpolate through the Pallas gather
    kernels of ``kernels.interp3d``, which Mosaic cannot compile."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; choose from {sorted(VARIANTS)}")
    _meas.resolve(measure)  # fail fast on unknown measure names
    if use_fused_matvec and not use_plan:
        raise ValueError("use_fused_matvec requires use_plan=True (the fused "
                         "kernel consumes prebuilt interpolation plans)")
    if (backend == "pallas" or use_fused_matvec) and jax.default_backend() == "tpu":
        raise NotImplementedError(
            "backend='pallas' and use_fused_matvec=True interpolate through "
            "the Pallas gather kernels of repro.kernels.interp3d, which Mosaic "
            "refuses for the TPU: 'Only 2D gather is supported' for the flat "
            "jnp.take of apply_plan_pallas/apply_plan_fused, and a halo block "
            "whose last two dims are not multiples of (8, 128) in "
            "interp3d_pallas. Use backend='jnp' and use_fused_matvec=False.")
    sel = VARIANTS[variant]
    return _tr.TransportConfig(
        interp=sel["interp"],
        deriv=sel["deriv"],
        nt=nt,
        backend=backend,
        weight_dtype=jnp.bfloat16 if mixed_precision else None,
        use_plan=use_plan,
        measure=measure,
        use_fused_matvec=use_fused_matvec,
    )


def register(
    m0: jnp.ndarray,
    m1: jnp.ndarray,
    variant: str = "fd8-cubic",
    beta: float = 5e-4,
    gamma: float = 1e-4,
    nt: int = 4,
    tol_rel_grad: float = 5e-2,
    max_newton: int = 50,
    continuation: bool = False,
    backend: str = "jnp",
    mixed_precision: bool = False,
    use_plan: bool = True,
    measure: object = "ssd",
    use_fused_matvec: bool = False,
    v0: Optional[jnp.ndarray] = None,
    gnorm_ref: Optional[float] = None,
    verbose: bool = False,
) -> RegistrationResult:
    """Register template ``m0`` to reference ``m1`` (paper eq. (1)).

    Returns the stationary velocity ``v`` and the paper's quality metrics.
    ``v0`` warm-starts the Gauss-Newton iteration (e.g. from a prior solve
    of the same subject); ``gnorm_ref`` fixes the stopping-test reference
    for such warm starts (see ``gauss_newton.solve``). ``measure`` selects
    the distance term (``"ssd" | "ncc" | "ngf"``); ``mismatch_rel`` stays
    the paper's L2 metric regardless, so for non-SSD measures judge quality
    by ``converged``/Dice rather than ``mismatch_rel``.
    """
    cfg = make_transport_config(variant, nt=nt, backend=backend,
                                mixed_precision=mixed_precision,
                                use_plan=use_plan, measure=measure,
                                use_fused_matvec=use_fused_matvec)
    gn_cfg = _gn.GNConfig(
        beta=beta,
        gamma=gamma,
        tol_rel_grad=tol_rel_grad,
        max_newton=max_newton,
        continuation=continuation,
    )
    res = _gn.solve(m0, m1, cfg, gn_cfg, v0=v0, gnorm_ref=gnorm_ref,
                    verbose=verbose)
    m_warped, mis, detf = _score_single(m0, m1, res.v, cfg)
    return RegistrationResult(
        v=res.v,
        m_warped=m_warped,
        mismatch_rel=mis,
        detF=detf,
        iters=res.iters,
        matvecs=res.matvecs,
        rel_grad=res.rel_grad,
        converged=res.converged,
        wall_time_s=res.wall_time_s,
        history=res.history,
    )


class MultiresRegistrationResult(NamedTuple):
    v: jnp.ndarray
    m_warped: jnp.ndarray
    mismatch_rel: float
    detF: Dict[str, float]
    iters: int                      # Newton iterations summed over all levels
    fine_iters: int                 # Newton iterations on the finest grid only
    matvecs: int
    rel_grad: float
    converged: bool
    wall_time_s: float
    levels: List[Tuple[int, int, int]]
    level_results: list             # multires.LevelResult per level
    history: list


def register_multires(
    m0: jnp.ndarray,
    m1: jnp.ndarray,
    variant: str = "fd8-cubic",
    beta: float = 5e-4,
    gamma: float = 1e-4,
    nt: int = 4,
    tol_rel_grad: float = 5e-2,
    max_newton: int = 50,
    continuation: bool = False,
    levels: Optional[Sequence[Tuple[int, int, int]]] = None,
    n_levels: Optional[int] = None,
    min_size: int = 8,
    coarse_tol: Optional[float] = None,
    level_newton: Optional[Sequence[int]] = None,
    coarse_variant: Optional[str] = None,
    presmooth_sigma: float = 0.0,
    backend: str = "jnp",
    mixed_precision: bool = False,
    use_plan: bool = True,
    measure: object = "ssd",
    use_fused_matvec: bool = False,
    v0: Optional[jnp.ndarray] = None,
    gnorm_ref: Optional[float] = None,
    verbose: bool = False,
) -> MultiresRegistrationResult:
    """Coarse-to-fine registration (CLAIRE grid continuation).

    The pyramid is ``levels`` (coarsest first) or a default halving schedule;
    each level warm-starts from the spectrally prolonged coarse velocity.
    ``coarse_variant`` optionally selects a cheaper solver variant (e.g.
    ``"fd8-linear"``) on all but the finest level. ``measure`` applies on
    every level (the restricted images feed the same distance term).
    """
    cfg = make_transport_config(variant, nt=nt, backend=backend,
                                mixed_precision=mixed_precision,
                                use_plan=use_plan, measure=measure,
                                use_fused_matvec=use_fused_matvec)
    gn_cfg = _gn.GNConfig(
        beta=beta,
        gamma=gamma,
        tol_rel_grad=tol_rel_grad,
        max_newton=max_newton,
        continuation=continuation,  # applied on the coarsest level only
    )
    if levels is None:
        levels = _mr.default_level_shapes(m0.shape, n_levels=n_levels,
                                          min_size=min_size)
    level_cfgs = None
    if coarse_variant is not None:
        coarse_cfg = make_transport_config(coarse_variant, nt=nt, backend=backend,
                                           mixed_precision=mixed_precision,
                                           use_plan=use_plan, measure=measure,
                                           use_fused_matvec=use_fused_matvec)
        level_cfgs = [coarse_cfg] * (len(levels) - 1) + [cfg]
    res = _mr.solve_multires(
        m0, m1, cfg, gn_cfg,
        levels=levels,
        coarse_tol=coarse_tol,
        level_newton=level_newton,
        level_cfgs=level_cfgs,
        presmooth_sigma=presmooth_sigma,
        v0=v0,
        gnorm_ref=gnorm_ref,
        verbose=verbose,
    )
    m_warped, mis, detf = _score_single(m0, m1, res.v, cfg)
    return MultiresRegistrationResult(
        v=res.v,
        m_warped=m_warped,
        mismatch_rel=mis,
        detF=detf,
        iters=res.iters,
        fine_iters=res.fine_iters,
        matvecs=res.matvecs,
        rel_grad=res.rel_grad,
        converged=res.converged,
        wall_time_s=res.wall_time_s,
        levels=list(res.levels),
        level_results=list(res.level_results),
        history=res.history,
    )


class BatchRegistrationResult(NamedTuple):
    v: jnp.ndarray                 # (B, 3, N1, N2, N3)
    m_warped: jnp.ndarray          # (B, N1, N2, N3)
    mismatch_rel: List[float]      # per pair
    detF: List[Dict[str, float]]   # per pair
    iters: List[int]
    matvecs: List[int]
    rel_grad: List[float]
    converged: List[bool]
    wall_time_s: float
    history: list


def register_batch(
    m0: jnp.ndarray,
    m1: jnp.ndarray,
    variant: str = "fd8-cubic",
    beta: float = 5e-4,
    gamma: float = 1e-4,
    nt: int = 4,
    tol_rel_grad: float = 5e-2,
    max_newton: int = 50,
    backend: str = "jnp",
    mixed_precision: bool = False,
    use_plan: bool = True,
    measure: object = "ssd",
    use_fused_matvec: bool = False,
    v0: Optional[jnp.ndarray] = None,
    gnorm_ref=None,
    verbose: bool = False,
) -> BatchRegistrationResult:
    """Register a batch of pairs ``m0[b] -> m1[b]`` with one vmapped solver.

    One compiled Newton step serves all pairs; per-pair convergence is
    handled with masked updates, so the per-pair results match independent
    :func:`register` calls (to floating-point noise) while the throughput is
    that of a single batched computation — the population-study / ensemble
    workload of the multi-node CLAIRE follow-up.
    """
    cfg = make_transport_config(variant, nt=nt, backend=backend,
                                mixed_precision=mixed_precision,
                                use_plan=use_plan, measure=measure,
                                use_fused_matvec=use_fused_matvec)
    gn_cfg = _gn.GNConfig(
        beta=beta,
        gamma=gamma,
        tol_rel_grad=tol_rel_grad,
        max_newton=max_newton,
    )
    res = _gn.solve_batch(m0, m1, cfg, gn_cfg, v0=v0, gnorm_ref=gnorm_ref,
                          verbose=verbose)
    # Post-solve scoring stays batched too: one dispatch for all pairs.
    m_warped, mis, detf = _score_batch(m0, m1, res.v, cfg)
    return BatchRegistrationResult(
        v=res.v,
        m_warped=m_warped,
        mismatch_rel=mis,
        detF=detf,
        iters=[int(i) for i in res.iters],
        matvecs=[int(m) for m in res.matvecs],
        rel_grad=[float(r) for r in res.rel_grad],
        converged=[bool(c) for c in res.converged],
        wall_time_s=res.wall_time_s,
        history=res.history,
    )


# ---------------------------------------------------------------------------
# Slab-distributed registration: the full Gauss-Newton-Krylov loop under
# shard_map on an (ensemble, slab) mesh (see repro.distributed.claire_dist).
# ---------------------------------------------------------------------------


def register_sharded(
    m0: jnp.ndarray,
    m1: jnp.ndarray,
    mesh,
    variant: str = "fd8-cubic",
    beta: float = 5e-4,
    gamma: float = 1e-4,
    nt: int = 4,
    tol_rel_grad: float = 5e-2,
    max_newton: int = 50,
    continuation: bool = False,
    slab_axis: Optional[str] = None,
    ensemble_axis: Optional[str] = None,
    halo: int = 6,
    multires: bool = False,
    levels: Optional[Sequence[Tuple[int, int, int]]] = None,
    n_levels: Optional[int] = None,
    min_size: int = 8,
    coarse_tol: Optional[float] = None,
    level_newton: Optional[Sequence[int]] = None,
    coarse_variant: Optional[str] = None,
    presmooth_sigma: float = 0.0,
    backend: str = "jnp",
    mixed_precision: bool = False,
    use_plan: bool = True,
    measure: object = "ssd",
    use_fused_matvec: bool = False,
    halo_compression: str = "none",
    v0: Optional[jnp.ndarray] = None,
    gnorm_ref=None,
    verbose: bool = False,
):
    """Register with the grid sharded in x1 slabs over ``mesh``.

    The entire Gauss-Newton-Krylov solve runs under ``shard_map``: FD8 and
    semi-Lagrangian interpolation exchange explicit CFL-bounded halos,
    spectral operators fall back to all-gather + local FFT, and inner
    products are psum reductions — matching the single-device
    :func:`register` to floating-point reduction noise (see
    ``repro.distributed.claire_dist``).

    Dispatch mirrors the single-device entry points:
      * ``m0.ndim == 3``                -> slab-parallel :func:`register`
      * ``m0.ndim == 3`` + ``multires`` (or ``levels``) -> slab-parallel
        :func:`register_multires`; each level re-shards its restricted
        images and prolonged warm start onto the same slab axes.
      * ``m0.ndim == 4``                -> ensemble x slab :func:`register_batch`
        (pairs over ``ensemble_axis``, grid over ``slab_axis``).

    ``halo`` is the interpolation halo width in voxels and is a *contract*:
    every per-step footpoint displacement along x1 must stay within
    ``halo - 2`` voxels (cubic stencil margin; FD8 and prefilter halos are
    derived internally). Out-of-contract footpoints are clamped to the
    exchanged slab — the solve still runs but values near slab boundaries
    silently degrade versus :func:`register`, exactly like exceeding the
    Pallas kernel's ``PALLAS_DISPLACEMENT_BOUND``. The solver regime
    (``|v| dt / h`` of a few voxels) satisfies the default; raise ``halo``
    for aggressive velocities. Post-solve metrics are computed on the
    gathered velocity. A mesh with ``Explicit`` axes (the default of
    ``jax.make_mesh``) is converted to ``Auto`` axes on the same devices.
    """
    from repro.distributed import claire_dist as _dist
    from repro.launch.mesh import auto_axes

    mesh = auto_axes(mesh)
    cfg = make_transport_config(variant, nt=nt, backend=backend,
                                mixed_precision=mixed_precision,
                                use_plan=use_plan, measure=measure,
                                use_fused_matvec=use_fused_matvec)
    gn_cfg = _gn.GNConfig(
        beta=beta,
        gamma=gamma,
        tol_rel_grad=tol_rel_grad,
        max_newton=max_newton,
        continuation=continuation,
    )

    if m0.ndim == 4:
        if multires or levels is not None:
            raise ValueError("batched sharded registration has no multires mode")
        res = _dist.solve_ensemble_slab(
            m0, m1, cfg, gn_cfg, mesh=mesh, ens_axis=ensemble_axis,
            slab_axis=slab_axis, halo=halo, compress=halo_compression,
            v0=v0, gnorm_ref=gnorm_ref, verbose=verbose)
        v = _unshard(res.v, mesh)
        m_warped, mis, detf = _score_batch(m0, m1, v, cfg)
        return BatchRegistrationResult(
            v=v,
            m_warped=m_warped,
            mismatch_rel=mis,
            detF=detf,
            iters=[int(i) for i in res.iters],
            matvecs=[int(m) for m in res.matvecs],
            rel_grad=[float(r) for r in res.rel_grad],
            converged=[bool(c) for c in res.converged],
            wall_time_s=res.wall_time_s,
            history=res.history,
        )

    if multires or levels is not None:
        if levels is None:
            levels = _mr.default_level_shapes(m0.shape, n_levels=n_levels,
                                              min_size=min_size)
        level_cfgs = None
        if coarse_variant is not None:
            coarse_cfg = make_transport_config(
                coarse_variant, nt=nt, backend=backend,
                mixed_precision=mixed_precision, use_plan=use_plan,
                measure=measure, use_fused_matvec=use_fused_matvec)
            level_cfgs = [coarse_cfg] * (len(levels) - 1) + [cfg]

        def solve_fn(m0_l, m1_l, cfg_l, gn_l, **kw):
            # Re-shard each level onto the mesh: restrict/prolong run on the
            # gathered fields, the level solve is slab-parallel again.
            return _dist.solve_slab(m0_l, m1_l, cfg_l, gn_l, mesh=mesh,
                                    slab_axis=slab_axis, halo=halo,
                                    compress=halo_compression, **kw)

        res = _mr.solve_multires(
            m0, m1, cfg, gn_cfg,
            levels=levels,
            coarse_tol=coarse_tol,
            level_newton=level_newton,
            level_cfgs=level_cfgs,
            presmooth_sigma=presmooth_sigma,
            v0=v0,
            gnorm_ref=gnorm_ref,
            verbose=verbose,
            solve_fn=solve_fn,
        )
        v = _unshard(res.v, mesh)
        m_warped, mis, detf = _score_single(m0, m1, v, cfg)
        return MultiresRegistrationResult(
            v=v,
            m_warped=m_warped,
            mismatch_rel=mis,
            detF=detf,
            iters=res.iters,
            fine_iters=res.fine_iters,
            matvecs=res.matvecs,
            rel_grad=res.rel_grad,
            converged=res.converged,
            wall_time_s=res.wall_time_s,
            levels=list(res.levels),
            level_results=list(res.level_results),
            history=res.history,
        )

    res = _dist.solve_slab(m0, m1, cfg, gn_cfg, mesh=mesh,
                           slab_axis=slab_axis, halo=halo,
                           compress=halo_compression, v0=v0,
                           gnorm_ref=gnorm_ref, verbose=verbose)
    v = _unshard(res.v, mesh)
    m_warped, mis, detf = _score_single(m0, m1, v, cfg)
    return RegistrationResult(
        v=v,
        m_warped=m_warped,
        mismatch_rel=mis,
        detF=detf,
        iters=res.iters,
        matvecs=res.matvecs,
        rel_grad=res.rel_grad,
        converged=res.converged,
        wall_time_s=res.wall_time_s,
        history=res.history,
    )
