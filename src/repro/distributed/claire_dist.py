"""Distributed registration — the paper's §1.2 'future work', implemented.

Two orthogonal parallel modes:

ENSEMBLE (data) parallelism — the paper's motivating clinical workload is
  thousands of independent registrations ("MPI parallelism cannot help since
  multiple registration tasks can take place in an embarrassingly parallel
  way"). ``ensemble_newton_step`` vmaps the Gauss-Newton step over a batch
  of image pairs and shards the batch over the mesh data axes. Zero
  collectives per step by construction.

SLAB (grid) parallelism — one registration spread over the ``model`` axis:
  fields are sharded on the x1 axis. Under ``jit`` + GSPMD:
    * FD8 rolls        -> width-k collective-permute halo exchanges,
    * interpolation    -> gathers (GSPMD falls back to all-gathering the
                          source slab: correct, collective-heavy),
    * FFT (A, A^-1)    -> all-gathers (XLA has no distributed FFT).
  ``halo_sl_step`` is the hand-optimized shard_map alternative for the
  semi-Lagrangian gather: exchange only the CFL halo with ring
  collective-permutes and interpolate locally — the §Perf iteration
  quantifies the collective-bytes delta vs the GSPMD fallback.

END-TO-END SLAB SOLVES — the first-class path. ``make_slab_step`` wraps the
  unmodified Gauss-Newton step body (``gauss_newton._build_step``) in
  ``shard_map`` with a ``halo.ShardInfo`` threaded through
  ``TransportConfig.shard``: FD8 and SL interpolation become explicit halo
  exchanges, spectral operators all-gathers, inner products psums.
  ``solve_slab`` / ``solve_ensemble_slab`` reuse the single-device outer
  drivers (``gauss_newton.solve`` / ``solve_batch``) with the sharded step
  injected; the user-facing entry is ``core.registration.register_sharded``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import gauss_newton as _gn
from repro.core import gradient as _grad
from repro.core import grid as _grid
from repro.core import interp as _interp
from repro.core import pcg as _pcg
from repro.core import transport as _tr
from repro.distributed import halo as _halo
from repro.launch.mesh import axis_size, dp_axis_names


# ---------------------------------------------------------------------------
# Ensemble (population study) parallelism
# ---------------------------------------------------------------------------


def ensemble_newton_step(cfg: _tr.TransportConfig, gn: _gn.GNConfig):
    """vmapped Gauss-Newton step over a batch of pairs: inputs
    m0, m1 (B, N1, N2, N3), v (B, 3, N1, N2, N3)."""
    step = _gn._make_step(cfg, gn)

    def batch_step(m0, m1, v, beta, gamma, eta):
        return jax.vmap(lambda a, b, c: step(a, b, c, beta, gamma, eta))(
            m0, m1, v)

    return batch_step


def ensemble_shardings(mesh: Mesh, batch: int):
    """Pairs are embarrassingly parallel — shard the pair axis over EVERY
    mesh axis that divides it (the paper's own observation: registration
    tasks need no cross-task communication, so the 'model' axis is free
    real estate here)."""
    axes = [a for a in ("pod", "data", "model") if a in mesh.axis_names]
    entry: tuple = ()
    size = 1
    for a in axes:
        if batch % (size * mesh.shape[a]) == 0:
            entry = entry + (a,)
            size *= mesh.shape[a]
    spec0 = entry if entry else None
    img = NamedSharding(mesh, P(spec0, None, None, None))
    vel = NamedSharding(mesh, P(spec0, None, None, None, None))
    return img, vel


def ensemble_input_specs(grid_shape, batch: int):
    sds = jax.ShapeDtypeStruct
    n1, n2, n3 = grid_shape
    return dict(
        m0=sds((batch, n1, n2, n3), jnp.float32),
        m1=sds((batch, n1, n2, n3), jnp.float32),
        v=sds((batch, 3, n1, n2, n3), jnp.float32),
    )


# ---------------------------------------------------------------------------
# Slab (grid) parallelism
# ---------------------------------------------------------------------------


def slab_shardings(mesh: Mesh, grid_shape):
    """x1-slab decomposition over the mesh model axis."""
    m = "model" if (grid_shape[0] % axis_size(mesh, "model") == 0) else None
    img = NamedSharding(mesh, P(m, None, None))
    vel = NamedSharding(mesh, P(None, m, None, None))
    return img, vel


def slab_input_specs(grid_shape):
    sds = jax.ShapeDtypeStruct
    n1, n2, n3 = grid_shape
    return dict(
        m0=sds((n1, n2, n3), jnp.float32),
        m1=sds((n1, n2, n3), jnp.float32),
        v=sds((3, n1, n2, n3), jnp.float32),
    )


def slab_newton_step(cfg: _tr.TransportConfig, gn: _gn.GNConfig):
    """Single-pair GN step; sharding comes from jit in_shardings (GSPMD
    propagates through rolls/gathers/FFTs)."""
    return _gn._make_step(cfg, gn)


# ---------------------------------------------------------------------------
# Hand-optimized halo-exchange semi-Lagrangian step (shard_map)
# ---------------------------------------------------------------------------


def halo_sl_step(mesh: Mesh, method: str = "cubic_bspline",
                 halo: int = 8, axis: str = "model"):
    """SL advection with explicit halo exchange on the x1 slab axis.

    f: (N1, N2, N3) sharded P(axis, None, None);
    foot: (3, N1, N2, N3) index-unit footpoints, sharded P(None, axis, ..).
    Per-step displacement must satisfy |foot - x| <= halo - stencil margin
    (same CFL contract as the Pallas interp kernel).

    Built on the ``distributed.halo`` primitives: the B-spline prefilter is
    *exact* (the exchange covers the prefilter radius on top of the interp
    halo), and the gather goes through the halo-frame
    :class:`~repro.core.interp.InterpPlan` — build once in the extended-slab
    frame, apply locally — exactly the path the end-to-end sharded solver
    amortizes across SL steps and Hessian matvecs.
    """
    shard = _halo.ShardInfo(axis=axis, nshards=axis_size(mesh, axis), halo=halo)

    def local(f_loc, foot_loc):
        plan = _halo.build_plan(foot_loc, method, None, shard)
        return _halo.apply_plan(plan, f_loc, method, shard)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None, None), P(None, axis, None, None)),
        out_specs=P(axis, None, None),
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# End-to-end slab-parallel Gauss-Newton-Krylov: the whole Newton step body
# (gradient -> PCG -> line search) under one shard_map on an
# (ensemble, slab) mesh.
# ---------------------------------------------------------------------------


def slab_axis_name(mesh: Mesh) -> str:
    """The mesh axis carrying the x1 slab decomposition: ``slab`` if present,
    else ``model`` (the transformer meshes), else the last axis."""
    for name in ("slab", "model"):
        if name in mesh.axis_names:
            return name
    return mesh.axis_names[-1]


def ensemble_axis_name(mesh: Mesh) -> Optional[str]:
    """The mesh axis sharding independent registrations: ``ensemble`` if
    present, else ``data``, else None (pure slab mesh)."""
    for name in ("ensemble", "data"):
        if name in mesh.axis_names:
            return name
    return None


def slab_solve_shardings(mesh: Mesh, slab_axis: str,
                         ens_axis: Optional[str] = None):
    """(image, velocity) NamedShardings for the end-to-end slab solve."""
    if ens_axis is None:
        return (NamedSharding(mesh, P(slab_axis, None, None)),
                NamedSharding(mesh, P(None, slab_axis, None, None)))
    return (NamedSharding(mesh, P(ens_axis, slab_axis, None, None)),
            NamedSharding(mesh, P(ens_axis, None, slab_axis, None, None)))


def _check_slab_cfg(cfg: _tr.TransportConfig):
    if cfg.backend not in ("jnp", "pallas"):
        raise NotImplementedError(
            f"slab-distributed solves support backend 'jnp' (XLA reference) "
            f"or 'pallas' (halo-tile kernels inside shard_map), got "
            f"{cfg.backend!r}")


def make_slab_step(mesh: Mesh, cfg: _tr.TransportConfig, gn: _gn.GNConfig,
                   slab_axis: Optional[str] = None, halo: int = 6,
                   ens_axis: Optional[str] = None, compress: str = "none"):
    """Jitted Newton step running entirely under ``shard_map``.

    The step *body* is the unmodified ``gauss_newton._build_step`` — the
    slab semantics enter exclusively through ``TransportConfig.shard``
    (halo-exchange FD8 and SL interpolation, all-gather spectral operators,
    psum inner products), so single-device and sharded solves share every
    line of solver logic. With ``ens_axis`` the body is additionally vmapped
    over the local pair batch: a 2D (ensemble, slab) mesh where the ensemble
    axis needs zero collectives and the slab axis only halo exchanges.

    Signature matches ``gauss_newton._make_step`` (and ``_make_batch_step``
    when ``ens_axis`` is given), so it can be injected into
    ``gauss_newton.solve(..., step_fn=)`` / ``solve_batch(..., step_fn=)``.
    """
    _check_slab_cfg(cfg)
    slab_axis = slab_axis or slab_axis_name(mesh)
    shard = _halo.ShardInfo(axis=slab_axis,
                            nshards=axis_size(mesh, slab_axis), halo=halo,
                            backend=cfg.backend, compress=compress)
    body = _gn._build_step(cfg._replace(shard=shard), gn)

    if ens_axis is None:
        img = P(slab_axis, None, None)
        vel = P(None, slab_axis, None, None)
        stat = P()     # psum/all-gather-reduced scalars: replicated
        eta_spec = P()
    else:
        body = jax.vmap(body, in_axes=(0, 0, 0, None, None, 0))
        img = P(ens_axis, slab_axis, None, None)
        vel = P(ens_axis, None, slab_axis, None, None)
        stat = P(ens_axis)   # per-pair scalars, replicated over slab only
        eta_spec = P(ens_axis)

    out_specs = _gn.NewtonStepStats(
        v_new=vel, gnorm=stat, j_total=stat, j_mismatch=stat, j_reg=stat,
        pcg_iters=stat, pcg_residual=stat, alpha=stat, ls_evals=stat)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(img, img, vel, P(), P(), eta_spec),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def _validate_slab(shape, mesh: Mesh, slab_axis: str, halo: int):
    n = axis_size(mesh, slab_axis)
    if shape[0] % n != 0:
        raise ValueError(
            f"grid x1 extent {shape[0]} not divisible by slab axis "
            f"{slab_axis!r} of size {n}")
    if halo < 1:
        raise ValueError(f"halo must be >= 1, got {halo}")


def solve_slab(
    m0: jnp.ndarray,
    m1: jnp.ndarray,
    cfg: _tr.TransportConfig,
    gn: _gn.GNConfig = _gn.GNConfig(),
    *,
    mesh: Mesh,
    slab_axis: Optional[str] = None,
    halo: int = 6,
    compress: str = "none",
    v0: jnp.ndarray | None = None,
    gnorm_ref: float | None = None,
    eta0: float | None = None,
    verbose: bool = False,
) -> _gn.GNResult:
    """Full Gauss-Newton-Krylov solve of one pair, x1-sharded over the mesh.

    Matches ``gauss_newton.solve`` on a single device to floating-point
    reduction noise (the only arithmetic difference is psum summation
    order). The velocity iterate stays slab-sharded across Newton steps.
    """
    _check_slab_cfg(cfg)
    slab_axis = slab_axis or slab_axis_name(mesh)
    _validate_slab(m0.shape, mesh, slab_axis, halo)
    step = make_slab_step(mesh, cfg, gn, slab_axis, halo, compress=compress)
    img_sh, vel_sh = slab_solve_shardings(mesh, slab_axis)
    m0 = jax.device_put(jnp.asarray(m0), img_sh)
    m1 = jax.device_put(jnp.asarray(m1), img_sh)
    if v0 is None:
        v0 = jnp.zeros((3,) + m0.shape, dtype=m0.dtype)
    v0 = jax.device_put(jnp.asarray(v0), vel_sh)
    return _gn.solve(m0, m1, cfg, gn, v0=v0, gnorm_ref=gnorm_ref, eta0=eta0,
                     verbose=verbose, step_fn=step)


def solve_ensemble_slab(
    m0: jnp.ndarray,
    m1: jnp.ndarray,
    cfg: _tr.TransportConfig,
    gn: _gn.GNConfig = _gn.GNConfig(),
    *,
    mesh: Mesh,
    ens_axis: Optional[str] = None,
    slab_axis: Optional[str] = None,
    halo: int = 6,
    compress: str = "none",
    v0: jnp.ndarray | None = None,
    gnorm_ref=None,
    verbose: bool = False,
    step_fn=None,
) -> _gn.BatchGNResult:
    """Batch of registrations on a 2D (ensemble, slab) mesh: pairs sharded
    over the ensemble axis (zero collectives), each pair's grid x1-sharded
    over the slab axis. Outer driver: ``gauss_newton.solve_batch``.

    ``step_fn`` injects a pre-built sharded Newton step (from
    :func:`make_slab_step` with the same mesh/axes/halo) so long-lived
    callers — the registration server solving many waves of the same shape —
    compile once instead of re-wrapping ``shard_map`` per call.
    """
    _check_slab_cfg(cfg)
    slab_axis = slab_axis or slab_axis_name(mesh)
    ens_axis = ens_axis or ensemble_axis_name(mesh)
    if ens_axis is None:
        raise ValueError(f"mesh {mesh.axis_names} has no ensemble axis")
    if m0.ndim != 4:
        raise ValueError(f"expected batched images (B, N1, N2, N3), got {m0.shape}")
    _validate_slab(m0.shape[1:], mesh, slab_axis, halo)
    ne = axis_size(mesh, ens_axis)
    if m0.shape[0] % ne != 0:
        raise ValueError(
            f"batch {m0.shape[0]} not divisible by ensemble axis "
            f"{ens_axis!r} of size {ne}")
    step = step_fn if step_fn is not None else make_slab_step(
        mesh, cfg, gn, slab_axis, halo, ens_axis=ens_axis, compress=compress)
    img_sh, vel_sh = slab_solve_shardings(mesh, slab_axis, ens_axis)
    m0 = jax.device_put(jnp.asarray(m0), img_sh)
    m1 = jax.device_put(jnp.asarray(m1), img_sh)
    if v0 is None:
        v0 = jnp.zeros((m0.shape[0], 3) + m0.shape[1:], dtype=m0.dtype)
    v0 = jax.device_put(jnp.asarray(v0), vel_sh)
    return _gn.solve_batch(m0, m1, cfg, gn, v0=v0, gnorm_ref=gnorm_ref,
                           verbose=verbose, step_fn=step)
