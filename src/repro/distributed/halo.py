"""Slab-halo primitives for grid-distributed registration.

One registration is spread over a mesh axis by decomposing the x1 axis into
slabs (the Brunn et al. 2020 multi-node CLAIRE layout). Every operator of the
optimality system then falls into one of three communication classes:

  * FD8 stencils          -> fixed-width (4) halo exchange,
  * SL interpolation      -> CFL-bounded halo exchange (displacement + taps,
                             plus the 7-point B-spline prefilter radius),
  * spectral operators    -> all-gather + local FFT + slice (XLA has no
                             distributed FFT; an open ROADMAP item),
  * inner products        -> local partial sums + one scalar psum.

Everything here runs *inside* ``shard_map``: fields are local slabs
``(..., N1/n, N2, N3)`` and the slab position comes from
``lax.axis_index``. The :class:`ShardInfo` record is carried by
``TransportConfig.shard`` so the unmodified solver stack (transport solves,
gradient, Hessian matvec, PCG, Newton step) assembles the sharded solve from
these primitives — see ``repro.distributed.claire_dist``.

CFL contract: per-step footpoint displacement along x1 must satisfy
``|foot_1 - x_1| <= halo - 2`` (cubic stencil reaches floor(q)-1..floor(q)+2).
This is the same contract as the Pallas halo-tile interpolation kernel
(``semilag.PALLAS_DISPLACEMENT_BOUND``); the solver's velocity regime keeps
SL displacements at a few voxels.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.core import grid as _grid
from repro.core import interp as _interp
from repro.core.derivatives import FD8_COEFFS

from . import compression as _comp


class ShardInfo(NamedTuple):
    """Static description of the slab decomposition (hashable; lives in
    ``TransportConfig.shard`` and is baked into the trace).

    axis    : mesh axis name the x1 grid axis is sharded over
    nshards : number of slabs (mesh axis size)
    halo    : interpolation halo width in voxels (CFL bound + stencil margin);
              the FD8 halo (4) and the prefilter radius (7) are derived
              internally and do not need to be included.
    backend : "jnp" (XLA reference) or "pallas" — routes the slab-local
              compute (prefilter, plan gather, FD8 stencils) through the
              Pallas kernels operating on the halo-extended tiles; the
              collectives are identical either way.
    compress: "none" or "int8" — quantize halo-exchange payloads on the wire
              (distributed.compression absmax int8). Remote halo rows become
              lossy; the owned slab interior stays exact.
    """

    axis: str
    nshards: int
    halo: int = 6
    backend: str = "jnp"
    compress: str = "none"

    def global_shape(self, local_shape) -> Tuple[int, int, int]:
        n1, n2, n3 = (int(n) for n in local_shape[-3:])
        return (n1 * self.nshards, n2, n3)


def _x1(f, start, stop):
    """Slice [start:stop) of the x1 axis (axis -3) of ``f``."""
    return f[..., start:stop, :, :]


def exchange(f: jnp.ndarray, halo: int, shard: ShardInfo) -> jnp.ndarray:
    """Extend the local slab by ``halo`` rows of the periodic global field on
    each side of the x1 axis: output x1 length = local + 2*halo.

    Nearby halos travel over a multi-hop ring of ``collective-permute``s
    (ceil(halo / n_local) hops); when the ring would reach most of the mesh
    anyway the exchange degenerates to one all-gather + local periodic
    window, which is also what makes small grids (n_local < halo) and
    1-shard meshes work unchanged.
    """
    if halo <= 0:
        return f
    n_loc = f.shape[-3]
    n = shard.nshards
    compress = shard.compress == "int8"

    def _perm(x, perm):
        """ppermute, int8 on the wire when halo compression is on (payload
        quantized per hop with an absmax scale that travels alongside)."""
        if not compress:
            return lax.ppermute(x, shard.axis, perm=perm)
        q, s = _comp.quantize_int8(x)
        q = lax.ppermute(q, shard.axis, perm=perm)
        s = lax.ppermute(s, shard.axis, perm=perm)
        return _comp.dequantize_int8(q, s).astype(x.dtype)

    hops = -(-halo // n_loc)  # ceil
    if 2 * hops + 1 >= n:
        n_glob = n_loc * n
        start = lax.axis_index(shard.axis) * n_loc
        idx = jnp.mod(start + jnp.arange(-halo, n_loc + halo), n_glob)
        if compress:
            # int8 all-gather; the own (interior) rows are re-spliced exactly
            # below, so quantization only touches the remote halo rows.
            q, s = _comp.quantize_int8(f)
            full_q = lax.all_gather(q, shard.axis, axis=f.ndim - 3,
                                    tiled=False)
            scales = lax.all_gather(s, shard.axis)
            full = (full_q.astype(f.dtype)
                    * scales.reshape((n, 1, 1, 1)).astype(f.dtype))
            full = full.reshape(f.shape[:-3] + (n_glob,) + f.shape[-2:])
            ext = jnp.take(full, idx, axis=f.ndim - 3)
            return jnp.concatenate(
                [_x1(ext, 0, halo), f,
                 _x1(ext, halo + n_loc, n_loc + 2 * halo)],
                axis=f.ndim - 3)
        full = lax.all_gather(f, shard.axis, axis=f.ndim - 3, tiled=True)
        return jnp.take(full, idx, axis=f.ndim - 3)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    # Intermediate hops must forward whole slabs to keep the chain intact,
    # but the final hop's source slab only contributes its ``rem`` rows
    # nearest the boundary — slicing before the permute keeps the moved
    # bytes at exactly 2*halo rows per direction (one hop, the common
    # n_local >= halo case, sends only the halo itself).
    rem = halo - (hops - 1) * n_loc
    top_parts, bot_parts = [], []
    cur_t, cur_b = f, f
    for h in range(hops):
        send_t, send_b = cur_t, cur_b
        if h == hops - 1:
            send_t = _x1(cur_t, n_loc - rem, n_loc)
            send_b = _x1(cur_b, 0, rem)
        cur_t = _perm(send_t, fwd)  # from left neighbor
        cur_b = _perm(send_b, bwd)  # from right neighbor
        top_parts.insert(0, cur_t)
        bot_parts.append(cur_b)
    top = jnp.concatenate(top_parts, axis=f.ndim - 3) if len(top_parts) > 1 \
        else top_parts[0]
    bot = jnp.concatenate(bot_parts, axis=f.ndim - 3) if len(bot_parts) > 1 \
        else bot_parts[0]
    return jnp.concatenate([top, f, bot], axis=f.ndim - 3)


def gather_full(f: jnp.ndarray, shard: ShardInfo) -> jnp.ndarray:
    """All-gather the x1 axis: the full global field, replicated per shard."""
    return lax.all_gather(f, shard.axis, axis=f.ndim - 3, tiled=True)


def slice_local(full: jnp.ndarray, n_loc: int, shard: ShardInfo) -> jnp.ndarray:
    """This shard's slab of a gathered global field."""
    start = lax.axis_index(shard.axis) * n_loc
    return lax.dynamic_slice_in_dim(full, start, n_loc, axis=full.ndim - 3)


def origin(f_or_shape, shard: ShardInfo):
    """Global x1 index of the first local row (traced int32)."""
    n_loc = f_or_shape if isinstance(f_or_shape, int) else f_or_shape.shape[-3]
    return lax.axis_index(shard.axis) * n_loc


# ---------------------------------------------------------------------------
# FD8 with halo exchange (supports arbitrary leading batch axes, so stored
# trajectories are differentiated in one stacked pass instead of a vmap).
# ---------------------------------------------------------------------------

FD8_HALO = len(FD8_COEFFS)  # stencil radius 4


def _vmap_leading(fn, ndim: int):
    """Vectorize a 3D-field kernel over ``ndim - 3`` leading axes."""
    for _ in range(ndim - 3):
        fn = jax.vmap(fn)
    return fn


def _fd8_x1_valid_pallas(f_ext: jnp.ndarray, h: float) -> jnp.ndarray:
    """Pallas valid-mode x1 derivative of a halo-extended slab."""
    from repro.kernels import pencil as _pencil

    fn = _vmap_leading(
        lambda g: _pencil.stencil_pencil_valid(g, 0, FD8_COEFFS,
                                               scale=1.0 / h),
        f_ext.ndim)
    return fn(f_ext)


def _fd8_axis_pallas(f: jnp.ndarray, axis3: int, h: float) -> jnp.ndarray:
    """Pallas periodic FD8 derivative along local spatial axis ``axis3``."""
    from repro.kernels import pencil as _pencil

    fn = _vmap_leading(
        lambda g: _pencil.stencil_pencil(g, axis3, FD8_COEFFS,
                                         symmetric=False, scale=1.0 / h),
        f.ndim)
    return fn(f)


def _fd8_x1_valid(f_ext: jnp.ndarray, n_loc: int, h: float) -> jnp.ndarray:
    """d/dx1 on the interior rows of a halo-extended slab (no wrap)."""
    r = FD8_HALO
    out = jnp.zeros_like(_x1(f_ext, r, r + n_loc))
    for k, c in enumerate(FD8_COEFFS, start=1):
        out = out + c * (_x1(f_ext, r + k, r + k + n_loc)
                         - _x1(f_ext, r - k, r - k + n_loc))
    return out / h


def _fd8_axis_periodic(f: jnp.ndarray, axis: int, h: float) -> jnp.ndarray:
    out = jnp.zeros_like(f)
    for k, c in enumerate(FD8_COEFFS, start=1):
        out = out + c * (jnp.roll(f, -k, axis=axis) - jnp.roll(f, k, axis=axis))
    return out / h


def fd8_grad(f: jnp.ndarray, shard: ShardInfo) -> jnp.ndarray:
    """FD8 gradient of scalar field(s) ``(..., N1/n, N2, N3)``; the component
    axis is inserted before the three spatial axes: ``(..., 3, N1/n, N2, N3)``."""
    h = _grid.spacing(shard.global_shape(f.shape))
    n_loc = f.shape[-3]
    f_ext = exchange(f, FD8_HALO, shard)
    if shard.backend == "pallas":
        d0 = _fd8_x1_valid_pallas(f_ext, h[0])
        d1 = _fd8_axis_pallas(f, 1, h[1])
        d2 = _fd8_axis_pallas(f, 2, h[2])
    else:
        d0 = _fd8_x1_valid(f_ext, n_loc, h[0])
        d1 = _fd8_axis_periodic(f, f.ndim - 2, h[1])
        d2 = _fd8_axis_periodic(f, f.ndim - 1, h[2])
    return jnp.stack([d0, d1, d2], axis=f.ndim - 3)


def fd8_div(w: jnp.ndarray, shard: ShardInfo) -> jnp.ndarray:
    """FD8 divergence of a vector field (3, N1/n, N2, N3) -> (N1/n, N2, N3)."""
    h = _grid.spacing(shard.global_shape(w.shape))
    n_loc = w.shape[-3]
    if shard.backend == "pallas":
        d0 = _fd8_x1_valid_pallas(exchange(w[0], FD8_HALO, shard), h[0])
        d1 = _fd8_axis_pallas(w[1], 1, h[1])
        d2 = _fd8_axis_pallas(w[2], 2, h[2])
    else:
        d0 = _fd8_x1_valid(exchange(w[0], FD8_HALO, shard), n_loc, h[0])
        d1 = _fd8_axis_periodic(w[1], w.ndim - 3, h[1])
        d2 = _fd8_axis_periodic(w[2], w.ndim - 2, h[2])
    return d0 + d1 + d2


def spectral_grad(f: jnp.ndarray, shard: ShardInfo) -> jnp.ndarray:
    """FFT gradient via all-gather + local FFT (no distributed FFT in XLA)."""
    from repro.core import derivatives as _deriv

    return slice_local(_deriv.spectral_grad(gather_full(f, shard)),
                       f.shape[-3], shard)


def spectral_div(w: jnp.ndarray, shard: ShardInfo) -> jnp.ndarray:
    from repro.core import derivatives as _deriv

    return slice_local(_deriv.spectral_div(gather_full(w, shard)),
                       w.shape[-3], shard)


# ---------------------------------------------------------------------------
# Halo-local semi-Lagrangian interpolation: CFL-bounded halo gather + the
# build-once/apply-many InterpPlan machinery of ``repro.core.interp``, built
# in the *extended-slab frame* (x1 clipped, x2/x3 periodic).
# ---------------------------------------------------------------------------


def _prefilter_pad(method: str) -> int:
    return _interp.PREFILTER_RADIUS if method == "cubic_bspline" else 0


def _prefilter_local(f: jnp.ndarray, method: str, shard: ShardInfo) -> jnp.ndarray:
    """Slab-local prefilter; Pallas pencil kernel on ``backend="pallas"``.

    The Pallas prefilter wraps periodically on every axis, but the wrap
    contamination along the non-periodic extended x1 axis only reaches the
    prefilter radius — exactly the pad rows :func:`sl_coefficients` trims.
    """
    if shard.backend == "pallas" and method == "cubic_bspline":
        from repro.kernels.prefilter.prefilter import prefilter3d_pallas

        return _vmap_leading(prefilter3d_pallas, f.ndim)(f)
    return _interp.prefilter_for(f, method)


@obs.scoped(obs.INTERP_APPLY)
def _apply_plan_local(plan: _interp.InterpPlan, coef: jnp.ndarray,
                      shard: ShardInfo) -> jnp.ndarray:
    """Plan gather on the halo-extended coefficient slab (Pallas or XLA)."""
    if shard.backend == "pallas":
        from repro.kernels.interp3d.interp3d import apply_plan_pallas

        return apply_plan_pallas(coef, plan)
    return _interp.apply_plan(plan, coef)


@obs.scoped(obs.INTERP_PLAN)
def build_plan(foot: jnp.ndarray, method: str, weight_dtype, shard: ShardInfo
               ) -> _interp.InterpPlan:
    """Interpolation plan for *global-coordinate* footpoints of a local slab.

    ``foot`` is (3, N1/n, N2, N3) in global index units. The x1 coordinate is
    rebased to the halo-extended local frame, so applying the plan needs only
    the extended coefficient slab from :func:`sl_coefficients` — no further
    communication per application (the sharded analogue of the paper's
    build-once/apply-many amortization).
    """
    n_loc = foot.shape[-3]
    x0 = (origin(n_loc, shard) - shard.halo).astype(foot.dtype)
    q1 = foot[0] - x0
    q = jnp.stack([q1, foot[1], foot[2]], axis=0)
    ext_shape = (n_loc + 2 * shard.halo,) + tuple(foot.shape[-2:])
    return _interp.build_plan(q, method=method, weight_dtype=weight_dtype,
                              shape=ext_shape, wrap=(False, True, True))


@obs.scoped(obs.INTERP_PREFILTER)
def sl_coefficients(f: jnp.ndarray, method: str, shard: ShardInfo) -> jnp.ndarray:
    """Halo-extended interpolation coefficients for local field(s) ``f``.

    One exchange of width ``halo + prefilter_radius`` followed by the local
    FIR prefilter; the returned slab covers exactly the plan's extended frame
    ``N1/n + 2*halo`` and its coefficients are *exact* (every kept row is at
    least the prefilter radius away from the exchanged edges, so the FIR's
    local wrap never contaminates them).
    """
    pad = _prefilter_pad(method)
    f_ext = exchange(f, shard.halo + pad, shard)
    coef = _prefilter_local(f_ext, method, shard)
    if pad:
        coef = _x1(coef, pad, coef.shape[-3] - pad)
    return coef


def apply_plan(plan: _interp.InterpPlan, f: jnp.ndarray, method: str,
               shard: ShardInfo) -> jnp.ndarray:
    """One sharded SL step through a prebuilt halo plan (exchange + gather)."""
    return _apply_plan_local(plan, sl_coefficients(f, method, shard), shard)


def interp(f: jnp.ndarray, foot: jnp.ndarray, method: str, weight_dtype,
           shard: ShardInfo) -> jnp.ndarray:
    """Plan-free sharded interpolation (builds a throwaway halo plan)."""
    plan = build_plan(foot, method, weight_dtype, shard)
    return apply_plan(plan, f, method, shard)


def index_coords_local(shape_loc, shard: ShardInfo, dtype=jnp.float32):
    """Global index-unit coordinates of the local slab, (3, N1/n, N2, N3)."""
    x = _grid.index_coords(shape_loc, dtype=dtype)
    x0 = origin(int(shape_loc[0]), shard).astype(dtype)
    return jnp.concatenate([x[0:1] + x0, x[1:]], axis=0)


def trace_characteristic(v: jnp.ndarray, dt: float, method: str, sign: float,
                         weight_dtype, shard: ShardInfo) -> jnp.ndarray:
    """RK2 backward characteristic trace on a slab (cf. ``semilag``): the
    midpoint velocity is a halo-local interpolation, and the returned
    footpoints are *global* index coordinates of local grid points."""
    lshape = v.shape[-3:]
    gshape = shard.global_shape(lshape)
    h = jnp.asarray(_grid.spacing(gshape), dtype=v.dtype).reshape(3, 1, 1, 1)
    x = index_coords_local(lshape, shard, dtype=v.dtype)
    q_mid = x - sign * (0.5 * dt) * v / h
    coef = sl_coefficients(v, method, shard)
    plan = build_plan(q_mid, method, weight_dtype, shard)
    v_mid = _apply_plan_local(plan, coef, shard)
    return x - sign * dt * v_mid / h


# ---------------------------------------------------------------------------
# Spectral operators (regularizer / preconditioner): all-gather fallback.
# ---------------------------------------------------------------------------


def spectral_op(op, v: jnp.ndarray, shard: ShardInfo) -> jnp.ndarray:
    """Apply a global spectral field->field operator: gather, apply, slice."""
    full = gather_full(v, shard)
    return slice_local(op(full), v.shape[-3], shard)
