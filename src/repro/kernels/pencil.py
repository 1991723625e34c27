"""Shared Pallas pencil-stencil machinery.

Both of the paper's axis-aligned stencil kernels (FD8 first derivatives and
the 15-point B-spline prefilter) follow the same TPU-native pattern:

  * the stencil axis is kept WHOLE inside the kernel block (a "pencil"),
    so periodic wrap is a static in-VMEM roll — no halo exchange, no
    out-of-bounds reads (the CUDA version's main headache);
  * the other two axes are tiled so the block fits VMEM and the (8, 128)
    sublane/lane layout is fully occupied;
  * grid iteration streams pencils HBM -> VMEM -> HBM exactly once, which is
    the memory-bound optimum the paper's roofline analysis targets.

On the CPU kernels run with ``interpret=True``, which executes the same
block program in Python for correctness validation; on the TPU they compile
with Mosaic. No other platform is supported.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def interpret_default() -> bool:
    """Pallas interpret mode on the CPU, compiled kernels on the TPU."""
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise NotImplementedError(
            f"Pallas kernels here run on 'tpu' (compiled) or 'cpu' "
            f"(interpret mode), not on {platform!r}")
    return platform == "cpu"


def largest_divisor(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is <= ``target`` (>=1)."""
    for d in range(min(target, n), 0, -1):
        if n % d == 0:
            return d
    return 1


def pencil_blocks(shape: Sequence[int], axis: int,
                  targets: Tuple[int, int] = (8, 128)):
    """Block shape + grid for a pencil kernel along ``axis``.

    The stencil axis is whole; the remaining two axes are tiled with target
    tile sizes ``targets`` (assigned in axis order). Returns
    (block_shape, grid, index_map).
    """
    tiled = [a for a in range(3) if a != axis]
    tiles = {}
    for t_axis, target in zip(tiled, targets):
        tiles[t_axis] = largest_divisor(shape[t_axis], target)
    block = tuple(shape[a] if a == axis else tiles[a] for a in range(3))
    grid = tuple(shape[a] // tiles[a] for a in tiled)

    def index_map(i, j):
        out = [0, 0, 0]
        out[tiled[0]] = i
        out[tiled[1]] = j
        return tuple(out)

    return block, grid, index_map


def _stencil_valid_body(f_ref, o_ref, *, taps, axis, n_out, scale):
    f = f_ref[...]
    r = len(taps)

    def window(start):
        idx = [slice(None)] * f.ndim
        idx[axis] = slice(start, start + n_out)
        return f[tuple(idx)]

    acc = None
    for k, c in enumerate(taps, start=1):
        term = c * (window(r + k) - window(r - k))
        acc = term if acc is None else acc + term
    o_ref[...] = acc * scale


def stencil_pencil_valid(
    f: jnp.ndarray,
    axis: int,
    taps: Tuple[float, ...],
    scale: float = 1.0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Valid-mode antisymmetric stencil along ``axis`` of a halo-extended
    field: input length ``n + 2*len(taps)`` along ``axis``, output length
    ``n``. This is the sharded-slab x1 derivative, where the boundary rows
    come from a collective halo exchange instead of periodic wrap — the
    kernel reads static shifted windows of the pencil, no rolls.
    """
    if f.ndim != 3:
        raise ValueError(f"expected 3D field, got shape {f.shape}")
    r = len(taps)
    n_out = f.shape[axis] - 2 * r
    if n_out <= 0:
        raise ValueError(
            f"axis {axis} length {f.shape[axis]} too short for radius {r}")
    if interpret is None:
        interpret = interpret_default()
    in_block, grid, index_map = pencil_blocks(f.shape, axis)
    out_block = tuple(n_out if a == axis else in_block[a] for a in range(3))
    out_shape = tuple(n_out if a == axis else f.shape[a] for a in range(3))
    body = functools.partial(
        _stencil_valid_body, taps=tuple(float(t) for t in taps), axis=axis,
        n_out=n_out, scale=float(scale),
    )
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[pl.BlockSpec(in_block, index_map)],
        out_specs=pl.BlockSpec(out_block, index_map),
        out_shape=jax.ShapeDtypeStruct(out_shape, f.dtype),
        interpret=interpret,
    )(f)


def _stencil_body(f_ref, o_ref, *, taps, axis, symmetric, scale):
    f = f_ref[...]
    if symmetric:
        # out = c0 f + sum_k c_k (f_{+k} + f_{-k})
        acc = taps[0] * f
        for k, c in enumerate(taps[1:], start=1):
            acc = acc + c * (jnp.roll(f, -k, axis=axis) + jnp.roll(f, k, axis=axis))
    else:
        # out = sum_k c_k (f_{+k} - f_{-k})
        acc = jnp.zeros_like(f)
        for k, c in enumerate(taps, start=1):
            acc = acc + c * (jnp.roll(f, -k, axis=axis) - jnp.roll(f, k, axis=axis))
    o_ref[...] = acc * scale


def stencil_pencil(
    f: jnp.ndarray,
    axis: int,
    taps: Tuple[float, ...],
    symmetric: bool,
    scale: float = 1.0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Apply a 1D symmetric/antisymmetric stencil along ``axis`` (periodic).

    ``taps``: for ``symmetric`` the tuple is (c0, c1, ..., cR); otherwise
    (c1, ..., cR) with the antisymmetric combination c_k (f_{+k} - f_{-k}).
    """
    if f.ndim != 3:
        raise ValueError(f"expected 3D field, got shape {f.shape}")
    if interpret is None:
        interpret = interpret_default()
    block, grid, index_map = pencil_blocks(f.shape, axis)
    body = functools.partial(
        _stencil_body, taps=tuple(float(t) for t in taps), axis=axis,
        symmetric=symmetric, scale=float(scale),
    )
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[pl.BlockSpec(block, index_map)],
        out_specs=pl.BlockSpec(block, index_map),
        out_shape=jax.ShapeDtypeStruct(f.shape, f.dtype),
        interpret=interpret,
    )(f)
