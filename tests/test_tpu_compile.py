"""Pallas kernels compiled for a described TPU v5e chip (no chip attached).

``interpret=False`` sends each kernel through Mosaic, the TPU compiler,
which refuses what interpret mode accepts: unaligned blocks, gathers it
cannot lower, more VMEM than a kernel may use. The stencil kernels of the
main path compile at 256^3 and must lower to a ``tpu_custom_call``. The
three interpolation kernels are refused today; their compiles are strict
expected failures, so they turn into errors once the kernels compile.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. The whole Newton step is not compiled here (minutes).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import interp
from repro.kernels import pencil
from repro.kernels.fd8.fd8 import FD8_COEFFS, fd8_partial_pallas
from repro.kernels.interp3d.interp3d import (
    apply_plan_fused, apply_plan_pallas, interp3d_pallas)
from repro.kernels.prefilter.prefilter import prefilter3d_pallas

N = 256
GATHER_REFUSED = "Mosaic refuses the flat jnp.take: 'Only 2D gather is supported'"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests.
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("axis", [0, 1, 2])
def test_fd8_pencil_compiles(one_chip, axis):
    hlo = _compile(lambda f: fd8_partial_pallas(f, axis, interpret=False),
                   one_chip, (N, N, N))
    assert "tpu_custom_call" in hlo


def test_prefilter_compiles(one_chip):
    hlo = _compile(lambda f: prefilter3d_pallas(f, interpret=False),
                   one_chip, (N, N, N))
    assert "tpu_custom_call" in hlo


def test_valid_stencil_halo_tile_compiles(one_chip):
    """The x1 FD8 of a slab solve: 64 owned planes (256^3 over 4 slabs) plus
    the 4-plane halo on each side."""
    hlo = _compile(
        lambda f: pencil.stencil_pencil_valid(f, 0, FD8_COEFFS, interpret=False),
        one_chip, (64 + 8, N, N))
    assert "tpu_custom_call" in hlo


@pytest.mark.xfail(strict=True, raises=ValueError,
                   reason="Mosaic refuses the (b+2h) halo block: its last two "
                   "dims are not multiples of (8, 128)")
def test_interp3d_pallas_compiles(one_chip):
    _compile(lambda f, q: interp3d_pallas(f, q, interpret=False),
             one_chip, (32, 32, 32), (3, 32, 32, 32))


@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=GATHER_REFUSED)
def test_apply_plan_pallas_compiles(one_chip):
    def fn(coef, q):
        return apply_plan_pallas(coef, interp.build_plan(q), interpret=False)

    _compile(fn, one_chip, (32, 32, 32), (3, 32, 32, 32))


@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=GATHER_REFUSED)
def test_apply_plan_fused_compiles(one_chip):
    def fn(coefs, q, extra):
        return apply_plan_fused(coefs, interp.build_plan(q), [extra],
                                lambda accs, ex: accs[0] + accs[1] * ex[0],
                                interpret=False)

    _compile(fn, one_chip, (2, 32, 32, 32), (3, 32, 32, 32), (32, 32, 32))
