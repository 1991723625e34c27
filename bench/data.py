"""Registration pairs for the benchmark, made on the device from a seed.

A traffic mix names a pool of base pairs: for each deformation amplitude,
``deformations`` pairs, each a brain-like phantom ``m0`` and ``m1 = m0 o
y^-1``, the phantom transported by its own smooth random stationary
velocity of that amplitude with the reference's semi-Lagrangian solve
(``bench.reference``). The pool comes from the mix's fixed ``pool_seed``.

``--seed`` draws how the pool is presented. The pairs come in passes; each
pass holds every base pair once. Within a pass the amplitudes cycle (so a
wave of as many pairs as there are amplitudes holds each amplitude once),
and each amplitude's deformations come in an order drawn from the seed.
Pair k is also put under a grid symmetry drawn from the stream ``(seed,
k)``: an axis permutation, axis reflections and a periodic shift. The
solver's discretization is equivariant under these maps (periodic grid,
equal spacings, symmetric stencils and B-spline), so a whole pass does the
same work on every seed while its images and velocities differ.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref


#: Stream tags of the passes' orders and of the warm-up pairs, apart from
#: the pairs' own streams.
_ORDER = 2**40
_WARM = 2**41


def _coords(n: int):
    x = jnp.arange(n, dtype=jnp.float32) * ref.spacing(n)
    return jnp.meshgrid(x, x, x, indexing="ij")


def _blobs(key, n, count, sigma_lo, sigma_hi):
    x = _coords(n)
    kc, ks, kw = jax.random.split(key, 3)
    centers = jax.random.uniform(kc, (count, 3), minval=1.5, maxval=2 * math.pi - 1.5)
    sigmas = jax.random.uniform(ks, (count,), minval=sigma_lo, maxval=sigma_hi)
    weights = jax.random.uniform(kw, (count,), minval=0.4, maxval=1.0)
    out = jnp.zeros((n, n, n), jnp.float32)
    for b in range(count):
        d2 = sum((x[d] - centers[b, d]) ** 2 for d in range(3))
        out = out + weights[b] * jnp.exp(-d2 / (2.0 * sigmas[b] ** 2))
    return out


def phantom(key, n: int):
    """Brain-like image in [0, 1]: ellipsoidal envelope times tissue blobs
    and finer 'fold' blobs (NIREP-like; no clinical data is used)."""
    k1, k2, _ = jax.random.split(key, 3)
    x = _coords(n)
    c = math.pi
    r2 = ((x[0] - c) / 2.2) ** 2 + ((x[1] - c) / 1.9) ** 2 + ((x[2] - c) / 2.2) ** 2
    envelope = jax.nn.sigmoid((1.0 - r2) * 8.0)
    img = envelope * (0.55 * _blobs(k1, n, 12, 0.35, 0.9)
                      + 0.45 * _blobs(k2, n, 24, 0.15, 0.35))
    return img / jnp.maximum(jnp.max(img), 1e-6)


def velocity(key, n: int, amplitude: float, sigma_vox: float):
    """Smooth random stationary velocity with max |v| = amplitude."""
    noise = jax.random.normal(key, (3, n, n, n), jnp.float32)
    sigma = sigma_vox * n / 64.0 if n >= 64 else sigma_vox
    v = jnp.stack([ref.gauss_smooth(noise[d], sigma) for d in range(3)], axis=0)
    vmax = jnp.max(jnp.sqrt(jnp.sum(v * v, axis=0)))
    return (amplitude / jnp.maximum(vmax, 1e-6)) * v


def pool_size(traffic: Dict) -> int:
    return len(traffic["amplitudes"]) * int(traffic["deformations"])


def make_pool(traffic: Dict, n: int, nt: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Base pairs of a mix: ``(m0, m1)``, each ``(P, n, n, n)``, one jitted
    call on the device. Base pair ``a * deformations + d`` is deformation d
    of amplitude a."""
    amps = tuple(float(a) for a in traffic["amplitudes"])
    per_amp = int(traffic["deformations"])
    sigma = float(traffic["velocity_sigma_vox"])
    root = jax.random.PRNGKey(int(traffic["pool_seed"]))

    @jax.jit
    def build(root):
        m0s, m1s = [], []
        for a, amp in enumerate(amps):
            for d in range(per_amp):
                j = a * per_amp + d
                k_img, k_vel = jax.random.split(jax.random.fold_in(root, j))
                m0 = phantom(k_img, n)
                m1 = ref.state_solve(m0, velocity(k_vel, n, amp, sigma), nt)[-1]
                m0s.append(m0)
                m1s.append(m1)
        return jnp.stack(m0s), jnp.stack(m1s)

    return build(root)


def _symmetry(rng, base: int, n: int) -> Dict:
    return dict(base=base,
                perm=[int(a) for a in rng.permutation(3)],
                flip=[int(a) for a in rng.integers(0, 2, 3)],
                shift=[int(a) for a in rng.integers(0, n, 3)])


def pair_plan(seed: int, traffic: Dict, k: int, n: int) -> Dict:
    """Which base pair and which grid symmetry pair k gets.

    Pass p = k // P of the pool (P base pairs) holds each base pair once:
    position i = k % P takes amplitude ``i % A`` and that amplitude's
    deformation ``order[i // A]``, the order drawn from ``(seed, 2**40, p,
    a)``. The symmetry comes from the pair's own stream ``(seed, k)``, the
    host's analogue of ``fold_in``.
    """
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    n_amp, per_amp = len(traffic["amplitudes"]), int(traffic["deformations"])
    p, i = divmod(k, n_amp * per_amp)
    a = i % n_amp
    order = np.random.default_rng([seed, _ORDER, p, a]).permutation(per_amp)
    return _symmetry(np.random.default_rng([seed, k]),
                     a * per_amp + int(order[i // n_amp]), n)


def warm_plan(seed: int, traffic: Dict, slot: int, n: int) -> Dict:
    """A pair for the uncounted warm-up solve: amplitude ``slot % A``,
    first deformation, its own stream."""
    a = slot % len(traffic["amplitudes"])
    return _symmetry(np.random.default_rng([seed, _WARM, slot]),
                     a * int(traffic["deformations"]), n)


#: Module name of the harness's own device work, which the trace reduction
#: keeps apart from the program's.
MATERIALIZE = "bench_materialize"


def _symmetry_index(n, perm, flip, shift):
    i = jnp.arange(n, dtype=jnp.int32)
    grid = jnp.stack(jnp.meshgrid(i, i, i, indexing="ij"), axis=0)
    src = jnp.take(grid, perm, axis=0)
    src = jnp.where(flip[:, None, None, None] == 1, (n - src) % n, src)
    src = (src + shift[:, None, None, None]) % n
    return (src[0] * n + src[1]) * n + src[2]


def _materialize(m0s, m1s, base, perm, flip, shift):
    n = m0s.shape[-1]

    def one(b, p, f, s):
        idx = _symmetry_index(n, p, f, s)
        return (jnp.take(jnp.take(m0s, b, axis=0).reshape(-1), idx),
                jnp.take(jnp.take(m1s, b, axis=0).reshape(-1), idx))

    return jax.vmap(one)(base, perm, flip, shift)


_materialize.__name__ = MATERIALIZE
_materialize_jit = jax.jit(_materialize)


def materialize(pool, plans: List[Dict]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Images of the planned pairs, stacked ``(len(plans), n, n, n)``, in
    one jitted call."""
    m0s, m1s = pool
    return _materialize_jit(
        m0s, m1s,
        jnp.asarray([p["base"] for p in plans], jnp.int32),
        jnp.asarray([p["perm"] for p in plans], jnp.int32),
        jnp.asarray([p["flip"] for p in plans], jnp.int32),
        jnp.asarray([p["shift"] for p in plans], jnp.int32))
