"""The plain reference and the program agree at 16^3 on the CPU, piece by
piece, before any chip run. (The benchmark itself never imports
``repro.core``; only this test does, to set the two side by side.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data
from bench import reference as ref

N = 16
NT = 4
RTOL = 1e-5


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def fields():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    m0 = data.phantom(k1, N)
    v = data.velocity(k2, N, 0.5, 3.0)
    m1 = ref.state_solve(m0, data.velocity(k3, N, 0.4, 3.0), NT)[-1]
    return m0, m1, v


@pytest.fixture(scope="module")
def cfg():
    from repro.core import transport

    return transport.TransportConfig(interp="cubic_bspline", deriv="fd8", nt=NT)


def test_fd8(fields):
    from repro.core import derivatives

    m0, _, v = fields
    assert rel(ref.fd8_grad(m0), derivatives.fd8_grad(m0)) < RTOL
    assert rel(ref.fd8_div(v), derivatives.fd8_div(v)) < RTOL


def test_prefilter_and_interp(fields):
    from repro.core import interp

    m0, _, v = fields
    q = ref.index_grid((N, N, N)) + 2.3 * v
    assert rel(ref.prefilter(m0), interp.prefilter_fir(m0)) < RTOL
    coef = ref.prefilter(m0)
    prog = interp.apply_plan(interp.build_plan(q, "cubic_bspline", shape=(N, N, N)), coef)
    assert rel(ref.interp(coef, q), prog) < RTOL


def test_transport(fields, cfg):
    from repro.core import transport

    m0, m1, v = fields
    assert rel(ref.footpoints(v, 1 / NT, 1.0), transport.footpoints(v, cfg, 1.0)) < RTOL
    assert rel(ref.footpoints(v, 1 / NT, -1.0), transport.footpoints(v, cfg, -1.0)) < RTOL
    assert rel(ref.state_solve(m0, v, NT), transport.solve_state(m0, v, cfg)) < RTOL
    lam1 = m1 - m0
    assert rel(ref.adjoint_solve(lam1, v, NT), transport.solve_adjoint(lam1, v, cfg)) < RTOL


def test_regularizer_objective_gradient(fields, cfg):
    from repro.core import gradient, objective, spectral

    m0, m1, v = fields
    assert rel(ref.reg_apply(v, 5e-4, 1e-4), spectral.apply_regop(v, 5e-4, 1e-4)) < RTOL
    j_ref = ref.objective(m0, m1, v, 5e-4, 1e-4, NT)
    assert rel(j_ref, objective.objective(m0, m1, v, 5e-4, 1e-4, cfg)) < RTOL
    g_prog = gradient.evaluate(m0, m1, v, 5e-4, 1e-4, cfg).g
    assert rel(ref.gradient(m0, m1, v, 5e-4, 1e-4, NT), g_prog) < RTOL


def test_det_f(fields, cfg):
    from repro.core import metrics

    _, _, v = fields
    assert rel(ref.det_f(v, NT), metrics.det_deformation_gradient(v, cfg)) < RTOL


def test_data_matches_program_generator():
    """The benchmark's phantom and velocity copy the program's generator."""
    from repro.data import synthetic

    key = jax.random.PRNGKey(3)
    assert rel(data.phantom(key, N), synthetic.brain_phantom(key, (N, N, N))) < RTOL
    assert rel(data.velocity(key, N, 0.5, 3.0),
               synthetic.random_velocity(key, (N, N, N), amplitude=0.5)) < RTOL


def test_symmetry_is_a_voxel_permutation():
    pool = (jnp.arange(N ** 3, dtype=jnp.float32).reshape(1, N, N, N),) * 2
    traffic = dict(amplitudes=[0.5], deformations=1)
    plans = [data.pair_plan(7, traffic, k, N) for k in range(3)]
    m0, m1 = data.materialize(pool, plans)
    for k in range(3):
        assert sorted(np.asarray(m0[k]).ravel().tolist()) == list(range(N ** 3))
    np.testing.assert_array_equal(m0, m1)
