"""The check catches what it exists to catch, at 16^3 on the CPU.

Each test skips only the harness's look for a chip and drives the rest of a
run (set-up, window, check) with the timed path left whole, or broken
underneath, and reads ``correct``:

- sound runs of both cells are correct;
- the control, the program's own lower-precision path (bfloat16
  interpolation weights, ``mixed_precision=True``), is not;
- a Newton step that returns its state unchanged is not;
- a batch whose second half is left out of the Newton update is not;
- an answer altered where it is produced (the velocity the solver returns,
  or the warped image it scores) is not.
"""

import time

import pytest

from bench import harness
from bench.registry import Benchmark

GRID = 16
PAIR = "claire_pair.large"
WAVE = "claire_ensemble.mixed"


@pytest.fixture(scope="module")
def bench():
    harness.configure_jax()
    return Benchmark()


def run(bench, workload, seed=11, **kw):
    return harness.run_cell(bench, workload, seed, seconds=0.1, trace=False,
                            t_start=time.perf_counter(), grid=GRID,
                            device_trace=False, log=lambda line: None, **kw)


def failing(out):
    return [k for k, c in out["checks"].items()
            if not (c["value"] <= c["limit"] if c["bound"] == "max" else c["value"] > c["limit"])]


@pytest.mark.parametrize("workload", [PAIR, WAVE])
def test_sound_run_is_correct(bench, workload):
    out = run(bench, workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1


@pytest.mark.parametrize("workload", [PAIR, WAVE])
def test_control_lower_precision_fails(bench, workload):
    out = run(bench, workload, solver_overrides={"mixed_precision": True})
    assert not out["correct"]
    assert "warp_rel" in failing(out)


def test_step_returning_state_unchanged_fails(bench, monkeypatch):
    from repro.core import gauss_newton

    build = gauss_newton._build_step

    def frozen(cfg, gn):
        step = build(cfg, gn)
        return lambda m0, m1, v, *a: step(m0, m1, v, *a)._replace(v_new=v)

    monkeypatch.setattr(gauss_newton, "_build_step", frozen)
    out = run(bench, PAIR)
    assert not out["correct"] and "rel_grad" in failing(out)


def test_half_batch_left_out_fails(bench, monkeypatch):
    import jax.numpy as jnp
    from repro.core import gauss_newton

    make = gauss_newton._make_batch_step

    def half(cfg, gn, donate=False):
        step = make(cfg, gn, donate=donate)

        def run_half(m0, m1, v, *a):
            out = step(m0, m1, v, *a)
            keep = v.shape[0] // 2
            return out._replace(v_new=jnp.concatenate([out.v_new[:keep], v[keep:]]))

        return run_half

    monkeypatch.setattr(gauss_newton, "_make_batch_step", half)
    out = run(bench, WAVE)
    assert not out["correct"] and "rel_grad" in failing(out)


def test_altered_velocity_fails(bench, monkeypatch):
    from repro.core import gauss_newton

    solve = gauss_newton.solve

    def altered(*a, **kw):
        res = solve(*a, **kw)
        return res._replace(v=res.v * 1.01)

    monkeypatch.setattr(gauss_newton, "solve", altered)
    out = run(bench, PAIR)
    assert not out["correct"] and "grad_gap" in failing(out)


def test_altered_warped_image_fails(bench, monkeypatch):
    from repro.core import registration

    score = registration._score_batch

    def altered(m0, m1, v, cfg):
        m_warped, mis, detf = score(m0, m1, v, cfg)
        return m_warped.at[0].multiply(1.001), mis, detf

    monkeypatch.setattr(registration, "_score_batch", altered)
    out = run(bench, WAVE)
    assert not out["correct"] and failing(out) == ["warp_rel"]
