"""Multi-device behaviour, run in subprocesses with forced host devices
(the parent test process must keep the default 1-device view).

The subprocess harness lives in ``conftest.run_forced`` (via the
``forced_devices`` fixture): it sets XLA_FLAGS before the first jax
initialization and *asserts* the forced device count materialized, so these
tests fail loudly instead of silently running on one device. End-to-end
sharded *registration* equality tests live in ``test_dist_registration.py``.
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.multidev


def test_halo_sl_step_matches_single_device(forced_devices):
    """Slab-sharded semi-Lagrangian with explicit ring halo exchange equals
    the single-device SL step."""
    forced_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.distributed.claire_dist import halo_sl_step
        from repro.core import semilag as SL, transport as T, grid as G
        from repro.data import synthetic

        mesh = make_mesh((1, 4), ("data", "model"))
        shape = (32, 16, 16)
        pair = synthetic.make_pair(jax.random.PRNGKey(0), shape, amplitude=0.4)
        cfg = T.TransportConfig(interp="cubic_bspline", nt=4)
        foot = T.footpoints(pair.v_true, cfg)
        ref = SL.sl_step(pair.m0, foot, cfg.interp)
        sharded = jax.jit(halo_sl_step(mesh, halo=8))(pair.m0, foot)
        # the halo prefilter is exact -> only fp32 op-ordering noise remains
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("halo OK")
    """)


def test_compressed_psum_matches_mean(forced_devices):
    """int8 cross-pod gradient exchange approximates the exact mean."""
    forced_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.distributed.compression import compressed_psum_pod

        mesh = make_mesh((4,), ("pod",))
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 64))

        f = jax.shard_map(
            lambda x: compressed_psum_pod({"g": x[0]}, "pod")["g"],
            mesh=mesh, in_specs=(P("pod", None),), out_specs=P(None),
            check_vma=False)
        # out_specs P(None): identical replicas -> take as-is
        approx = f(g.reshape(4, 1, 64))
        exact = jnp.mean(g, axis=0)
        rel = float(jnp.max(jnp.abs(approx - exact))
                    / (jnp.max(jnp.abs(exact)) + 1e-9))
        assert rel < 2e-2, rel
        print("compression OK", rel)
    """)


def test_sharded_train_step_runs_on_4_devices(forced_devices):
    """Smoke config train step on a (2, 2) mesh: sharded end to end."""
    forced_devices(4, """
        import jax, jax.numpy as jnp
        from repro.configs import ARCHS
        from repro.configs.base import ShapeConfig
        from repro.models import build_model
        from repro.launch.mesh import make_mesh
        from repro.train import steps as tsteps

        cfg = ARCHS["smollm-135m"].smoke()
        model = build_model(cfg)
        mesh = make_mesh((2, 2), ("data", "model"))
        step_fn, state_sh = tsteps.make_train_step(model, mesh)
        state = tsteps.init_train_state(model, jax.random.PRNGKey(0))
        state = jax.device_put(state, state_sh)
        shape = ShapeConfig("t", 64, 4, "train")
        batch = model.make_batch(jax.random.PRNGKey(1), shape)["batch"]
        batch = jax.device_put(batch, tsteps.batch_shardings(model, mesh, batch))
        new_state, metrics = jax.jit(step_fn, donate_argnums=(0,))(state, batch)
        loss = float(metrics["loss"])
        assert loss == loss and loss < 20, loss
        print("4-dev train OK", loss)
    """)


def test_dryrun_cell_end_to_end():
    """The dry-run driver itself: one cell on the production 512-device
    mesh, JSON record with all roofline fields present."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "smollm-135m",
         "--shape", "decode_32k", "--mesh", "multi"],
        env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout
    assert "bound=" in res.stdout


def test_slab_pallas_backend_matches_jnp(forced_devices):
    """Pallas halo-tile kernels inside shard_map: a full slab solve with
    backend="pallas" equals the jnp slab path, and the fused matvec and the
    int8-compressed halos stay on the same solution (int8 is lossy, so only
    loosely)."""
    forced_devices(8, """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core import registration as R, gauss_newton as GN
        from repro.distributed import claire_dist as D
        from repro.data import synthetic as S

        n = 24
        pair = S.make_pair(jax.random.PRNGKey(3), (n, n, n), amplitude=0.5)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("slab",))
        gn = GN.GNConfig(max_newton=2)

        outs = {}
        for backend in ("jnp", "pallas"):
            cfg = R.make_transport_config("fd8-cubic", nt=4, backend=backend)
            res = D.solve_slab(pair.m0, pair.m1, cfg, gn, mesh=mesh, halo=6)
            outs[backend] = np.asarray(jax.device_get(res.v))
        dev = float(np.max(np.abs(outs["jnp"] - outs["pallas"])))
        assert dev <= 1e-4, dev

        cfg = R.make_transport_config("fd8-cubic", nt=4)
        res_c = D.solve_slab(pair.m0, pair.m1, cfg, gn, mesh=mesh, halo=6,
                             compress="int8")
        dev_c = float(np.max(np.abs(
            outs["jnp"] - np.asarray(jax.device_get(res_c.v)))))
        assert np.isfinite(dev_c) and dev_c < 5e-2, dev_c

        cfg_f = R.make_transport_config("fd8-cubic", nt=4,
                                        use_fused_matvec=True)
        res_f = D.solve_slab(pair.m0, pair.m1, cfg_f, gn, mesh=mesh, halo=6)
        dev_f = float(np.max(np.abs(
            outs["jnp"] - np.asarray(jax.device_get(res_f.v)))))
        assert dev_f <= 1e-4, dev_f
        print("slab pallas OK", dev, dev_c, dev_f)
    """)


def test_ensemble_registration_sharded(forced_devices):
    """Ensemble (population-study) DP: batch of pairs sharded over devices;
    results match the unsharded vmap."""
    forced_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.distributed.claire_dist import (
            ensemble_newton_step, ensemble_shardings)
        from repro.core import gauss_newton as GN, transport as T
        from repro.data import synthetic

        mesh = make_mesh((4, 1), ("data", "model"))
        shape = (12, 12, 12)
        batch = synthetic.make_batch(jax.random.PRNGKey(0), shape, batch=4,
                                     amplitude=0.4)
        cfg = T.TransportConfig(nt=2)
        gn = GN.GNConfig(max_pcg=10)
        step = ensemble_newton_step(cfg, gn)
        v0 = jnp.zeros((4, 3) + shape, jnp.float32)
        img_sh, vel_sh = ensemble_shardings(mesh, 4)
        m0 = jax.device_put(batch.m0, img_sh)
        m1 = jax.device_put(batch.m1, img_sh)
        v = jax.device_put(v0, vel_sh)
        stats = jax.jit(step)(m0, m1, v, jnp.float32(5e-4),
                              jnp.float32(1e-4), jnp.float32(0.5))
        assert stats.v_new.shape == (4, 3) + shape
        assert bool(jnp.all(jnp.isfinite(stats.gnorm)))
        print("ensemble OK")
    """)
