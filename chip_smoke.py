#!/usr/bin/env python3
"""Smoke run of the registration solve on TPU chips, through ``repro.api``.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips: the slab-sharded solve only

One chip (the default) runs three phases in this one process:

1. ``claire_64`` (64^3, fd8-cubic, nt=4, beta=5e-4, tol_rel_grad=5e-2) through
   ``api.solve`` on the default path (``backend="jnp"``, ``use_plan=True``):
   a cold solve, whose time includes compiling, then a warm repeat. Checks: no
   NaN, det F min > 0, relative mismatch below the initial one.
2. The same solver on a 32^3 pair, once on the chip and once on the host CPU
   device, compared at ``REF_RTOL``.
3. The Pallas FD8 gradient and B-spline prefilter kernels compiled for the
   chip at 256^3, compared with the jnp stencils the solver runs.

``--chips 4`` runs ``claire_64`` slab-sharded over a 4-chip ``("slab",)``
mesh (16 x1 planes per chip) and single-device on one of those chips, and
compares the per-step gradient norms and the final mismatch at
``SHARD_RTOL``.

The script needs a TPU: on any other platform it exits non-zero before
running anything. It never starts a child process (a parent that has touched
JAX holds the chip). A failed check raises; the last line of standard output
is a JSON record of the device, printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys
import time

#: Chip vs host CPU at 32^3. Both run fp32 with the same program, so they
#: differ only by round-off: reduction order, the FFT implementation and the
#: fusion of elementwise chains. Round-off enters the first gradient norm
#: through one state/adjoint solve (expected near 1e-6), and the final
#: mismatch through the Newton iterates, where the PCG stopping test and the
#: Armijo acceptance can amplify it. 1e-3 bounds both while still catching a
#: wrong result: a kernel or layout fault moves these numbers by percents.
REF_RTOL = 1e-3
#: Slab-sharded vs one chip: the CPU tests' bound for the same comparison.
SHARD_RTOL = 1e-4
#: Pallas stencil vs jnp stencil, relative to the largest |value|: the same
#: fp32 taps summed in a different order.
KERNEL_RTOL = 1e-5

ROOT = pathlib.Path(__file__).resolve().parent


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(ok: bool, msg: str):
    if not ok:
        fail(msg)


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def claire_options(name: str, **kw):
    from repro import api
    from repro.configs import REGISTRATIONS

    c = REGISTRATIONS[name]
    return c, api.SolverOptions(
        mode="single", variant=c.variant, nt=c.nt, beta=c.beta, gamma=c.gamma,
        tol_rel_grad=c.tol_rel_grad, max_newton=c.max_newton, **kw)


def timed_solve(problem, options):
    import jax
    from repro import api

    t0 = time.perf_counter()
    res = api.solve(problem, options)
    jax.block_until_ready(res.v)
    return res, time.perf_counter() - t0


def check_solution(label: str, problem, res):
    import jax.numpy as jnp
    from repro.core import objective

    initial = float(objective.relative_mismatch(problem.m0, problem.m1, problem.m0))
    check(bool(jnp.all(jnp.isfinite(res.v))), f"{label}: non-finite velocity")
    check(bool(jnp.all(jnp.isfinite(res.m_warped))), f"{label}: non-finite warped image")
    check(all(math.isfinite(x) for x in (res.mismatch_rel, *res.detF.values())),
          f"{label}: non-finite metrics {res.mismatch_rel} {res.detF}")
    check(res.detF["min"] > 0.0, f"{label}: det F min {res.detF['min']} <= 0 (folding)")
    check(res.mismatch_rel < initial,
          f"{label}: mismatch {res.mismatch_rel} not below initial {initial}")
    return initial


def print_solve(label: str, res, wall_s: float, what: str):
    d = res.detF
    print(f"[{label}] newton_iters={res.iters} matvecs={res.matvecs} "
          f"converged={res.converged} rel_grad={res.rel_grad!r} "
          f"mismatch_rel={res.mismatch_rel!r} "
          f"detF min={d['min']!r} mean={d['mean']!r} max={d['max']!r}", flush=True)
    print(f"[{label}] wall_s={wall_s!r} ({what})", flush=True)


def phase_solve(dev, name="claire_64"):
    from repro import api

    cfg, opts = claire_options(name)
    problem = api.RegistrationProblem.synthetic(seed=0, grid=cfg.grid)
    cold, cold_s = timed_solve(problem, opts)
    initial = check_solution(name, problem, cold)
    print_solve(name, cold, cold_s,
                "cold solve: includes tracing and compiling every program")
    warm, warm_s = timed_solve(problem, opts)
    check_solution(f"{name} warm", problem, warm)
    print(f"[{name}] warm repeat wall_s={warm_s!r} (same process, same "
          f"problem: retraces, compiled programs come from the compile cache) "
          f"newton_iters={warm.iters} mismatch_rel={warm.mismatch_rel!r}",
          flush=True)
    print(f"[{name}] initial mismatch_rel={initial!r} "
          f"dice before={cold.dice_before!r} after={cold.dice_after!r}",
          flush=True)
    stats = dev.memory_stats() or {}
    print(f"[{name}] device peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"(process peak after both solves; "
          f"bytes_limit={stats.get('bytes_limit')})", flush=True)


def phase_cpu_reference(grid=(32, 32, 32)):
    import jax
    from repro import api

    _, opts = claire_options("claire_64")
    syn = api.RegistrationProblem.synthetic(seed=0, grid=grid)
    on_chip = api.RegistrationProblem(m0=syn.m0, m1=syn.m1)
    chip, chip_s = timed_solve(on_chip, opts)
    check_solution("ref tpu", on_chip, chip)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        on_cpu = api.RegistrationProblem(m0=jax.device_put(syn.m0, cpu),
                                         m1=jax.device_put(syn.m1, cpu))
        host, host_s = timed_solve(on_cpu, opts)
    check(host.v.devices() == {cpu}, f"CPU reference ran on {host.v.devices()}")
    check_solution("ref cpu", on_cpu, host)
    print_solve("ref tpu", chip, chip_s, f"{grid} on the chip, includes compiling")
    print_solve("ref cpu", host, host_s, f"{grid} on the host CPU, includes compiling")
    g_chip, g_cpu = chip.history[0]["gnorm"], host.history[0]["gnorm"]
    d_g = rel_diff(g_chip, g_cpu)
    d_m = rel_diff(chip.mismatch_rel, host.mismatch_rel)
    print(f"[ref] first-step gnorm tpu={g_chip!r} cpu={g_cpu!r} rel_diff={d_g!r}; "
          f"final mismatch_rel tpu={chip.mismatch_rel!r} cpu={host.mismatch_rel!r} "
          f"rel_diff={d_m!r}; tolerance {REF_RTOL}", flush=True)
    check(d_g <= REF_RTOL, f"first-step gradient norm differs by {d_g} > {REF_RTOL}")
    check(d_m <= REF_RTOL, f"final mismatch differs by {d_m} > {REF_RTOL}")


def phase_pallas_stencils(n=256):
    import jax
    import jax.numpy as jnp
    from repro.core import derivatives, interp
    from repro.kernels.fd8 import ops as fd8_ops
    from repro.kernels.prefilter import ops as prefilter_ops

    f = jax.random.normal(jax.random.PRNGKey(0), (n, n, n), jnp.float32)
    pairs = {
        "fd8_grad": (fd8_ops.fd8_grad(f, interpret=False),
                     jax.jit(derivatives.fd8_grad)(f)),
        "prefilter": (prefilter_ops.prefilter3d(f, interpret=False),
                      jax.jit(interp.prefilter_fir)(f)),
    }
    for kernel_name, (kernel, ref) in pairs.items():
        err = float(jnp.max(jnp.abs(kernel - ref)) / jnp.max(jnp.abs(ref)))
        print(f"[pallas {n}^3] {kernel_name}: max|pallas - jnp| / max|jnp| = "
              f"{err!r} (tolerance {KERNEL_RTOL})", flush=True)
        check(err <= KERNEL_RTOL, f"Pallas {kernel_name} differs from jnp by {err}")


def phase_sharded(devices, name="claire_64"):
    import jax
    from repro import api

    cfg, single_opts = claire_options(name)
    # Explicit axes, as jax.make_mesh builds them: register_sharded converts.
    mesh = jax.make_mesh((len(devices),), ("slab",), devices=devices)
    _, sharded_opts = claire_options(name, mesh=mesh)
    problem = api.RegistrationProblem.synthetic(seed=0, grid=cfg.grid)
    sharded_label = f"{name} slab x{len(devices)}"
    sharded, sharded_s = timed_solve(problem, sharded_opts)
    check(sharded.mesh == {"slab": len(devices)}, f"solved on mesh {sharded.mesh}")
    check_solution(sharded_label, problem, sharded)
    with jax.default_device(devices[0]):
        one = api.RegistrationProblem(m0=jax.device_put(problem.m0, devices[0]),
                                      m1=jax.device_put(problem.m1, devices[0]))
        single, single_s = timed_solve(one, single_opts)
    check(single.v.devices() == {devices[0]}, f"single solve ran on {single.v.devices()}")
    check_solution(f"{name} one chip", one, single)
    print_solve(sharded_label, sharded, sharded_s, "cold, includes compiling")
    print_solve(f"{name} one chip", single, single_s, "cold, includes compiling")
    g_sh = [h["gnorm"] for h in sharded.history]
    g_one = [h["gnorm"] for h in single.history]
    print(f"[sharded] per-step gnorm {sharded_label}={g_sh!r}", flush=True)
    print(f"[sharded] per-step gnorm one chip={g_one!r}", flush=True)
    check(len(g_sh) == len(g_one),
          f"Newton evaluations differ: {len(g_sh)} sharded vs {len(g_one)}")
    d_g = max(rel_diff(a, b) for a, b in zip(g_sh, g_one))
    d_m = rel_diff(sharded.mismatch_rel, single.mismatch_rel)
    print(f"[sharded] max per-step gnorm rel_diff={d_g!r}; final mismatch_rel "
          f"sharded={sharded.mismatch_rel!r} one chip={single.mismatch_rel!r} "
          f"rel_diff={d_m!r}; tolerance {SHARD_RTOL}", flush=True)
    check(d_g <= SHARD_RTOL, f"per-step gradient norms differ by {d_g} > {SHARD_RTOL}")
    check(d_m <= SHARD_RTOL, f"final mismatch differs by {d_m} > {SHARD_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    # The CPU reference needs the host platform next to the TPU; it never
    # replaces it (the platform check below comes first).
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
              f"({len(devices)} device(s)). No CPU fallback.", file=sys.stderr)
        return 2
    print(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, found {len(devices)}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    print(f"[device] compile cache: {where or os.environ['JAX_COMPILATION_CACHE_DIR']}",
          flush=True)

    if args.chips == 4:
        phase_sharded(devices[:4])
    else:
        phase_solve(dev)
        phase_cpu_reference()
        phase_pallas_stencils()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
