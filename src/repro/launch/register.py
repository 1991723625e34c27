"""Registration launcher — the paper's workload.

    python -m repro.launch.register --config claire_64 --variant fd8-cubic
    python -m repro.launch.register --grid 32 --variant fft-cubic --verbose

Generates a synthetic NIREP-like pair at the configured grid size (no
clinical data in this container), runs the Gauss-Newton-Krylov solver and
reports the paper's metrics (relative mismatch, det F stats, iterations,
Hessian matvecs, runtime).
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import REGISTRATIONS, get_registration
from repro.core.registration import VARIANTS, register
from repro.data import synthetic
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", choices=sorted(REGISTRATIONS), default=None)
    ap.add_argument("--grid", type=int, default=None,
                    help="cubic grid size override (e.g. 32 for CPU runs)")
    ap.add_argument("--variant", choices=sorted(VARIANTS), default="fd8-cubic")
    ap.add_argument("--nt", type=int, default=4)
    ap.add_argument("--max-newton", type=int, default=50)
    ap.add_argument("--beta", type=float, default=5e-4)
    ap.add_argument("--amplitude", type=float, default=0.5)
    ap.add_argument("--backend", choices=("jnp", "pallas"), default="jnp")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.grid:
        grid = (args.grid,) * 3
    else:
        cfg = get_registration(args.config or "claire_64")
        grid = cfg.grid

    print(f"[register] synthesizing pair at {grid} ...")
    pair = synthetic.make_pair(jax.random.PRNGKey(args.seed), grid,
                               amplitude=args.amplitude, nt=args.nt)
    res = register(pair.m0, pair.m1, variant=args.variant, beta=args.beta,
                   nt=args.nt, max_newton=args.max_newton,
                   backend=args.backend, verbose=args.verbose)
    print(f"[register] variant={args.variant} grid={grid}")
    print(f"  converged={res.converged} iters={res.iters} matvecs={res.matvecs}")
    print(f"  rel mismatch={res.mismatch_rel:.3e} rel grad={res.rel_grad:.3e}")
    print(f"  det F: min={res.detF['min']:.3f} mean={res.detF['mean']:.3f} "
          f"max={res.detF['max']:.3f}")
    print(f"  wall time: {res.wall_time_s:.2f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
