#!/usr/bin/env python3
"""Readings for the check's limits, several seeds in one process.

    python3 bench/calibrate.py --workload claire_pair.large --seeds 1 2 3 --control

Runs the harness of ``bench/run.py`` once per seed, with a short window (one
pair or wave), and prints each run's compared numbers as a JSON line. With
``--control`` the program runs its own lower-precision path
(``mixed_precision=True``: bfloat16 interpolation weights), the control that
the limits must reject. The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
# libtpu would otherwise log to a fixed path under /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench.registry import Benchmark

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.configure_jax()
    bench = Benchmark(ROOT)
    overrides = {"mixed_precision": True} if args.control else None
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = harness.run_cell(bench, args.workload, seed, args.seconds, trace=False,
                               t_start=t0, solver_overrides=overrides,
                               log=lambda line: None)
        print(json.dumps(dict(seed=seed, control=args.control, correct=out["correct"],
                              attempted=out["attempted"], failed=out["failed"],
                              seconds=time.perf_counter() - t0,
                              checks={k: c["value"] for k, c in out["checks"].items()})),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
