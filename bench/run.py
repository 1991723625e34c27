#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload claire_pair.large --seed 7 --seconds 10 --trace 0

The run needs the chips its cell asks for on the JAX platform ``tpu``;
elsewhere it exits with code 2 and prints no result. ``--rehearse`` runs the
same harness on the CPU at a 16^3 grid to check control flow; it prints the
counts and the check, and no metric under a device metric's name.

Standard output: one ``pair {...}`` line per pair the window completed, then
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(``end_to_end`` with ``--trace 0``, ``per_layer`` with ``--trace 1``),
``device``, ``breakdown`` (traced runs) and ``checks``, the compared numbers
with their limits, last. Standard error ends with the same numbers.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# libtpu would otherwise log to a fixed path under /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: Rehearsal grid (CPU): small enough to compile and run in minutes.
REHEARSAL_GRID = 16


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a 16^3 grid; prints no device metric")
    return ap.parse_args(argv)


def log_checks(checks):
    for name, c in checks.items():
        op = "<=" if c["bound"] == "max" else ">"
        print(f"check {name} {c['value']!r} {op} {c['limit']!r}", file=sys.stderr)


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    from bench import harness
    from bench.registry import Benchmark

    harness.configure_jax()
    bench = Benchmark(ROOT)
    cell = bench.workload(args.workload)
    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu"
                              or len(devices) < int(cell["chips"])):
        print(f"bench: needs {cell['chips']} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    out = harness.run_cell(
        bench, args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, grid=REHEARSAL_GRID if args.rehearse else None,
        device_trace=not args.rehearse)
    if args.rehearse:
        # Times and device readings on the CPU are not device metrics.
        out = dict(rehearsal=True, correct=out["correct"],
                   attempted=out["attempted"], failed=out["failed"],
                   cpu_metric_values={k: v["value"] for k, v in out["metrics"].items()},
                   checks=out["checks"])
    log_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
