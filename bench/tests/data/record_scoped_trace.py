"""Record ``scoped_trace.xplane.pb`` and ``scoped_trace.json``, the fixture
of ``test_scopes``.

    python3 bench/tests/data/record_scoped_trace.py            # on a TPU
    JAX_PLATFORMS=cpu python3 bench/tests/data/record_scoped_trace.py

One 16^3 pair, solved once to warm up and then once under the profiler as
the harness's window does: a ``data`` span, then a ``solve`` span around
``repro.api.Solver.solve`` (fd8-linear, nt 1, two Newton evaluations at
most), with the harness's own ``monitor.Spans`` and ``CompileWatch``. The
program records its spans (``repro.obs``) and scopes its device ops. The
JSON file holds the program's solve record, the harness's wall-clock spans
(``trace/lower`` included), the window and the platform.

On the CPU the trace's XLA ops are laid out as a ``/device:TPU:0`` plane,
as ``record_tiny_trace.py`` does.
"""

import glob
import json
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]


def main() -> int:
    import jax
    import jax.numpy as jnp

    from bench import monitor
    from record_tiny_trace import as_device_plane
    from repro import api, obs

    problem = api.RegistrationProblem.synthetic(seed=0, grid=(16, 16, 16))
    solver = api.Solver(api.SolverOptions(variant="fd8-linear", nt=1,
                                          max_newton=2, mode="single"))
    res = solver.solve(problem)
    jax.block_until_ready(res.v)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    spans = monitor.Spans()
    watch = monitor.CompileWatch()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        spans.annotate = True
        mark = watch.mark()
        start = time.time_ns()
        with spans.span("data"):
            m0 = jax.block_until_ready(jnp.asarray(problem.m0) + 0.0)
        with spans.span("solve"):
            res = solver.solve(api.RegistrationProblem(m0=m0, m1=problem.m1))
            jax.block_until_ready((res.v, res.m_warped))
        end = time.time_ns()
        spans.annotate = False
        jax.profiler.stop_trace()
        host_spans = spans.records + watch.spans_ns(mark)
        path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
        space = pathlib.Path(path).read_bytes()
        if jax.devices()[0].platform != "tpu":
            space = as_device_plane(space)
        (HERE / "scoped_trace.xplane.pb").write_bytes(space)
    finally:
        watch.close()
        shutil.rmtree(tmp, ignore_errors=True)
    (HERE / "scoped_trace.json").write_text(json.dumps(dict(
        recorded_on=jax.devices()[0].device_kind, window_wall_ns=[start, end],
        host_spans=host_spans, records=obs.recent(1),
        matvecs=int(res.matvecs)), indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
