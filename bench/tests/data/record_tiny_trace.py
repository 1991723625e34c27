"""Record ``tiny_trace.xplane.pb`` and ``tiny_trace_spans.json``, the trace
fixture of ``test_trace_reduce``.

    python3 bench/tests/data/record_tiny_trace.py            # on a TPU
    JAX_PLATFORMS=cpu python3 bench/tests/data/record_tiny_trace.py

Twice, under the profiler and with the harness's own span names: a ``data``
span makes a 16^3 pair with the harness's jitted data op
(``bench.data.materialize``), then a ``solve`` span runs one jitted call (a
B-spline interpolation through the program's plan, an FD8 divergence and
the spectral inverse regularizer). The spans are the harness's
``monitor.Spans``: recorded on the wall clock and mirrored into the trace,
so the test aligns the two clocks as a run does. Their wall-clock records,
the window and the platform it was recorded on go to the JSON file.

On a TPU the trace is kept as recorded. On the CPU the events of the host
plane that carry a ``program_id`` (its XLA ops) are copied into a plane
named ``/device:TPU:0`` with one line ``XLA Ops``, the layout of a TPU
trace, so the reduction reads them as it reads a chip's; the HLO modules
stay in ``/host:metadata`` as recorded.
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
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _key(num, wt):
    return _varint((num << 3) | wt)


def _varint(x):
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        if x:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _ld(num, payload: bytes) -> bytes:
    return _key(num, 2) + _varint(len(payload)) + payload


def _encode(num, wt, val) -> bytes:
    if wt == 0:
        return _key(num, 0) + _varint(val)
    if wt == 2:
        return _ld(num, val)
    return _key(num, wt) + val


def as_device_plane(space: bytes) -> bytes:
    """Append a ``/device:TPU:0`` plane with one ``XLA Ops`` line: the CPU
    plane's events that carry a ``program_id``, i.e. its XLA ops."""
    from bench import xspace

    out = bytearray(space)
    for num, _, plane in xspace.fields(space):
        if num != 1:
            continue
        name = next((v for n, _, v in xspace.fields(plane) if n == 2), b"")
        if name != b"/host:CPU":
            continue
        stat_ids = {}
        for n, _, v in xspace.fields(plane):
            if n == 5:
                for kn, _, kv in xspace.fields(v):
                    if kn == 2:
                        f = dict((a, b) for a, _, b in xspace.fields(kv))
                        stat_ids[f.get(2, b"").decode()] = f.get(1)
        pid_stat = stat_ids.get("program_id")
        new = bytearray()
        events = bytearray()
        base = None
        for n, wt, v in xspace.fields(plane):
            if n == 2:
                new += _ld(2, b"/device:TPU:0")
            elif n == 3:
                line = list(xspace.fields(v))
                ts = next((b for a, _, b in line if a == 3), 0)
                evs = [b for a, _, b in line if a == 4]
                evs = [ev for ev in evs
                       if any(any(sn == 1 and sv == pid_stat
                                  for sn, _, sv in xspace.fields(st))
                              for en, _, st in xspace.fields(ev) if en == 4)]
                if not evs:
                    continue
                base = ts if base is None else base
                for ev in evs:
                    # Re-base each event's offset (field 2, ps) on the first line.
                    fixed = bytearray()
                    for en, ewt, ev_v in xspace.fields(ev):
                        if en == 2:
                            ev_v = ev_v + (ts - base) * 1000
                        fixed += _encode(en, ewt, ev_v)
                    events += _ld(4, bytes(fixed))
            else:
                new += _encode(n, wt, v)
        line = _ld(2, b"XLA Ops") + _key(3, 0) + _varint(base or 0) + bytes(events)
        new += _ld(3, line)
        out += _ld(1, bytes(new))
    return bytes(out)


def main() -> int:
    import jax

    from bench import data, monitor
    from repro.core import derivatives, interp, spectral

    n = 16

    def tiny(f, q, v):
        plan = interp.build_plan(q, "cubic_bspline", shape=f.shape)
        a = interp.apply_plan(plan, interp.prefilter_fir(f))
        return a + derivatives.fd8_div(v) + spectral.apply_inv_regop(v, 5e-4, 1e-4)[0]

    fn = jax.jit(tiny)
    k = jax.random.PRNGKey(0)
    pool = (jax.random.normal(k, (1, n, n, n)),) * 2
    traffic = dict(amplitudes=[0.5], deformations=1)
    q = jax.random.uniform(k, (3, n, n, n)) * n
    v = jax.random.normal(k, (3, n, n, n))
    m0, _ = data.materialize(pool, [data.pair_plan(0, traffic, 0, n)])
    fn(m0[0], q, v).block_until_ready()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    spans = monitor.Spans()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        spans.annotate = True
        start = time.time_ns()
        for k in range(2):
            with spans.span("data"):
                m0, _ = data.materialize(pool, [data.pair_plan(0, traffic, k, n)])
                jax.block_until_ready(m0)
            with spans.span("solve"):
                fn(m0[0], q, v).block_until_ready()
            time.sleep(0.005)
        end = time.time_ns()
        spans.annotate = False
        jax.profiler.stop_trace()
        path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
        space = pathlib.Path(path).read_bytes()
        if jax.devices()[0].platform != "tpu":
            space = as_device_plane(space)
        (HERE / "tiny_trace.xplane.pb").write_bytes(space)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    (HERE / "tiny_trace_spans.json").write_text(json.dumps(dict(
        recorded_on=jax.devices()[0].device_kind, window_wall_ns=[start, end],
        spans=spans.records), indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
