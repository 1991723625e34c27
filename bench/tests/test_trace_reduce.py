"""The trace reduction on a small recorded trace, and its interval
arithmetic and peaks table on their own.

``data/tiny_trace.xplane.pb`` was recorded by ``data/record_tiny_trace.py``
(``data/tiny_trace_spans.json`` names the platform): twice, a ``data`` span
that makes a 16^3 pair with the harness's own data op, then a ``solve``
span around one jitted call (a B-spline interpolation through a plan, an
FD8 divergence and the spectral inverse regularizer). The spans are the
harness's own, so the JSON file holds their wall-clock records and the
window, and the reduction aligns the clocks as a run does.
"""

import json
import pathlib

import pytest

from bench import trace_reduce as tr
from bench import xspace

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACE = DATA / "tiny_trace.xplane.pb"
SPANS = DATA / "tiny_trace_spans.json"
KIND = "TPU v5 lite"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    rec = json.loads(SPANS.read_text())
    spans = [(n, int(a), int(b)) for n, a, b in rec["spans"]]
    return ProfileData.from_file(str(TRACE)), spans, tuple(rec["window_wall_ns"])


def test_interval_arithmetic():
    a = tr.union([(5, 9), (0, 2), (1, 3), (8, 12)])
    assert a == [(0, 3), (5, 12)]
    assert tr.intersect(a, [(2, 6), (10, 20)]) == [(2, 3), (5, 6), (10, 12)]
    assert tr.subtract([(0, 20)], a) == [(3, 5), (12, 20)]
    assert tr.length(a) == 10


def test_unknown_device_kind_fails(recorded):
    _, spans, window = recorded
    assert tr.peaks(KIND)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        tr.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        tr.reduce(str(TRACE), spans, window, "cpu")


def test_clock_offset_from_wall_spans(recorded):
    pd, spans, _ = recorded
    offset = tr._clock_offset(pd, spans)
    starts = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in tr.HARNESS_SPANS:
                        starts.setdefault(ev.name, []).append(int(ev.start_ns))
    assert sorted(starts) == ["data", "solve"] and all(len(v) == 2 for v in starts.values())
    # Each annotated span starts in the trace at its wall-clock start plus
    # the offset, to within a millisecond.
    for name in tr.HARNESS_SPANS:
        wall = sorted(a for n, a, _ in spans if n == name)
        for w, t in zip(wall, sorted(starts[name])):
            assert abs(t - (w + offset)) < 1_000_000
    moved = [(n, a + 5_000_000_000, b + 5_000_000_000) for n, a, b in spans]
    assert tr._clock_offset(pd, moved) == offset - 5_000_000_000
    with pytest.raises(ValueError):
        tr._clock_offset(pd, [])


def test_recorded_trace_categories(recorded):
    _, spans, window = recorded
    out = tr.reduce(str(TRACE), spans, window, KIND)
    cats = out["category_s"]
    assert cats["gather"] > 0 and cats["fft"] > 0 and cats["other"] > 0
    assert cats["harness"] > 0
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["window_s"] == pytest.approx((window[1] - window[0]) / 1e9)
    assert sum(cats.values()) >= out["busy_s"] * 0.999
    # Every idle stretch is given to a host span or to "host"; the harness's
    # spans hold most of the window, so they get idle time of their own.
    idle = out["window_s"] - out["busy_s"]
    assert sum(out["gap_s"].values()) == pytest.approx(idle, rel=1e-6, abs=1e-9)
    assert out["gap_s"]["data"] > 0 and out["gap_s"]["solve"] > 0
    assert 0 < len(out["breakdown"]["device_ops"]) <= 10


def test_fusions_are_read_from_the_stored_hlo(recorded):
    # Every device op is found through its program id in the HLO stored
    # with the trace, fusions included; a miss raises.
    pd = recorded[0]
    modules = [xspace.hlo_module(b) for b in xspace.hlo_protos(str(TRACE)).values()]
    opcodes = {i["opcode"] for m in modules for c in m["computations"].values()
               for i in c["instructions"]}
    assert "gather" in opcodes and "fusion" in opcodes
    classes = tr.OpClasses(str(TRACE))
    ops = tr._device_ops(pd)
    found = {classes.category(pid, name) for _, name, pid, _, _ in ops}
    assert {"gather", "fft", "other", "harness"} <= found
    pid = next(p for _, _, p, _, _ in ops if classes.category(p, _op(ops, p)) != "harness")
    with pytest.raises(KeyError):
        classes.category(max(classes.by_program) + 1, "fusion")
    with pytest.raises(KeyError):
        classes.category(pid, "no-such-instruction.0")


def _op(ops, pid):
    return next(name for _, name, p, _, _ in ops if p == pid)
