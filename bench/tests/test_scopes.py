"""The reduction of device time by the program's layer scopes and spans
(``bench.scopes``) and the readers built on it, on a small recorded trace.

``data/scoped_trace.xplane.pb`` was recorded by
``data/record_scoped_trace.py`` (``data/scoped_trace.json`` names the
platform): one 16^3 solve through ``repro.api.Solver.solve`` under the
harness's ``data``/``solve`` spans, with the program's solve record and the
harness's wall-clock spans, ``trace/lower`` included.
"""

import json
import pathlib
import sys
import types

import pytest
from jax.profiler import ProfileData

from bench import scopes
from bench import trace_reduce as tr
from bench.registry import Benchmark

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACE = DATA / "scoped_trace.xplane.pb"
REC = DATA / "scoped_trace.json"
NEW = ("retraces", "score_s", "newton_step_s", "matvec_ms", "fd8_share")


@pytest.fixture(scope="module")
def recorded():
    rec = json.loads(REC.read_text())
    host = [(n, int(a), int(b)) for n, a, b in rec["host_spans"]]
    return rec, host, scopes.reduce(str(TRACE), rec["records"], host)


def test_scope_paths():
    assert scopes.scopes_of("jit(step)/vmap(claire.gradient)/while/body/"
                            "claire.matvec/claire.interp.apply/jit(_take)/gather") == (
        "claire.gradient", "claire.matvec", "claire.interp.apply")
    assert scopes.scopes_of("jit(step)/jit(fft)/fft") == ()


def test_ops_without_a_scope_take_one_from_what_they_feed():
    def ins(name, opcode, op_name="", calls=(), operands=()):
        return dict(name=name, id=name, opcode=opcode, op_name=op_name, target="",
                    calls=list(calls), operands=list(operands))

    apply = "jit(step)/claire.pcg/while/body/claire.matvec/claire.interp.apply"
    mod = {"name": "m", "computations": {
        1: {"instructions": [
            ins("g", "gather", apply + "/jit(_take)/gather"),
            ins("m", "multiply", apply + "/mul"),
            ins("a", "add", "jit(step)/claire.pcg/add")]},
        3: {"instructions": [ins("c", "clamp", "gather")]},
        2: {"instructions": [
            ins("fusion.1", "fusion", "", [1]),
            ins("fusion.2", "fusion", "jit(step)/claire.fd8/add", [1]),
            # An index clamp the compiler added, named only "gather", feeds
            # a bounds assertion that feeds the gather fusion.
            ins("clamp_fusion.3", "fusion", "gather", [3]),
            ins("custom-call.4", "custom-call", "gather", operands=["clamp_fusion.3"]),
            ins("fusion.5", "fusion", "", [1], operands=["custom-call.4"]),
            # A buffer allocated for a loop's carry takes the loop's scope.
            ins("custom-call.6", "custom-call", ""),
            ins("tuple.7", "tuple", "", operands=["custom-call.6"]),
            ins("while.1", "while", "jit(step)/claire.gradient/while", [1],
                operands=["tuple.7"]),
            ins("copy.1", "copy", "")]}}}
    got = scopes._module_scopes(mod)
    assert got["fusion.1"][0] == ("claire.pcg", "claire.matvec", "claire.interp.apply")
    assert got["fusion.2"][0] == ("claire.fd8",)
    assert got["clamp_fusion.3"][0] == got["fusion.1"][0]
    assert got["custom-call.6"][0] == ("claire.gradient",)
    assert got["copy.1"][0] == ()


def test_program_clock_matches_the_harness(recorded):
    rec, host, out = recorded
    assert out["spans_matched"] == len(rec["records"][0]["spans"])
    assert out["offset_spread_ns"] < 1_000_000
    assert abs(out["offset_ns"] - out["harness_offset_ns"]) < 1_000_000
    assert out["harness_offset_ns"] == tr._clock_offset(
        ProfileData.from_file(str(TRACE)), host)


def test_scope_times_are_unions(recorded):
    _, _, out = recorded
    for sc in ("claire.gradient", "claire.pcg", "claire.matvec", "claire.precond",
               "claire.linesearch", "claire.interp.plan", "claire.interp.apply",
               "claire.fd8", "claire.spectral"):
        assert 0 < out["scope_s"][sc] <= out["busy_s"] + 1e-12, sc
    # Nesting: the matvec runs inside PCG.
    assert out["scope_s"]["claire.matvec"] <= out["scope_s"]["claire.pcg"]
    # Unions, not sums: busy time is at most the summed op time, and the
    # innermost layers partition the ops.
    assert out["busy_s"] <= out["op_s"] and out["overlap_s"] >= 0
    layers = out["layer_s"]
    assert sum(layers.values()) >= out["busy_s"] * (1 - 1e-9)
    assert all(v <= out["busy_s"] + 1e-12 for v in layers.values())
    assert out["unscoped_s"] == layers.get(scopes.UNSCOPED, 0.0) < out["busy_s"]
    assert 0 < len(out["top_ops"]) <= 10


def test_idle_time_goes_to_the_innermost_span(recorded):
    rec, _, out = recorded
    idle = out["idle_s"]
    assert sum(idle.values()) == pytest.approx(out["solve_s"] - out["busy_s"], rel=1e-6)
    names = {s["name"] for s in rec["records"][0]["spans"]}
    assert set(idle) <= names
    # The window's one compile happens in the first step's dispatch.
    assert max(idle, key=idle.get) == "claire.newton.dispatch"
    # The harness's solve gaps leave trace/lower out, so they hold less.
    gaps = out["solve_gap_s"]
    assert 0 < sum(gaps.values()) < sum(idle.values())


def _run(rec, trace=True):
    pairs = [dict(matvecs=rec["matvecs"])]
    return types.SimpleNamespace(
        waves=[{}], pairs=pairs, n_pairs=1,
        trace=dict(busy_s=0.02) if trace else None)


def test_readers_on_the_recorded_run(recorded, monkeypatch):
    rec, _, out = recorded
    monkeypatch.setattr(scopes, "window_records", lambda run: rec["records"])
    monkeypatch.setattr(scopes, "newest_trace", lambda: str(TRACE))
    bench = Benchmark()
    run = _run(rec)
    got = {m: bench.reader(m)(run) for m in NEW}
    assert got["retraces"] == 1.0
    spans = rec["records"][0]["spans"]
    score = [s for s in spans if s["name"] == "claire.score"][0]
    assert got["score_s"] == (score["end_ns"] - score["start_ns"]) / 1e9
    warm = [s for s in spans if s["name"] == "claire.newton" and not s["counters"]]
    assert got["newton_step_s"] == pytest.approx(
        sum(s["end_ns"] - s["start_ns"] for s in warm) / len(warm) / 1e9)
    assert got["matvec_ms"] == pytest.approx(
        1e3 * out["scope_s"]["claire.matvec"] / rec["matvecs"])
    assert got["fd8_share"] == pytest.approx(100 * out["scope_s"]["claire.fd8"] / 0.02)
    # Without a trace the device readers have nothing to read.
    untraced = _run(rec, trace=False)
    assert bench.reader("matvec_ms")(untraced) is None
    assert bench.reader("fd8_share")(untraced) is None


def test_readers_read_nothing_without_program_spans(monkeypatch):
    # A program without repro.obs (an older checkout): every new reader
    # returns None and none raises.
    import repro

    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs", raising=False)
    run = types.SimpleNamespace(waves=[{}], pairs=[dict(matvecs=3)], n_pairs=1,
                                trace=dict(busy_s=1.0))
    assert scopes.window_records(run) is None
    bench = Benchmark()
    assert all(bench.reader(m)(run) is None for m in NEW)


def test_window_records_are_the_last_solves():
    from repro import obs

    for k in range(3):
        with obs.span(obs.SOLVE, k=k):
            pass
    run = types.SimpleNamespace(waves=[{}, {}])
    assert [r["attrs"]["k"] for r in scopes.window_records(run)] == [1, 2]
