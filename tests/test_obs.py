"""The program's own spans, trace counters and layer scopes (``repro.obs``),
on the CPU at 16^3 with at most two Newton evaluations per solve."""

import re
import tempfile
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, obs
from repro.core import gauss_newton as gn
from repro.core import registration as reg

GRID = (16, 16, 16)
#: The cheapest variant that runs every layer but the B-spline prefilter.
FAST = dict(variant="fd8-linear", nt=1, max_newton=2)


@pytest.fixture(scope="module")
def pair():
    return api.RegistrationProblem.synthetic(seed=0, grid=GRID)


@pytest.fixture(scope="module")
def single(pair):
    res = api.Solver(api.SolverOptions(mode="single", **FAST)).solve(pair)
    return res, obs.recent(1)[0]


@pytest.fixture(scope="module")
def batched(pair):
    prob = api.RegistrationProblem(m0=jnp.stack([pair.m0, pair.m0]),
                                   m1=jnp.stack([pair.m1, pair.m0 * 0.9 + pair.m1 * 0.1]))
    res = api.Solver(api.SolverOptions(mode="batch", **FAST)).solve(prob)
    return res, obs.recent(1)[0]


def _check_tree(rec, history, dice):
    spans = rec["spans"]
    by_id = {s["id"]: s for s in spans}
    root = spans[0]
    assert root["name"] == obs.SOLVE and root["parent"] is None
    assert root["id"] == rec["id"] == rec["solve_id"]
    assert all(s["solve_id"] == rec["id"] for s in spans)
    parents = {}
    for s in spans[1:]:
        p = by_id[s["parent"]]
        assert p["start_ns"] <= s["start_ns"] <= s["end_ns"] <= p["end_ns"]
        parents.setdefault(s["name"], set()).add(p["name"])
    assert parents[obs.NEWTON] == {obs.SOLVE}
    assert parents[obs.DISPATCH] == parents[obs.SYNC] == {obs.NEWTON}
    assert parents[obs.SCORE] == {obs.SOLVE}
    assert parents.get(obs.DICE) == ({obs.SOLVE} if dice else None)
    assert obs.SOLVE in parents[obs.BUILD]
    newton = [s for s in spans if s["name"] == obs.NEWTON]
    # One claire.newton span per evaluation, numbered in order, each with
    # one dispatch and one sync.
    assert len(newton) == len(history)
    assert [s["attrs"]["step_num"] for s in newton] == list(range(len(history)))
    for s in newton:
        kids = [c["name"] for c in spans if c["parent"] == s["id"]]
        assert kids == [obs.DISPATCH, obs.SYNC]
    return newton


def test_single_solve_span_tree(single):
    res, rec = single
    newton = _check_tree(rec, res.history, dice=True)
    assert rec["attrs"] == dict(mode="single", grid=GRID, batch=1, sharded=False)
    # wall_time_s is the Newton spans' extent.
    assert res.wall_time_s == pytest.approx(
        (newton[-1]["end_ns"] - newton[0]["start_ns"]) / 1e9)


def test_batched_solve_span_tree(batched):
    res, rec = batched
    newton = _check_tree(rec, res.history, dice=False)
    assert rec["attrs"]["mode"] == "batch" and rec["attrs"]["batch"] == 2
    assert res.wall_time_s == pytest.approx(
        (newton[-1]["end_ns"] - newton[0]["start_ns"]) / 1e9)
    # The batched history counts each pair's line-search trials.
    for h in res.history:
        assert h["ls_evals"].shape == (2,) and np.all(h["ls_evals"] >= 1)


def test_traces_counted_per_program(single, batched, pair):
    _, rec = single
    assert rec["counters"] == {"traces.newton_step": 1}
    newton = [s for s in rec["spans"] if s["name"] == obs.NEWTON]
    assert [s["counters"].get("traces.newton_step", 0) for s in newton] == [1, 0]
    assert batched[1]["counters"] == {"traces.newton_step_batch": 1}

    # A built step passed again through step_fn= traces nothing.
    cfg = reg.make_transport_config(FAST["variant"], nt=FAST["nt"])
    gcfg = gn.GNConfig(max_newton=1)
    step = gn._make_step(cfg, gcfg)
    counts = []
    for _ in range(2):
        with obs.span(obs.SOLVE):
            gn.solve(pair.m0, pair.m1, cfg, gcfg, step_fn=step)
        counts.append(obs.recent(1)[0]["counters"].get("traces.newton_step", 0))
    assert counts == [1, 0]


def _gather_paths(hlo: str):
    """Name path of every gather in an HLO text, call sites included."""
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        s = line.strip()
        if s.endswith("{") and " = " not in s:
            name = s[:-1].split()[-1]
            cur = comps.setdefault(name, [])
            entry = name if s.startswith("ENTRY") else entry
        elif s == "}":
            cur = None
        elif cur is not None and " = " in s:
            op = re.search(r'op_name="([^"]*)"', s)
            calls = re.findall(r"(?:to_apply|calls|body|condition)=%?([\w.\-]+)", s)
            cur.append((op.group(1) if op else "", " gather(" in s, calls))
    out, paths = [], []

    def walk(comp, prefix):
        for op_name, is_gather, calls in comps[comp]:
            path = prefix + "/" + op_name
            paths.append(path)
            if is_gather:
                out.append(path)
            for c in calls:
                walk(c, path)

    walk(entry, "")
    return out, paths


def test_newton_step_hlo_carries_every_scope():
    cfg = reg.make_transport_config("fd8-cubic", nt=1)
    step = gn._make_step(cfg, gn.GNConfig(max_newton=2))
    m = jnp.zeros(GRID)
    v = jnp.zeros((3,) + GRID)
    one = jnp.float32(1.0)
    hlo = step.lower(m, m, v, one, one, one).as_text(dialect="hlo", debug_info=True)
    gathers, paths = _gather_paths(hlo)
    found = {sc for p in paths for sc in re.findall(r"claire\.[a-z0-9_.]*[a-z0-9_]", p)}
    assert set(obs.SCOPES) - {obs.SCORE} <= found
    assert gathers and all("claire.interp." in p for p in gathers)
    # No component of a scope reads as the FFT primitive.
    assert all("fft" not in sc.split(".") for sc in obs.SCOPES)

    from repro.core import metrics

    def score(m0, v):
        return metrics.warp_image(m0, v, cfg), metrics.detF_stats(v, cfg)

    hlo = jax.jit(score).lower(m, v).as_text(dialect="hlo", debug_info=True)
    gathers, paths = _gather_paths(hlo)
    assert all("claire.score" in p and "claire.interp." in p for p in gathers)


def test_record_buffer_is_bounded():
    first = None
    for k in range(obs.MAX_RECORDS + 5):
        with obs.span(obs.SOLVE, k=k) as s:
            first = first or s.id
    recs = obs.recent()
    assert len(recs) == obs.MAX_RECORDS == len(obs.recent(10 * obs.MAX_RECORDS))
    assert [r["attrs"]["k"] for r in recs[-3:]] == [obs.MAX_RECORDS + 2,
                                                    obs.MAX_RECORDS + 3,
                                                    obs.MAX_RECORDS + 4]
    assert obs.recent(0) == []


def test_span_stacks_are_per_thread():
    seen = {}

    def other():
        with obs.span(obs.SOLVE, where="thread"):
            obs.count_trace("serve_scorer")
        seen["rec"] = obs.recent(1)[0]

    with obs.span(obs.SOLVE, where="main") as outer:
        t = threading.Thread(target=other)
        t.start()
        t.join()
    rec = seen["rec"]
    assert rec["parent"] is None and rec["solve_id"] != outer.id
    assert rec["counters"] == {"traces.serve_scorer": 1}
    assert obs.recent(1)[0]["attrs"] == {"where": "main"}
    assert obs.recent(1)[0]["counters"] == {}


def test_spans_land_in_the_profiler_trace(pair):
    from jax.profiler import ProfileData

    cfg = reg.make_transport_config(FAST["variant"], nt=FAST["nt"])
    gcfg = gn.GNConfig(max_newton=2)
    step = gn._make_step(cfg, gcfg)
    gn.solve(pair.m0, pair.m1, cfg, gcfg, step_fn=step)
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            with obs.span(obs.SOLVE, mode="single"):
                res = gn.solve(pair.m0, pair.m1, cfg, gcfg, step_fn=step)
                reg._score_single(pair.m0, pair.m1, res.v, cfg)
        finally:
            jax.profiler.stop_trace()
        import glob

        path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
        pd = ProfileData.from_file(path)
    rec = obs.recent(1)[0]
    found = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    sid = dict(ev.stats).get("span_id")
                    if ev.name.startswith("claire.") and sid is not None:
                        found[int(sid)] = (ev.name, int(ev.start_ns),
                                           dict(ev.stats).get("step_num"))
    assert {s["id"] for s in rec["spans"]} <= set(found)
    for s in rec["spans"]:
        assert found[s["id"]][0] == s["name"]
        assert found[s["id"]][2] == s["attrs"].get("step_num")
    offsets = [found[s["id"]][1] - s["start_ns"] for s in rec["spans"]]
    med = float(np.median(offsets))
    assert all(abs(o - med) < 1_000_000 for o in offsets)
