"""One run of one cell: set-up, the measured window, the check, the metrics.

The window drives the entry users call, ``repro.api.Solver.solve``, closed
loop with one client: a pair (or a wave of ``batch`` pairs, as one batched
problem) starts as soon as the last one is done, until ``seconds`` have
passed and the pool's current pass is complete (``bench.data``): every run
does whole passes, the same work on every seed.
"""

from __future__ import annotations

import glob
import json
import math
import shutil
import sys
import time
from types import SimpleNamespace
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from bench import check, data, monitor, trace_reduce
from bench.registry import ROOT, Benchmark

#: Fixed paths inside the checkout: the persistent compile cache (its path
#: is part of the cache key, so it never moves) and the traced run's files.
CACHE_DIR = ROOT / ".bench_cache"
JAX_CACHE = CACHE_DIR / "jax"
TRACE_DIR = CACHE_DIR / "trace"


def configure_jax():
    """Persistent compile cache in the checkout, every program cached."""
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _phase(name: str, t0: float) -> float:
    """Time of one phase of the run, on standard error."""
    t = time.perf_counter()
    print(f"phase {name} {t - t0!r} s", file=sys.stderr, flush=True)
    return t


def _problem(api, m0, m1, batch: int):
    if batch == 1:
        return api.RegistrationProblem(m0=m0[0], m1=m1[0], name="bench-pair")
    return api.RegistrationProblem(m0=m0, m1=m1, name="bench-wave")


def _per_pair(res, batch: int) -> List[Dict]:
    """The answer of each pair of one solve: what the check compares."""
    hist = res.history or []
    if batch == 1:
        last = hist[-1] if hist else {}
        return [dict(v=res.v, m_warped=res.m_warped, detF=res.detF,
                     rel_grad=res.rel_grad, j=last.get("j", math.nan),
                     iters=int(res.iters), matvecs=int(res.matvecs),
                     mismatch_rel=float(res.mismatch_rel),
                     converged=bool(res.converged),
                     pcg=[int(h["pcg_iters"]) for h in hist],
                     ls=[int(h["ls_evals"]) for h in hist],
                     evals=len(hist), active_evals=len(hist))]
    out = []
    for b in range(batch):
        evals = [h for h in hist if bool(h["active"][b])]
        last = evals[-1] if evals else {}
        out.append(dict(
            v=res.v[b], m_warped=res.m_warped[b], detF=res.detF[b],
            rel_grad=float(res.rel_grad[b]),
            j=float(last["j"][b]) if evals else math.nan,
            iters=int(res.iters[b]), matvecs=int(res.matvecs[b]),
            mismatch_rel=float(res.mismatch_rel[b]),
            converged=bool(res.converged[b]),
            pcg=[int(h["pcg_iters"][b]) for h in evals],
            # The batch history has no line-search count; the accepted step
            # is 0.5**k after k halvings, so the trials were k + 1.
            ls=[int(round(-math.log2(float(h["alpha"][b])))) + 1
                if float(h["alpha"][b]) > 0 else 1 for h in evals],
            evals=len(hist), active_evals=len(evals)))
    return out


def _failed(p: Dict) -> bool:
    return (not p["converged"] or p["detF"]["min"] <= 0.0
            or not math.isfinite(p["mismatch_rel"])
            or not bool(jnp.all(jnp.isfinite(p["v"]))))


def run_cell(bench: Benchmark, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, grid: Optional[int] = None,
             solver_overrides: Optional[Dict] = None, device_trace: bool = True,
             log=print) -> Dict:
    from repro import api

    cell = bench.workload(workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    bounds = check.limits_for(bench.limits(workload), cfg["solver"])
    n = int(grid or cfg["grid"][0])
    batch = int(cfg["batch"])
    solver = dict(cfg["solver"], **(solver_overrides or {}))
    per_pass = data.pool_size(traffic)
    if per_pass % batch:
        raise ValueError(f"a pass of {per_pass} pairs is not whole waves of {batch}")
    waves_per_pass = per_pass // batch

    def wave_plans(k):
        return [data.pair_plan(seed, traffic, k * batch + b, n) for b in range(batch)]

    spans = monitor.Spans()
    watch = monitor.CompileWatch()
    try:
        # ---- set-up: data, then one solve at max_newton=1 on a pair that is
        # not counted, which loads or compiles every program the window runs.
        pool = data.make_pool(traffic, n, int(solver["nt"]))
        warm0, warm1 = data.materialize(
            pool, [data.warm_plan(seed, traffic, b, n) for b in range(batch)])
        warm_opts = api.SolverOptions(**dict(solver, max_newton=1))
        warm = api.Solver(warm_opts).solve(_problem(api, warm0, warm1, batch))
        jax.block_until_ready((warm.v, warm.m_warped))
        del warm, warm0, warm1
        setup_s = time.perf_counter() - t_start
        _phase("setup", t_start)

        # ---- window
        solver_obj = api.Solver(api.SolverOptions(**solver))
        trace_path = None
        profile = trace and device_trace
        if profile:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            spans.annotate = True
        waves, answers = [], []
        mark0 = watch.mark()
        t0 = time.perf_counter()
        win_start_ns = time.time_ns()
        t_last = t0
        while (time.perf_counter() - t0 < seconds
               or len(waves) % waves_per_pass):
            chunk = wave_plans(len(waves))
            mark = watch.mark()
            ts = time.perf_counter()
            with spans.span("data"):
                m0, m1 = data.materialize(pool, chunk)
                jax.block_until_ready((m0, m1))
            with spans.span("solve"):
                res = solver_obj.solve(_problem(api, m0, m1, batch))
                jax.block_until_ready((res.v, res.m_warped))
            t_last = time.perf_counter()
            pairs = _per_pair(res, batch)
            waves.append(dict(time_s=t_last - ts, plans=chunk,
                              **watch.since(mark)))
            answers.append(pairs)
            del res, m0, m1
        win_end_ns = time.time_ns()
        window_s = t_last - t0
        pipeline = watch.since(mark0)
        host_spans = spans.records + watch.spans_ns(mark0)
        if profile:
            jax.profiler.stop_trace()
            spans.annotate = False
            trace_path = sorted(glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"),
                                          recursive=True))[-1]
    finally:
        watch.close()
    t_phase = _phase("window", t0)

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    # ---- per-pair lines, then the check against the reference
    all_pairs = [p for pairs in answers for p in pairs]
    for k, (w, pairs) in enumerate(zip(waves, answers)):
        for b, (p, plan) in enumerate(zip(pairs, w["plans"])):
            log("pair " + json.dumps(dict(
                wave=k, slot=b, base=plan["base"], time_s=w["time_s"],
                iters=p["iters"], matvecs=p["matvecs"],
                mismatch_rel=p["mismatch_rel"], detF=p["detF"],
                rel_grad=p["rel_grad"], converged=p["converged"],
                compiles=w["compiles"], trace_s=w["trace_s"])))
    numbers = []
    for w, pairs in zip(waves, answers):
        m0, m1 = data.materialize(pool, w["plans"])
        for b, p in enumerate(pairs):
            numbers.append(check.pair_numbers(p, m0[b], m1[b], solver))
    compared = check.worst(numbers, bounds)
    t_phase = _phase("check", t_phase)
    correct = bool(all_pairs) and all(check.within(compared[k], bounds[k])
                                      for k in bounds)
    n_pairs = len(all_pairs)

    # What a per-layer metric's reader gets.
    run = SimpleNamespace(cell=cell, config=cfg, traffic=traffic, solver=solver,
                          grid=n, batch=batch, waves=waves, pairs=all_pairs,
                          window_s=window_s, pipeline=pipeline, device=dev,
                          trace=None, n_pairs=n_pairs)
    out = dict(correct=correct, attempted=n_pairs,
               failed=sum(_failed(p) for p in all_pairs))
    if profile:
        run.trace = trace_reduce.reduce(
            trace_path, host_spans, (win_start_ns, win_end_ns), dev.device_kind)
        _phase("trace_reduce", t_phase)
    if trace:
        metrics = {}
        for m in bench.per_layer(workload):
            val = bench.reader(m["name"])(run)
            if val is not None:
                metrics[m["name"]] = dict(value=val, unit=m["unit"])
        out["metrics"] = metrics
    else:
        e2e = dict(pair_s=window_s / n_pairs, setup_s=setup_s)
        out["metrics"] = {m["name"]: dict(value=e2e[m["name"]], unit=m["unit"])
                          for m in bench.end_to_end(workload)}
    out["device"] = dict(platform=dev.platform, kind=dev.device_kind,
                         count=jax.device_count(), memory_peak_bytes=memory_peak)
    if profile:
        out["device"].update(busy_s=run.trace["busy_s"], window_s=run.trace["window_s"])
        out["breakdown"] = run.trace["breakdown"]
    out["checks"] = {k: dict(value=compared[k], limit=next(iter(bounds[k].values())),
                             bound=next(iter(bounds[k])))
                     for k in bounds}
    return out
