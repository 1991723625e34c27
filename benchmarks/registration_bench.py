"""Paper Table 7: full registration runs across solver variants.

For each variant (cpu-fft-cubic analogue, fd8-cubic, fd8-linear) we report
det F (min/mean/max), Dice before/after, relative mismatch, relative
gradient, GN iterations, Hessian matvecs, wall time. The paper's claims to
reproduce: (i) iteration counts / quality metrics are (nearly) invariant
across variants, (ii) fd8 variants are faster, (iii) det F stays in the
healthy band, (iv) Dice improves substantially.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

if __package__ in (None, ""):  # direct `python benchmarks/registration_bench.py`
    _root = pathlib.Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(_root))
    sys.path.insert(0, str(_root / "src"))

import jax
import jax.numpy as jnp

from repro.core import metrics as M
from repro.core.registration import register, register_batch, register_multires
from repro.data import synthetic
from repro.launch.compile_cache import enable_compile_cache
from benchmarks.common import fmt, print_table

VARIANTS = ["fft-cubic", "fd8-cubic", "fd8-linear"]

RESULTS_DIR = pathlib.Path(__file__).resolve().parent.parent / "results"


def run(n: int = 32, max_newton: int = 10, seeds=(0,)):
    rows = []
    for seed in seeds:
        pair = synthetic.make_pair(jax.random.PRNGKey(seed), (n, n, n),
                                   amplitude=0.5)
        dice_before = float(M.dice(pair.labels0, pair.labels1))
        for variant in VARIANTS:
            res = register(pair.m0, pair.m1, variant=variant,
                           max_newton=max_newton)
            cfg_interp = {"fft-cubic": "cubic_lagrange",
                          "fd8-cubic": "cubic_bspline",
                          "fd8-linear": "linear"}[variant]
            from repro.core import transport as T
            tcfg = T.TransportConfig(interp=cfg_interp,
                                     deriv=variant.split("-")[0])
            warped_labels = M.warp_labels(pair.labels0, res.v, tcfg)
            dice_after = float(M.dice(warped_labels, pair.labels1))
            rows.append([
                f"{n}^3", variant,
                fmt(res.detF["min"], 2), fmt(res.detF["mean"], 2),
                fmt(res.detF["max"], 2),
                fmt(dice_before, 2), fmt(dice_after, 2),
                fmt(res.mismatch_rel), fmt(res.rel_grad),
                res.iters, res.matvecs, fmt(res.wall_time_s, 1)])
    print_table(
        f"Table 7 analogue: registration variants at {n}^3 (synthetic pair, "
        "CPU; paper invariance claim: quality ~constant across variants)",
        ["N", "variant", "detF min", "mean", "max", "dice pre", "dice post",
         "mismatch", "|g|rel", "iters", "matvecs", "time s"],
        rows)
    # invariance claim: iterations within +-3 across variants
    iters = [r[9] for r in rows]
    assert max(iters) - min(iters) <= 4
    return rows



# ---------------------------------------------------------------------------
# Solve-strategy comparison: single-level vs multi-resolution vs batched.
# Records the acceptance numbers for the multires/batch pipeline into
# results/BENCH_api_smoke.json (appending entries of the same schema).
# ---------------------------------------------------------------------------


def _append_json(path: pathlib.Path, entry: dict):
    path.parent.mkdir(parents=True, exist_ok=True)
    entries = []
    if path.exists():
        try:
            entries = json.loads(path.read_text())
        except (ValueError, OSError):
            entries = None
        if not isinstance(entries, list):
            # keep the unusable history aside instead of overwriting it
            backup = path.with_suffix(path.suffix + ".corrupt")
            path.replace(backup)
            print(f"[bench] WARNING: {path} was unusable; moved to {backup}")
            entries = []
    entries.append(entry)
    path.write_text(json.dumps(entries, indent=2))


def run_modes(
    n: int = 16,
    max_newton: int = 20,
    variant: str = "fd8-cubic",
    seed: int = 7,
    out: str = "BENCH_api_smoke.json",
):
    """Single vs multires vs batch on one synthetic problem.

    Claims checked (the multires/batch pipeline acceptance):
      * multires reaches the single-level mismatch (+-5%) with strictly
        fewer fine-grid Newton iterations;
      * batched registration matches the per-pair single results to 1e-5.
    """
    grid = (n, n, n)
    key = jax.random.PRNGKey(seed)
    pair = synthetic.make_pair(key, grid, amplitude=0.5)

    single = register(pair.m0, pair.m1, variant=variant, max_newton=max_newton)
    multires = register_multires(pair.m0, pair.m1, variant=variant,
                                 max_newton=max_newton)

    # batch: pair 0 = the same problem, pair 1 = the reverse registration.
    m0b = jnp.stack([pair.m0, pair.m1])
    m1b = jnp.stack([pair.m1, pair.m0])
    batched = register_batch(m0b, m1b, variant=variant, max_newton=max_newton)
    single_rev = register(pair.m1, pair.m0, variant=variant,
                          max_newton=max_newton)

    rows = [
        ["single", f"{n}^3", single.iters, single.iters, single.matvecs,
         fmt(single.mismatch_rel), fmt(single.rel_grad),
         fmt(single.wall_time_s, 1)],
        ["multires", "->".join(str(s[0]) for s in multires.levels),
         multires.iters, multires.fine_iters, multires.matvecs,
         fmt(multires.mismatch_rel), fmt(multires.rel_grad),
         fmt(multires.wall_time_s, 1)],
        ["batch[0]", f"{n}^3", batched.iters[0], batched.iters[0],
         batched.matvecs[0], fmt(batched.mismatch_rel[0]),
         fmt(batched.rel_grad[0]), fmt(batched.wall_time_s, 1)],
        ["batch[1]", f"{n}^3", batched.iters[1], batched.iters[1],
         batched.matvecs[1], fmt(batched.mismatch_rel[1]),
         fmt(batched.rel_grad[1]), fmt(batched.wall_time_s, 1)],
    ]
    print_table(
        f"Solve strategies at {n}^3 (variant {variant}): grid continuation "
        "cuts fine-grid Newton iterations; batching matches per-pair results",
        ["mode", "grid(s)", "iters", "fine iters", "matvecs", "mismatch",
         "|g|rel", "time s"],
        rows)

    entry = dict(
        ts=time.time(),
        host_devices=jax.device_count(),
        single=dict(
            grid=list(grid),
            iters=single.iters,
            matvecs=single.matvecs,
            mismatch_rel=single.mismatch_rel,
            rel_grad=single.rel_grad,
            wall_time_s=single.wall_time_s,
        ),
        multires=dict(
            grid=list(grid),
            levels=[list(s) for s in multires.levels],
            iters=multires.iters,
            fine_iters=multires.fine_iters,
            matvecs=multires.matvecs,
            mismatch_rel=multires.mismatch_rel,
            rel_grad=multires.rel_grad,
            wall_time_s=multires.wall_time_s,
        ),
        batch=dict(
            grid=list(grid),
            batch=int(m0b.shape[0]),
            iters=batched.iters,
            matvecs=batched.matvecs,
            mismatch_rel=batched.mismatch_rel,
            single_mismatch_rel=[single.mismatch_rel, single_rev.mismatch_rel],
            max_abs_delta=max(
                abs(batched.mismatch_rel[0] - single.mismatch_rel),
                abs(batched.mismatch_rel[1] - single_rev.mismatch_rel),
            ),
            wall_time_s=batched.wall_time_s,
        ),
    )
    _append_json(RESULTS_DIR / out, entry)
    print(f"[bench] appended entry to {RESULTS_DIR / out}")

    # acceptance claims
    assert multires.fine_iters < single.iters, (
        f"multires fine iters {multires.fine_iters} !< single {single.iters}")
    assert multires.mismatch_rel <= single.mismatch_rel * 1.05, (
        f"multires mismatch {multires.mismatch_rel} worse than "
        f"single {single.mismatch_rel} (+5%)")
    assert entry["batch"]["max_abs_delta"] < 1e-5, (
        f"batch/single mismatch delta {entry['batch']['max_abs_delta']}")
    return entry


# ---------------------------------------------------------------------------
# Hessian-matvec microbenchmark: the build-once/apply-many claim.
#
# Per Newton step the solver evaluates one gradient (which *builds* the
# per-step invariants: footpoints, interpolation plans, grad(m_traj), div v)
# and then spends up to ``max_pcg`` Hessian matvecs that only *apply* them.
# This mode measures the per-matvec wall time with plans on vs off (and fp32
# vs bf16 weights, jnp vs pallas backend) — the paper's Table 1 amortization,
# demonstrated rather than asserted.
# ---------------------------------------------------------------------------


def run_matvec(
    n: int = 16,
    iters: int = 20,
    seed: int = 7,
    backends=("jnp",),
    out: str = "BENCH_matvec.json",
):
    import numpy as np

    from repro.core import gradient as GR
    from repro.core import hessian as HS
    from repro.core import transport as T
    from repro.data import synthetic as S

    grid = (n, n, n)
    pair = synthetic.make_pair(jax.random.PRNGKey(seed), grid, amplitude=0.5)
    v = 0.3 * S.random_velocity(jax.random.PRNGKey(seed + 1), grid)
    vt = S.random_velocity(jax.random.PRNGKey(seed + 2), grid, amplitude=0.2)
    beta, gamma = 5e-4, 1e-4

    cases = []
    for backend in backends:
        for wd_name, wd in (("fp32", None), ("bf16", jnp.bfloat16)):
            # plan-free first: it is the reference the deviations are
            # measured against.
            for use_plan in (False, True):
                cases.append(dict(
                    backend=backend, weights=wd_name, use_plan=use_plan,
                    fused=False,
                    cfg=T.TransportConfig(interp="cubic_bspline", deriv="fd8",
                                          nt=4, backend=backend,
                                          weight_dtype=wd, use_plan=use_plan),
                ))
            # fused gather+epilogue Pallas kernel: the PCG hot-loop path
            # (one HBM pass per transport step instead of three).
            cases.append(dict(
                backend=backend, weights=wd_name, use_plan=True, fused=True,
                cfg=T.TransportConfig(interp="cubic_bspline", deriv="fd8",
                                      nt=4, backend=backend, weight_dtype=wd,
                                      use_plan=True, use_fused_matvec=True),
            ))

    # Reference answer for the deviation column: the plan-free jnp/fp32
    # matvec, computed up front so every case (any --backends order/subset)
    # is measured against it.
    cfg_ref = T.TransportConfig(interp="cubic_bspline", deriv="fd8", nt=4,
                                use_plan=False)
    gs_ref = jax.jit(
        lambda m0, m1, v: GR.evaluate(m0, m1, v, beta, gamma, cfg_ref)
    )(pair.m0, pair.m1, v)
    ref_hv = jax.jit(
        lambda vt, gs, v: HS.matvec(vt, gs, v, beta, gamma, cfg_ref)
    )(vt, gs_ref, v)

    rows, records = [], []
    for case in cases:
        cfg = case["cfg"]

        # Per-Newton-step setup: one gradient evaluation builds the plans,
        # grad(m_traj) and div v that every matvec below reuses.
        ev = jax.jit(lambda m0, m1, v: GR.evaluate(m0, m1, v, beta, gamma, cfg))
        gs = jax.block_until_ready(ev(pair.m0, pair.m1, v))
        t0 = time.perf_counter()
        gs = jax.block_until_ready(ev(pair.m0, pair.m1, v))
        evaluate_ms = (time.perf_counter() - t0) * 1e3

        mv = jax.jit(lambda vt, gs, v: HS.matvec(vt, gs, v, beta, gamma, cfg))
        hv = jax.block_until_ready(mv(vt, gs, v))  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            hv = mv(vt, gs, v)
        jax.block_until_ready(hv)
        per_matvec_ms = (time.perf_counter() - t0) * 1e3 / iters

        max_dev = float(jnp.max(jnp.abs(hv - ref_hv)))
        rec = dict(
            backend=case["backend"], weights=case["weights"],
            use_plan=case["use_plan"], fused=case["fused"],
            per_matvec_ms=per_matvec_ms, evaluate_ms=evaluate_ms,
            max_abs_dev_vs_plan_free_fp32=max_dev,
        )
        records.append(rec)
        rows.append([
            case["backend"], case["weights"],
            "fused" if case["fused"] else
            ("plan" if case["use_plan"] else "no-plan"),
            fmt(per_matvec_ms, 2), fmt(evaluate_ms, 2), fmt(max_dev),
        ])

    print_table(
        f"Hessian matvec at {n}^3 (cubic B-spline, Nt=4): build-once plans + "
        "cached grad(m_traj) vs per-matvec recomputation",
        ["backend", "weights", "mode", "matvec ms", "eval ms", "|dev|"],
        rows)

    def _ms(backend, weights, use_plan, fused=False):
        for r in records:
            if (r["backend"], r["weights"], r["use_plan"],
                    r["fused"]) == (backend, weights, use_plan, fused):
                return r["per_matvec_ms"]
        return None

    speedup = None
    on, off = _ms("jnp", "fp32", True), _ms("jnp", "fp32", False)
    if on and off:
        speedup = off / on
        print(f"[bench] plan speedup (jnp fp32, {n}^3): {speedup:.2f}x "
              f"({off:.2f} ms -> {on:.2f} ms per matvec)")

    fused_speedup = None
    fused_ms = _ms("jnp", "fp32", True, fused=True)
    if fused_ms and on:
        fused_speedup = on / fused_ms
        print(f"[bench] fused-kernel speedup vs plan-apply (jnp fp32, "
              f"{n}^3): {fused_speedup:.2f}x "
              f"({on:.2f} ms -> {fused_ms:.2f} ms per matvec)")

    entry = dict(
        ts=time.time(),
        grid=list(grid),
        nt=4,
        iters=iters,
        host_devices=jax.device_count(),
        results=records,
        plan_speedup_jnp_fp32=speedup,
        fused_speedup_vs_plan_jnp_fp32=fused_speedup,
    )
    _append_json(RESULTS_DIR / out, entry)
    print(f"[bench] appended entry to {RESULTS_DIR / out}")

    # acceptance: plan-based matvec strictly faster than plan-free at >= 16^3
    if n >= 16 and speedup is not None:
        assert speedup > 1.0, (
            f"plan-based matvec not faster at {n}^3: {speedup:.2f}x")
    # acceptance: the fused Pallas matvec beats the plan-apply path by >=
    # 1.3x at 24^3 (the speed-campaign floor; measured ~2x).
    if n >= 24 and fused_speedup is not None:
        assert fused_speedup >= 1.3, (
            f"fused matvec below 1.3x at {n}^3: {fused_speedup:.2f}x")
    return entry


# ---------------------------------------------------------------------------
# Distributed (slab-parallel) Newton step: collective-bytes accounting.
#
# The §Perf claim of the sharded pipeline: the hand-written halo path
# (shard_map with ring halo exchanges for FD8 + SL interpolation, all-gather
# only for the spectral operators) moves strictly fewer collective bytes per
# Newton step than letting GSPMD propagate the slab sharding through the
# same step body (which falls back to all-gathering the interpolation
# sources and rolls). Measured from the optimized post-SPMD HLO with the
# roofline walker; recorded into results/BENCH_dist.json.
# ---------------------------------------------------------------------------


def run_dist(
    n: int = 24,
    devices: int = 8,
    halo: int = 6,
    variant: str = "fd8-cubic",
    seed: int = 7,
    timing_iters: int = 3,
    out: str = "BENCH_dist.json",
):
    from repro.launch import hostenv

    if hostenv.ensure_host_devices(devices):
        return None

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import gauss_newton as GN
    from repro.core.registration import make_transport_config
    from repro.distributed import claire_dist as D
    from repro.launch.mesh import make_mesh
    from repro.roofline import collective_bytes

    grid = (n, n, n)
    mesh = make_mesh((devices,), ("slab",))
    pair = synthetic.make_pair(jax.random.PRNGKey(seed), grid, amplitude=0.4)
    cfg = make_transport_config(variant)
    gn = GN.GNConfig()
    img_sh, vel_sh = D.slab_solve_shardings(mesh, "slab")
    sc_sh = NamedSharding(mesh, P())
    m0 = jax.device_put(pair.m0, img_sh)
    m1 = jax.device_put(pair.m1, img_sh)
    v = jax.device_put(jnp.zeros((3,) + grid, jnp.float32), vel_sh)
    step_args = (m0, m1, v, jnp.float32(5e-4), jnp.float32(1e-4),
                 jnp.float32(0.5))

    def measure(step, label):
        compiled = step.lower(*step_args).compile()
        bytes_, by_kind = collective_bytes(compiled.as_text())
        stats = jax.block_until_ready(compiled(*step_args))  # warm
        t0 = time.perf_counter()
        for _ in range(timing_iters):
            stats = compiled(*step_args)
        jax.block_until_ready(stats)
        ms = (time.perf_counter() - t0) * 1e3 / timing_iters
        print(f"[bench] {label}: {bytes_ / 1e6:.2f} MB collectives/step, "
              f"{ms:.0f} ms/step, kinds={ {k: round(b / 1e6, 2) for k, b in by_kind.items()} }")
        return stats, dict(collective_bytes=bytes_, by_kind=by_kind,
                           step_ms=ms)

    halo_step = D.make_slab_step(mesh, cfg, gn, "slab", halo)
    halo_stats, halo_rec = measure(halo_step, f"halo (shard_map, halo={halo})")

    # GSPMD fallback: the *same* step body, sharded inputs, no shard_map —
    # the partitioner inserts the collectives (all-gathers for the
    # interpolation gathers and FFTs, halo collective-permutes for rolls).
    gspmd_step = jax.jit(
        GN._build_step(cfg, gn),
        in_shardings=(img_sh, img_sh, vel_sh, sc_sh, sc_sh, sc_sh))
    gspmd_stats, gspmd_rec = measure(gspmd_step, "gspmd fallback")

    # int8 halo compression: identical shard_map step with quantized halo
    # payloads on the wire (remote halo rows lossy, owned interior exact).
    int8_step = D.make_slab_step(mesh, cfg, gn, "slab", halo, compress="int8")
    int8_stats, int8_rec = measure(int8_step, f"halo + int8 wire (halo={halo})")

    dv = float(jnp.max(jnp.abs(halo_stats.v_new - gspmd_stats.v_new)))
    dv8 = float(jnp.max(jnp.abs(halo_stats.v_new - int8_stats.v_new)))
    ratio = halo_rec["collective_bytes"] / max(gspmd_rec["collective_bytes"], 1.0)
    int8_saving = 1.0 - (int8_rec["collective_bytes"]
                         / max(halo_rec["collective_bytes"], 1.0))
    print_table(
        f"Slab-parallel Newton step at {n}^3 on {devices} devices "
        f"({variant}): explicit halo exchange vs GSPMD all-gather fallback",
        ["path", "coll MB/step", "ms/step", "max |dv| vs halo"],
        [["halo", fmt(halo_rec["collective_bytes"] / 1e6, 2),
          fmt(halo_rec["step_ms"], 0), "0"],
         ["halo+int8", fmt(int8_rec["collective_bytes"] / 1e6, 2),
          fmt(int8_rec["step_ms"], 0), fmt(dv8)],
         ["gspmd", fmt(gspmd_rec["collective_bytes"] / 1e6, 2),
          fmt(gspmd_rec["step_ms"], 0), fmt(dv)]])

    entry = dict(
        ts=time.time(),
        grid=list(grid),
        devices=devices,
        halo=halo,
        variant=variant,
        halo_path=halo_rec,
        halo_int8=int8_rec,
        gspmd_fallback=gspmd_rec,
        collective_bytes_ratio=ratio,
        int8_collective_saving=int8_saving,
        max_abs_dv=dv,
        max_abs_dv_int8=dv8,
    )
    _append_json(RESULTS_DIR / out, entry)
    print(f"[bench] appended entry to {RESULTS_DIR / out}")

    # acceptance: the halo path moves fewer collective bytes than GSPMD,
    # int8 compression moves fewer still, and both agree numerically (exact
    # path to fp32 reduction noise; int8 to quantization noise).
    assert halo_rec["collective_bytes"] < gspmd_rec["collective_bytes"], (
        f"halo path not cheaper: {halo_rec['collective_bytes']:.3e} >= "
        f"{gspmd_rec['collective_bytes']:.3e}")
    assert int8_rec["collective_bytes"] < halo_rec["collective_bytes"], (
        f"int8 wire not cheaper: {int8_rec['collective_bytes']:.3e} >= "
        f"{halo_rec['collective_bytes']:.3e}")
    assert dv < 1e-3, dv
    assert dv8 < 5e-2, dv8
    return entry


# ---------------------------------------------------------------------------
# Serving SLO benchmark: the registration server under mixed traffic.
#
# Drives `repro.serve.Server` with a mixed-grid longitudinal request stream
# under two arrival patterns — closed-loop burst (everything at t=0: peak
# dynamic-batching utilization, throughput-bound) and open-loop Poisson
# (latency includes batching wait; utilization < 1 under trickle traffic) —
# and records p50/p99 latency, pairs/sec, wave utilization, and warm-vs-cold
# Newton iteration counts into results/BENCH_serve.json. The warm-start
# claim: repeat-subject (longitudinal) requests, started from the cached
# prior velocity with the cold gradient norm as stopping reference, converge
# in fewer Newton iterations than their cold first visits.
# ---------------------------------------------------------------------------


def _phase_stats(results, wall_s):
    from repro.serve import percentile

    lat = [r.latency_s for r in results]
    warm = [r.iters for r in results if r.warm_started]
    cold = [r.iters for r in results if not r.warm_started]
    mean = lambda xs: (sum(xs) / len(xs)) if xs else None
    return dict(
        n=len(results),
        converged=sum(1 for r in results if r.converged),
        warm=len(warm),
        cold=len(cold),
        latency_p50_s=percentile(lat, 50),
        latency_p99_s=percentile(lat, 99),
        latency_mean_s=mean(lat),
        queue_mean_s=mean([r.queue_s for r in results]),
        pairs_per_sec=len(results) / max(wall_s, 1e-9),
        iters_mean_warm=mean(warm),
        iters_mean_cold=mean(cold),
        wall_s=wall_s,
    )


def run_serve(
    smoke: bool = False,
    grids=(16, 24),
    subjects: int = 4,
    max_batch: int = 2,
    max_wait_s: float = 0.25,
    max_newton: int = 4,
    tol: float = 0.25,
    rate: float = 0.5,
    open_loop_requests: int = None,
    variant: str = "fd8-cubic",
    seed: int = 7,
    out: str = "BENCH_serve.json",
):
    """Three phases against one server (one warm-start cache):

      1. closed-loop cold burst  — every subject's first visit at t=0;
      2. closed-loop warm burst  — every subject's second visit (all warm);
      3. open-loop Poisson       — revisit stream at ``rate`` req/s (skipped
                                   with --smoke unless it is short).
    """
    import tempfile

    from repro.launch.serve_registration import (poisson_delays, serve_stream,
                                                 synthetic_study)
    from repro.serve import ServeConfig, Server

    grid_shapes = [(g, g, g) for g in grids]
    n_open = open_loop_requests if open_loop_requests is not None else \
        (subjects if smoke else 3 * subjects)
    # Two visits per subject up front (cold burst + warm burst), then the
    # open-loop phase keeps revisiting (third+ visits, all warm).
    requests = synthetic_study(grid_shapes, 2 * subjects + n_open, subjects,
                               seed=seed, variant=variant)
    cold_burst = requests[:subjects]
    warm_burst = requests[subjects:2 * subjects]
    open_reqs = requests[2 * subjects:]

    cache_dir = tempfile.mkdtemp(prefix="serve_bench_cache_")
    # ``tol`` is sized so the cold bursts *converge* below max_newton at
    # smoke grids — a capped cold solve would make warm-vs-cold vacuous.
    cfg = ServeConfig(max_batch=max_batch, max_wait_s=max_wait_s,
                      max_newton=max_newton, tol_rel_grad=tol,
                      cache_dir=cache_dir)

    phases = {}
    with Server(cfg) as server:
        t0 = time.perf_counter()
        res_cold = serve_stream(server, cold_burst)
        phases["burst_cold"] = _phase_stats(res_cold,
                                            time.perf_counter() - t0)
        t0 = time.perf_counter()
        res_warm = serve_stream(server, warm_burst)
        phases["burst_warm"] = _phase_stats(res_warm,
                                            time.perf_counter() - t0)
        if open_reqs:
            delays = poisson_delays(len(open_reqs), rate, seed=seed)
            t0 = time.perf_counter()
            res_open = serve_stream(server, open_reqs, delays)
            phases["open_loop_poisson"] = _phase_stats(
                res_open, time.perf_counter() - t0)
            phases["open_loop_poisson"]["rate_req_s"] = rate
        summary = server.summary()

    all_results = res_cold + res_warm + (res_open if open_reqs else [])
    rows = []
    for name, p in phases.items():
        rows.append([
            name, p["n"], fmt(p["latency_p50_s"], 2), fmt(p["latency_p99_s"], 2),
            fmt(p["pairs_per_sec"], 2),
            fmt(p["iters_mean_cold"], 1) if p["iters_mean_cold"] is not None else "-",
            fmt(p["iters_mean_warm"], 1) if p["iters_mean_warm"] is not None else "-",
        ])
    print_table(
        f"Registration serving SLOs (grids {list(grids)}, {subjects} subjects, "
        f"max_batch={max_batch}, variant {variant}): dynamic batching + "
        "warm-start cache",
        ["phase", "n", "p50 s", "p99 s", "pairs/s", "cold iters", "warm iters"],
        rows)
    print(f"[bench] waves: {summary['waves']}, mean utilization "
          f"{summary['utilization_mean']:.2f}, warm hits {summary['warm_hits']}")

    entry = dict(
        ts=time.time(),
        smoke=smoke,
        host_devices=jax.device_count(),
        grids=[list(g) for g in grid_shapes],
        subjects=subjects,
        max_batch=max_batch,
        max_wait_s=max_wait_s,
        max_newton=max_newton,
        tol_rel_grad=tol,
        variant=variant,
        phases=phases,
        server=summary,
        per_request=[dict(r.to_dict(), v=None) for r in all_results],
    )
    _append_json(RESULTS_DIR / out, entry)
    print(f"[bench] appended entry to {RESULTS_DIR / out}")

    # acceptance: every request completed; the stream mixed grids; warm
    # repeat-subject solves took fewer Newton iterations than cold starts.
    n_expected = 2 * subjects + len(open_reqs)
    assert summary["completed"] == n_expected, (
        f"{summary['completed']}/{n_expected} requests completed")
    assert len({r.grid for r in all_results}) >= min(len(grid_shapes), 2), (
        "request stream did not mix grids")
    cold_iters = phases["burst_cold"]["iters_mean_cold"]
    warm_iters = phases["burst_warm"]["iters_mean_warm"]
    assert warm_iters is not None and cold_iters is not None
    assert warm_iters < cold_iters, (
        f"warm-start mean iters {warm_iters} !< cold {cold_iters}")
    return entry


# ---------------------------------------------------------------------------
# Distance-measure benchmark: SSD vs NCC vs NGF, uni- and multi-modal.
#
# Two scenarios per measure: the same-modality pair (SSD's home turf — every
# measure should register it) and the contrast-inverted pair (the multi-modal
# scenario SSD cannot handle). Dice on the geometric label masks is the
# modality-independent quality metric; mismatch_rel stays the L2 number and
# is reported for SSD context only. Records results/BENCH_measures.json.
# ---------------------------------------------------------------------------


def run_measures(
    smoke: bool = False,
    n: int = None,
    max_newton: int = None,
    variant: str = None,
    seed: int = 5,
    measures=("ssd", "ncc", "ngf"),
    out: str = "BENCH_measures.json",
):
    from repro.core import transport as T

    n = n or (12 if smoke else 16)
    max_newton = max_newton or (8 if smoke else 12)
    variant = variant or ("fd8-linear" if smoke else "fd8-cubic")
    nt = 2 if smoke else 4
    grid = (n, n, n)
    key = jax.random.PRNGKey(seed)
    scenarios = [
        ("same-modality", synthetic.make_pair(key, grid, amplitude=0.6,
                                              nt=nt)),
        ("inverted", synthetic.make_multimodal_pair(key, grid, amplitude=0.6,
                                                    nt=nt, mode="inverted")),
    ]
    interp = {"fft-cubic": "cubic_lagrange", "fd8-cubic": "cubic_bspline",
              "fd8-linear": "linear"}[variant]
    lbl_cfg = T.TransportConfig(interp=interp, deriv=variant.split("-")[0],
                                nt=nt)

    rows, records = [], []
    for scen_name, pair in scenarios:
        dice_before = float(M.dice(pair.labels0, pair.labels1))
        for meas in measures:
            t0 = time.perf_counter()
            res = register(pair.m0, pair.m1, variant=variant, nt=nt,
                           max_newton=max_newton, measure=meas)
            wall = time.perf_counter() - t0
            warped = M.warp_labels(pair.labels0, res.v, lbl_cfg)
            dice_after = float(M.dice(warped, pair.labels1))
            rec = dict(
                scenario=scen_name, measure=meas, converged=res.converged,
                iters=res.iters, matvecs=res.matvecs,
                dice_before=dice_before, dice_after=dice_after,
                mismatch_rel=res.mismatch_rel, rel_grad=res.rel_grad,
                detF_min=res.detF["min"], wall_time_s=wall,
            )
            records.append(rec)
            rows.append([
                scen_name, meas, str(res.converged), res.iters, res.matvecs,
                fmt(dice_before, 2), fmt(dice_after, 2),
                fmt(res.mismatch_rel), fmt(res.detF["min"], 2), fmt(wall, 1)])
    print_table(
        f"Distance measures at {n}^3 ({variant}, Nt={nt}): SSD vs NCC vs NGF "
        "on same-modality and contrast-inverted pairs (Dice is the "
        "modality-independent referee)",
        ["scenario", "measure", "conv", "iters", "matvecs", "dice pre",
         "dice post", "mismatch", "detF min", "time s"],
        rows)

    entry = dict(
        ts=time.time(),
        smoke=smoke,
        grid=list(grid),
        variant=variant,
        nt=nt,
        max_newton=max_newton,
        seed=seed,
        host_devices=jax.device_count(),
        results=records,
    )
    _append_json(RESULTS_DIR / out, entry)
    print(f"[bench] appended entry to {RESULTS_DIR / out}")

    # acceptance: every measure registers the same-modality pair (Dice
    # improves); on the inverted pair SSD fails (Dice drops) while some
    # intensity-invariant measure recovers the geometry.
    by = {(r["scenario"], r["measure"]): r for r in records}
    for meas in measures:
        r = by[("same-modality", meas)]
        assert r["dice_after"] > r["dice_before"], (
            f"{meas} failed on same-modality pair: "
            f"{r['dice_before']:.3f} -> {r['dice_after']:.3f}")
    if "ssd" in measures:
        r = by[("inverted", "ssd")]
        assert not (r["dice_after"] >= r["dice_before"]), (
            "SSD unexpectedly registered the inverted pair")
    inv_best = max((by[("inverted", m)] for m in measures if m != "ssd"),
                   key=lambda r: r["dice_after"], default=None)
    if inv_best is not None:
        assert inv_best["dice_after"] > inv_best["dice_before"] + 0.05, (
            f"no intensity-invariant measure recovered the inverted pair "
            f"(best {inv_best['measure']}: {inv_best['dice_after']:.3f})")
    return entry


# ---------------------------------------------------------------------------
# Roofline mode: per-kernel achieved-vs-roofline fractions + collective bytes.
#
# Jits each hot kernel of the solve (interp plan-apply, FD8 gradient, fused
# PCG matvec, full Newton step), walks the compiled HLO with the trip-count-
# aware cost model (repro.roofline.hlo), and records flops / HBM bytes /
# collective bytes, the no-overlap roofline time bound under the TPU v5e
# constants, and the achieved fraction (bound / measured wall time) into
# results/BENCH_roofline.json. With forced host devices it also isolates the
# sharded matvec's collective bytes (eval+matvec minus eval alone) and
# checks them against the checked-in results/roofline_baseline.json — a >20%
# regression fails the run (and CI).
# ---------------------------------------------------------------------------


def run_roofline(
    n: int = 64,
    devices: int = 8,
    halo: int = 6,
    variant: str = "fd8-cubic",
    seed: int = 7,
    timing_iters: int = 3,
    smoke: bool = False,
    out: str = "BENCH_roofline.json",
):
    from repro.launch import hostenv

    if smoke:
        n, devices, timing_iters = min(n, 24), min(devices, 2), 2
    if n >= 256 and jax.default_backend() == "cpu":
        # 256^3 fields (16 GiB of fp32 trajectories per solve) need a real
        # accelerator; CPU host runs clamp to the largest feasible grid.
        print("[bench] 256^3 roofline on the CPU host; clamping to 128^3")
        n = 128
    if hostenv.ensure_host_devices(devices):
        return None

    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import derivatives as DV
    from repro.core import gauss_newton as GN
    from repro.core import gradient as GR
    from repro.core import hessian as HS
    from repro.core import interp as I
    from repro.core.registration import make_transport_config
    from repro.data import synthetic as S
    from repro.distributed import halo as H
    from repro.roofline import analyze_hlo, achieved_fraction, kernel_roofline

    grid = (n, n, n)
    pair = synthetic.make_pair(jax.random.PRNGKey(seed), grid, amplitude=0.5)
    v = 0.3 * S.random_velocity(jax.random.PRNGKey(seed + 1), grid)
    vt = S.random_velocity(jax.random.PRNGKey(seed + 2), grid, amplitude=0.2)
    beta, gamma = 5e-4, 1e-4
    cfg = make_transport_config(variant, nt=4)
    cfg_fused = make_transport_config(variant, nt=4, use_fused_matvec=True)
    gn = GN.GNConfig()

    def measure(fn, args, label):
        compiled = jax.jit(fn).lower(*args).compile()
        costs = analyze_hlo(compiled.as_text())
        res = jax.block_until_ready(compiled(*args))  # warm
        t0 = time.perf_counter()
        for _ in range(timing_iters):
            res = compiled(*args)
        jax.block_until_ready(res)
        measured_s = (time.perf_counter() - t0) / timing_iters
        # stencil/gather kernels are elementwise-dominated: their compute
        # term is dot FLOPs + 1-per-element float arithmetic
        flops = costs.flops + costs.ew_flops
        kr = kernel_roofline(flops, costs.mem_bytes, costs.coll_bytes)
        rec = dict(
            flops=flops, dot_flops=costs.flops, ew_flops=costs.ew_flops,
            mem_bytes=costs.mem_bytes,
            collective_bytes=costs.coll_bytes, intensity=kr.intensity,
            bound=kr.bound, roofline_s=kr.roofline_s, measured_s=measured_s,
            achieved_fraction=achieved_fraction(kr.roofline_s, measured_s),
        )
        print(f"[bench] {label}: {flops / 1e9:.3f} GFLOP, "
              f"{costs.mem_bytes / 1e6:.1f} MB, {kr.bound}-bound, "
              f"roofline {kr.roofline_s * 1e6:.1f} us vs measured "
              f"{measured_s * 1e3:.2f} ms")
        return rec

    # Per-Newton-step invariants (plans, trajectory gradients) built once;
    # the kernels below are the per-matvec / per-step hot loop.
    gs = jax.jit(
        lambda m0, m1, v: GR.evaluate(m0, m1, v, beta, gamma, cfg)
    )(pair.m0, pair.m1, v)
    gs = jax.block_until_ready(gs)
    coef = I.prefilter_for(pair.m0, cfg.interp)

    kernels = {}
    kernels["interp"] = measure(
        lambda c: I.apply_plan(gs.plan_fwd, c), (coef,), "interp (plan apply)")
    kernels["fd8"] = measure(
        lambda f: DV.fd8_grad(f), (pair.m0,), "fd8 gradient")
    kernels["fused_matvec"] = measure(
        lambda vt_, gs_, v_: HS.matvec(vt_, gs_, v_, beta, gamma, cfg_fused),
        (vt, gs, v), "fused matvec")
    kernels["matvec_xla"] = measure(
        lambda vt_, gs_, v_: HS.matvec(vt_, gs_, v_, beta, gamma, cfg),
        (vt, gs, v), "plan matvec (XLA)")
    if not smoke:  # full-step XLA compile takes minutes on host CPU
        step_args = (pair.m0, pair.m1, v, jnp.float32(beta),
                     jnp.float32(gamma), jnp.float32(0.5))
        kernels["newton_step"] = measure(
            GN._build_step(cfg, gn), step_args, "newton step")

    # Sharded matvec collective bytes: lower eval-only and eval+matvec under
    # shard_map and difference the collective bytes (the eval collectives —
    # plan build, trajectory halos — are common to both modules).
    matvec_coll = None
    if devices > 1 and n % devices == 0:
        mesh = Mesh(np.array(jax.devices()[:devices]).reshape(devices),
                    ("slab",))
        shard = H.ShardInfo(axis="slab", nshards=devices, halo=halo)
        cfg_sh = cfg._replace(shard=shard)
        img, vel = P("slab", None, None), P(None, "slab", None, None)

        def eval_only(m0, m1, v_):
            return GR.evaluate(m0, m1, v_, beta, gamma, cfg_sh).g

        def eval_mv(vt_, m0, m1, v_):
            gs_l = GR.evaluate(m0, m1, v_, beta, gamma, cfg_sh)
            return HS.matvec(vt_, gs_l, v_, beta, gamma, cfg_sh)

        def coll(fn, in_specs, args):
            wrapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                    out_specs=vel, check_vma=False)
            text = jax.jit(wrapped).lower(*args).compile().as_text()
            return analyze_hlo(text).coll_bytes

        c_eval = coll(eval_only, (img, img, vel), (pair.m0, pair.m1, v))
        c_both = coll(eval_mv, (vel, img, img, vel), (vt, pair.m0, pair.m1, v))
        matvec_coll = max(c_both - c_eval, 0.0)
        print(f"[bench] sharded matvec collectives ({devices} slabs): "
              f"{matvec_coll / 1e6:.3f} MB/matvec "
              f"(eval+mv {c_both / 1e6:.2f} - eval {c_eval / 1e6:.2f})")

    print_table(
        f"Roofline at {n}^3 ({variant}, Nt=4, TPU v5e constants)",
        ["kernel", "GFLOP", "MB", "intensity", "bound", "roofline us",
         "measured ms", "achieved"],
        [[k, fmt(r["flops"] / 1e9, 3), fmt(r["mem_bytes"] / 1e6, 1),
          fmt(r["intensity"], 2), r["bound"], fmt(r["roofline_s"] * 1e6, 1),
          fmt(r["measured_s"] * 1e3, 2), fmt(r["achieved_fraction"], 4)]
         for k, r in kernels.items()])

    entry = dict(
        ts=time.time(),
        grid=list(grid),
        devices=devices,
        halo=halo,
        variant=variant,
        smoke=smoke,
        backend=jax.default_backend(),
        kernels=kernels,
        matvec_collective_bytes=matvec_coll,
    )
    _append_json(RESULTS_DIR / out, entry)
    print(f"[bench] appended entry to {RESULTS_DIR / out}")

    # acceptance: every tracked kernel has nonzero cost/roofline entries.
    for k in ("interp", "fd8", "fused_matvec"):
        r = kernels[k]
        assert r["flops"] > 0 and r["mem_bytes"] > 0, (k, r)
        assert r["roofline_s"] > 0 and r["achieved_fraction"] > 0, (k, r)

    # regression gate: sharded matvec collective bytes vs checked-in baseline
    # for this (grid, devices) point; >20% growth fails.
    baseline_path = RESULTS_DIR / "roofline_baseline.json"
    if matvec_coll is not None and baseline_path.exists():
        base = json.loads(baseline_path.read_text())
        ref = next((b for b in base
                    if b["grid"] == list(grid) and b["devices"] == devices),
                   None)
        if ref is not None:
            ratio = matvec_coll / max(ref["matvec_collective_bytes"], 1.0)
            print(f"[bench] matvec collective bytes vs baseline: "
                  f"{ratio:.3f}x")
            assert ratio <= 1.2, (
                f"matvec collective bytes regressed {ratio:.2f}x over "
                f"baseline {ref['matvec_collective_bytes']:.3e}")
    return entry


# ---------------------------------------------------------------------------
# Precision presets: fp32 vs bf16 plan weights vs mixed precision at scale.
# Records quality/runtime per preset into results/BENCH_precision.json — the
# number base of the README precision table.
# ---------------------------------------------------------------------------


def run_precision(
    grids=(64, 128),
    variant: str = "fd8-cubic",
    seed: int = 7,
    max_newton: int = 3,
    smoke: bool = False,
    out: str = "BENCH_precision.json",
):
    import numpy as np

    if smoke:
        grids, max_newton = (16,), 2

    presets = [
        ("fp32", dict()),
        ("bf16-weights", dict(mixed_precision=True)),
    ]
    rows, records = [], []
    for n in grids:
        grid3 = (n, n, n)
        newton = max_newton if n < 128 else 1
        pair = synthetic.make_pair(jax.random.PRNGKey(seed), grid3,
                                   amplitude=0.5)
        v_ref = None
        for name, kw in presets:
            t0 = time.perf_counter()
            res = register(pair.m0, pair.m1, variant=variant,
                           max_newton=newton, **kw)
            wall = time.perf_counter() - t0
            if v_ref is None:
                v_ref = np.asarray(res.v)
                dv = 0.0
            else:
                dv = float(np.max(np.abs(np.asarray(res.v) - v_ref)))
            rec = dict(
                grid=list(grid3), preset=name, max_newton=newton,
                mismatch_rel=float(res.mismatch_rel),
                rel_grad=float(res.rel_grad), iters=res.iters,
                matvecs=res.matvecs, detF_min=float(res.detF["min"]),
                detF_max=float(res.detF["max"]), wall_s=wall,
                max_abs_dv_vs_fp32=dv,
            )
            records.append(rec)
            rows.append([f"{n}^3", name, fmt(res.mismatch_rel),
                         fmt(res.rel_grad), res.iters, res.matvecs,
                         fmt(res.detF["min"], 3), fmt(dv), fmt(wall, 1)])

    print_table(
        f"Precision presets ({variant}, Nt=4): fp32 vs bf16 interpolation "
        "weights (quality must be preset-invariant)",
        ["N", "preset", "mismatch", "|g|rel", "iters", "matvecs", "detF min",
         "|dv| vs fp32", "time s"],
        rows)

    entry = dict(
        ts=time.time(),
        variant=variant,
        seed=seed,
        smoke=smoke,
        host_devices=jax.device_count(),
        results=records,
    )
    _append_json(RESULTS_DIR / out, entry)
    print(f"[bench] appended entry to {RESULTS_DIR / out}")

    # acceptance: bf16 weights do not change the registration outcome beyond
    # interpolation-weight rounding (same iterations, tiny velocity delta).
    by_grid = {}
    for r in records:
        by_grid.setdefault(tuple(r["grid"]), {})[r["preset"]] = r
    for g, by in by_grid.items():
        if "fp32" in by and "bf16-weights" in by:
            assert abs(by["fp32"]["iters"] - by["bf16-weights"]["iters"]) <= 1
            assert by["bf16-weights"]["max_abs_dv_vs_fp32"] < 5e-2
    return entry


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=["variants", "api-smoke", "matvec",
                                       "dist", "serve", "measures",
                                       "roofline", "precision"],
                    default="variants")
    ap.add_argument("--grid", type=int, default=None)
    ap.add_argument("--max-newton", type=int, default=None)
    ap.add_argument("--variant", default="fd8-cubic")
    ap.add_argument("--iters", type=int, default=20,
                    help="matvec mode: timed matvecs per configuration")
    ap.add_argument("--backends", default="jnp",
                    help="matvec mode: comma list of kernel backends "
                         "(jnp,pallas)")
    ap.add_argument("--devices", type=int, default=8,
                    help="dist mode: forced host device count / slab shards")
    ap.add_argument("--halo", type=int, default=6,
                    help="dist mode: SL interpolation halo width (voxels)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (serve/measures/roofline/precision "
                         "modes): small grids, short phases")
    ap.add_argument("--grids", default=None,
                    help="serve mode: comma list of cubic grid sizes")
    ap.add_argument("--subjects", type=int, default=None,
                    help="serve mode: distinct longitudinal subjects")
    ap.add_argument("--max-batch", type=int, default=2,
                    help="serve mode: dynamic-batching wave width")
    ap.add_argument("--rate", type=float, default=None,
                    help="serve mode: open-loop Poisson arrival rate (req/s)")
    ap.add_argument("--tol", type=float, default=None,
                    help="serve mode: relative-gradient stopping tolerance")
    ap.add_argument("--measures", default="ssd,ncc,ngf",
                    help="measures mode: comma list of distance measures")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.mode == "measures":
        # argparse default "fd8-cubic" means "let the mode pick" here.
        run_measures(smoke=args.smoke, n=args.grid,
                     max_newton=args.max_newton,
                     variant=None if args.variant == "fd8-cubic"
                     else args.variant,
                     measures=tuple(args.measures.split(",")))
        return
    if args.mode == "serve":
        if args.smoke:
            grids = tuple(int(g) for g in (args.grids or "12,16").split(","))
            run_serve(smoke=True, grids=grids,
                      subjects=args.subjects or 2,
                      max_batch=args.max_batch,
                      max_newton=args.max_newton or 4,
                      tol=args.tol if args.tol is not None else 0.25,
                      rate=args.rate if args.rate is not None else 1.0)
        else:
            grids = tuple(int(g) for g in (args.grids or "16,24").split(","))
            run_serve(smoke=False, grids=grids,
                      subjects=args.subjects or 4,
                      max_batch=args.max_batch,
                      max_newton=args.max_newton or 8,
                      tol=args.tol if args.tol is not None else 0.15,
                      rate=args.rate if args.rate is not None else 0.5)
        return
    if args.mode == "variants":
        run(args.grid or 32,
            **({"max_newton": args.max_newton} if args.max_newton else {}))
    elif args.mode == "matvec":
        run_matvec(n=args.grid or 16, iters=args.iters,
                   backends=tuple(args.backends.split(",")))
    elif args.mode == "dist":
        run_dist(n=args.grid or 24, devices=args.devices, halo=args.halo,
                 variant=args.variant)
    elif args.mode == "roofline":
        run_roofline(n=args.grid or 64, devices=args.devices, halo=args.halo,
                     variant=args.variant, smoke=args.smoke)
    elif args.mode == "precision":
        grids = (tuple(int(g) for g in args.grids.split(","))
                 if args.grids else (64, 128))
        run_precision(grids=grids, variant=args.variant,
                      max_newton=args.max_newton or 3, smoke=args.smoke)
    else:
        run_modes(n=args.grid or 16, max_newton=args.max_newton or 20,
                  variant=args.variant)


if __name__ == "__main__":
    main()
