import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes and extract memory / cost / collective analysis.

This file MUST set XLA_FLAGS before any jax-importing module (jax locks the
device count on first init) — hence the two lines above everything else.

Usage:
    python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out results/dryrun.jsonl
    python -m repro.launch.dryrun --list
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, REGISTRATIONS, SHAPES
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.roofline.hlo import analyze_hlo
from repro.roofline.analysis import roofline_terms
from repro.roofline.lm import model_flops
from repro.train import steps as tsteps

#: long_500k needs a sub-quadratic sequence path; the pure full-attention
#: archs have none (see DESIGN.md §Arch-applicability) — recorded skips.
LONG_CAPABLE = {"mamba2-780m", "jamba-v0.1-52b"}


def cell_is_skipped(arch: str, shape: str) -> bool:
    return shape == "long_500k" and arch not in LONG_CAPABLE


def run_cell(arch: str, shape: str, mesh_kind: str) -> dict:
    cfg = ARCHS[arch]
    shape_cfg = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    model = build_model(cfg)
    rec = dict(arch=arch, shape=shape, mesh=mesh_kind, chips=chips,
               kind=shape_cfg.kind, status="ok")
    t0 = time.time()

    if shape_cfg.kind == "train":
        step_fn, state_sh = tsteps.make_train_step(model, mesh)
        state_abs = tsteps.abstract_train_state(model)
        batch_abs = model.input_specs(shape_cfg)["batch"]
        batch_sh = tsteps.batch_shardings(model, mesh, batch_abs)
        jitted = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
        lowered = jitted.lower(state_abs, batch_abs)
    elif shape_cfg.kind == "prefill":
        step_fn, p_sh = tsteps.make_prefill_step(model, mesh)
        params_abs = model.abstract_params()
        batch_abs = model.input_specs(shape_cfg)["batch"]
        batch_sh = tsteps.batch_shardings(model, mesh, batch_abs)
        jitted = jax.jit(step_fn, in_shardings=(p_sh, batch_sh))
        lowered = jitted.lower(params_abs, batch_abs)
    else:  # decode
        step_fn, p_sh = tsteps.make_decode_step(model, mesh)
        params_abs = model.abstract_params()
        specs = model.input_specs(shape_cfg)
        cache_abs, tok_abs, pos_abs = (specs["cache"], specs["tokens"],
                                       specs["position"])
        cache_sh = tsteps.cache_shardings(model, mesh, cache_abs)
        tok_sh = tsteps.batch_shardings(model, mesh, {"tokens": tok_abs})["tokens"]
        jitted = jax.jit(step_fn,
                         in_shardings=(p_sh, cache_sh, tok_sh, None),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(1,))
        lowered = jitted.lower(params_abs, cache_abs, tok_abs, pos_abs)

    rec["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)

    # ---- memory analysis (per device) ----
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = dict(
            argument_bytes=int(ma.argument_size_in_bytes),
            output_bytes=int(ma.output_size_in_bytes),
            temp_bytes=int(ma.temp_size_in_bytes),
            alias_bytes=int(ma.alias_size_in_bytes),
        )
        rec["memory"]["peak_bytes"] = (rec["memory"]["argument_bytes"]
                                       + rec["memory"]["output_bytes"]
                                       + rec["memory"]["temp_bytes"]
                                       - rec["memory"]["alias_bytes"])
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}

    # ---- cost analysis ----
    # compiled.cost_analysis() counts while-loop bodies ONCE (verified; see
    # DESIGN.md) — useless under scan-over-layers. We walk the partitioned
    # HLO with trip-count weighting instead; raw values kept for reference.
    ca = compiled.cost_analysis() or {}
    rec["xla_cost_analysis"] = dict(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
    )
    hlo = compiled.as_text()
    costs = analyze_hlo(hlo)
    flops_dev = costs.flops
    bytes_dev = costs.mem_bytes
    coll_dev = costs.coll_bytes
    rec["collectives_by_kind"] = {k: round(v) for k, v in
                                  costs.coll_by_kind.items()}

    mf = model_flops(cfg, shape_cfg)
    rl = roofline_terms(flops_dev, bytes_dev, coll_dev, chips, mf)
    rec["roofline"] = dict(
        hlo_flops_device=flops_dev,
        hlo_bytes_device=bytes_dev,
        collective_bytes_device=coll_dev,
        compute_s=rl.compute_s,
        memory_s=rl.memory_s,
        collective_s=rl.collective_s,
        bound=rl.bound,
        model_flops=mf,
        useful_ratio=rl.useful_ratio,
        step_s=rl.step_s,
        roofline_fraction=rl.roofline_fraction,
    )
    return rec


def run_claire_cell(config_name: str, mode: str, mesh_kind: str) -> dict:
    """Dry-run of the paper's own workload: one Gauss-Newton step.

    ``mode='ensemble'``: a batch of independent registrations vmapped and
    sharded over the data axes (the paper's population-study workload).
    ``mode='slab'``: one registration slab-decomposed over the model axis
    (the paper's declared MPI future work).

    The jitted unit is a Newton step with a 6-matvec PCG budget and a
    single-trial line search (typical early-GN behaviour per the paper's
    Table 7: ~6 matvecs/step); costs scale linearly in matvecs.
    """
    import jax.numpy as jnp
    from repro.core import gauss_newton as GN
    from repro.core import transport as T
    from repro.distributed import claire_dist as CD

    rcfg = REGISTRATIONS[config_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    tcfg = T.TransportConfig(interp="cubic_bspline", deriv="fd8", nt=rcfg.nt)
    gcfg = GN.GNConfig(max_pcg=6, ls_max=1)
    rec = dict(arch=config_name, shape=f"claire_{mode}", mesh=mesh_kind,
               chips=chips, kind="registration", status="ok")
    t0 = time.time()

    scalars = (jnp.float32(rcfg.beta), jnp.float32(rcfg.gamma),
               jnp.float32(0.5))
    if mode == "ensemble":
        batch = max(rcfg.ensemble, chips)
        step = CD.ensemble_newton_step(tcfg, gcfg)
        specs = CD.ensemble_input_specs(rcfg.grid, batch)
        img_sh, vel_sh = CD.ensemble_shardings(mesh, batch)
        jitted = jax.jit(step, in_shardings=(img_sh, img_sh, vel_sh,
                                             None, None, None))
        lowered = jitted.lower(specs["m0"], specs["m1"], specs["v"], *scalars)
    else:  # slab
        step = CD.slab_newton_step(tcfg, gcfg)
        specs = CD.slab_input_specs(rcfg.grid)
        img_sh, vel_sh = CD.slab_shardings(mesh, rcfg.grid)
        jitted = jax.jit(step, in_shardings=(img_sh, img_sh, vel_sh,
                                             None, None, None))
        lowered = jitted.lower(specs["m0"], specs["m1"], specs["v"], *scalars)

    rec["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = dict(
            argument_bytes=int(ma.argument_size_in_bytes),
            output_bytes=int(ma.output_size_in_bytes),
            temp_bytes=int(ma.temp_size_in_bytes),
            alias_bytes=int(ma.alias_size_in_bytes))
        rec["memory"]["peak_bytes"] = (rec["memory"]["argument_bytes"]
                                       + rec["memory"]["output_bytes"]
                                       + rec["memory"]["temp_bytes"]
                                       - rec["memory"]["alias_bytes"])
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}
    costs = analyze_hlo(compiled.as_text())
    rec["collectives_by_kind"] = {k: round(v) for k, v in
                                  costs.coll_by_kind.items()}
    rl = roofline_terms(costs.flops, costs.mem_bytes, costs.coll_bytes,
                        chips, 0.0)
    rec["roofline"] = dict(
        hlo_flops_device=costs.flops, hlo_bytes_device=costs.mem_bytes,
        collective_bytes_device=costs.coll_bytes,
        compute_s=rl.compute_s, memory_s=rl.memory_s,
        collective_s=rl.collective_s, bound=rl.bound, model_flops=0.0,
        useful_ratio=0.0, step_s=rl.step_s, roofline_fraction=0.0)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--claire", choices=sorted(REGISTRATIONS), default=None,
                    help="dry-run the registration workload instead")
    ap.add_argument("--claire-mode", choices=("ensemble", "slab"),
                    default="ensemble")
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--jobs", type=int, default=1,
                    help="parallel subprocesses for --all")
    args = ap.parse_args(argv)

    if args.list:
        for a in sorted(ARCHS):
            for s in sorted(SHAPES):
                skip = " (skip: no sub-quadratic path)" if cell_is_skipped(a, s) else ""
                print(f"{a:22s} {s}{skip}")
        return 0

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.claire:
        rc = 0
        for m in meshes:
            try:
                rec = run_claire_cell(args.claire, args.claire_mode, m)
            except Exception as e:
                rec = dict(arch=args.claire, shape=f"claire_{args.claire_mode}",
                           mesh=m, status=f"error: {type(e).__name__}: {e}",
                           traceback=traceback.format_exc())
                rc = 1
            _record(rec, args.out)
        return rc

    if args.all:
        cells = [(a, s, m) for a in sorted(ARCHS) for s in sorted(SHAPES)
                 for m in meshes]
        if args.jobs > 1:
            return _run_parallel(cells, args.out, args.jobs)
        rc = 0
        for a, s, m in cells:
            rc |= _run_one(a, s, m, args.out)
        return rc

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all / --list)")
    rc = 0
    for m in meshes:
        rc |= _run_one(args.arch, args.shape, m, args.out)
    return rc


def _record(rec: dict, out: str | None):
    line = json.dumps(rec)
    if out:
        Path(out).parent.mkdir(parents=True, exist_ok=True)
        with open(out, "a") as f:
            f.write(line + "\n")
    r = rec.get("roofline", {})
    mem = rec.get("memory", {})
    status = rec.get("status")
    if status == "ok":
        print(f"[dryrun] {rec['arch']} x {rec['shape']} x {rec['mesh']}: OK "
              f"compile={rec.get('compile_s')}s "
              f"peak={mem.get('peak_bytes', 0)/1e9:.2f}GB/dev "
              f"bound={r.get('bound')} "
              f"terms(c/m/x)={r.get('compute_s', 0):.3e}/{r.get('memory_s', 0):.3e}/"
              f"{r.get('collective_s', 0):.3e}s "
              f"useful={r.get('useful_ratio', 0):.2f}")
    else:
        print(f"[dryrun] {rec['arch']} x {rec['shape']} x {rec['mesh']}: {status}")


def _run_one(arch: str, shape: str, mesh_kind: str, out: str | None) -> int:
    if cell_is_skipped(arch, shape):
        _record(dict(arch=arch, shape=shape, mesh=mesh_kind,
                     status="skipped: full-attention arch has no sub-quadratic "
                            "path at 500k (DESIGN.md §Arch-applicability)"), out)
        return 0
    try:
        rec = run_cell(arch, shape, mesh_kind)
    except Exception as e:
        rec = dict(arch=arch, shape=shape, mesh=mesh_kind,
                   status=f"error: {type(e).__name__}: {e}",
                   traceback=traceback.format_exc())
        _record(rec, out)
        return 1
    _record(rec, out)
    return 0


def _run_parallel(cells, out, jobs) -> int:
    """Fan out one subprocess per cell (compiles are process-parallel)."""
    pending = list(cells)
    running: list = []
    rc = 0
    while pending or running:
        while pending and len(running) < jobs:
            a, s, m = pending.pop(0)
            if cell_is_skipped(a, s):
                _record(dict(arch=a, shape=s, mesh=m,
                             status="skipped: full-attention arch has no "
                                    "sub-quadratic path at 500k"), out)
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--mesh", m]
            if out:
                cmd += ["--out", out]
            running.append(((a, s, m), subprocess.Popen(cmd)))
        done = [(k, p) for k, p in running if p.poll() is not None]
        for k, p in done:
            running.remove((k, p))
            rc |= p.returncode
        if running:
            time.sleep(1.0)
    return rc


if __name__ == "__main__":
    sys.exit(main())
