"""HLO cost-walker unit tests: trip-count weighting, dot FLOPs, collective
byte models — on handcrafted HLO and on live-compiled modules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo import analyze_hlo, parse_module
from repro.roofline.analysis import (
    HW, achieved_fraction, kernel_roofline, roofline_terms)

SYNTH = """
HloModule test, num_partitions=4

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %w = f32[16,16]{1,0} constant({...})
  %dot = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(%dot), replica_groups={{0,1,2,3}}
  %one = s32[] constant(1)
  %inc = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,16]{1,0}) tuple(%inc, %ar)
}

%cond (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[8,16]) -> f32[8,16] {
  %x = f32[8,16]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[8,16]{1,0}) tuple(%zero, %x)
  %w = (s32[], f32[8,16]{1,0}) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,16]{1,0} get-tuple-element(%w), index=1
}
"""


def test_synthetic_module_trip_weighting():
    c = analyze_hlo(SYNTH)
    # dot: 2*8*16*16 = 4096 flops, x5 trips
    assert c.flops == pytest.approx(5 * 4096)
    # all-reduce: 2 * (8*16*4 bytes) * 3/4, x5
    assert c.coll_bytes == pytest.approx(5 * 2 * 512 * 0.75)
    assert set(c.coll_by_kind) == {"all-reduce"}


def test_parse_module_finds_entry_and_roots():
    comps, entry = parse_module(SYNTH)
    assert entry == "main"
    assert comps["body"].root_kind == "tuple"
    assert "cond" in comps


def test_live_matmul_flops_exact():
    """Compile a known matmul chain; walker FLOPs must match analytics."""
    w1 = jnp.zeros((64, 128), jnp.float32)
    w2 = jnp.zeros((128, 32), jnp.float32)
    x = jnp.zeros((16, 64), jnp.float32)

    def f(x):
        return (x @ w1) @ w2

    compiled = jax.jit(f).lower(x).compile()
    c = analyze_hlo(compiled.as_text())
    expect = 2 * 16 * 64 * 128 + 2 * 16 * 128 * 32
    assert c.flops == pytest.approx(expect)


def test_live_scan_flops_weighted():
    w = jnp.zeros((32, 32), jnp.float32)

    def body(x, _):
        return jnp.tanh(x @ w), None

    def f(x):
        y, _ = jax.lax.scan(body, x, None, length=11)
        return y

    compiled = jax.jit(f).lower(jnp.zeros((4, 32))).compile()
    c = analyze_hlo(compiled.as_text())
    assert c.flops == pytest.approx(11 * 2 * 4 * 32 * 32)
    # sanity: cost_analysis (unweighted) reports only ~1 body
    ca = compiled.cost_analysis()
    assert ca["flops"] < c.flops / 5


def test_synthetic_elementwise_flops_separate():
    """Float elementwise ops land in ew_flops (trip-weighted); integer loop
    bookkeeping (the s32 counter add) does not count as FLOPs at all."""
    c = analyze_hlo(SYNTH)
    assert c.ew_flops == 0.0  # only the s32 %inc add — not a float FLOP
    ew = SYNTH.replace(
        "%ar = f32[8,16]{1,0} all-reduce(%dot), replica_groups={{0,1,2,3}}",
        "%sq = f32[8,16]{1,0} multiply(%dot, %dot)\n"
        "  %ar = f32[8,16]{1,0} all-reduce(%sq), replica_groups={{0,1,2,3}}")
    c2 = analyze_hlo(ew)
    assert c2.ew_flops == pytest.approx(5 * 8 * 16)
    assert c2.flops == pytest.approx(5 * 4096)  # dot count untouched


def test_live_stencil_kernel_nonzero_ew_flops():
    """A registration-style stencil (no dots at all) must still produce a
    nonzero compute roofline via ew_flops — the regression behind the
    --mode roofline bench."""
    from repro.core import derivatives as DV

    f = jnp.zeros((16, 16, 16), jnp.float32)
    compiled = jax.jit(lambda g: DV.fd8_grad(g)).lower(f).compile()
    c = analyze_hlo(compiled.as_text())
    assert c.ew_flops > 0
    assert c.mem_bytes > 0
    kr = kernel_roofline(c.flops + c.ew_flops, c.mem_bytes, c.coll_bytes)
    assert kr.roofline_s > 0
    assert 0 < achieved_fraction(kr.roofline_s, measured_s=1e-3) < 1


@pytest.mark.slow
def test_newton_step_module_parse():
    """Capture a full Newton-step module (the --mode roofline subject) and
    walk it: the step is dot-free but must report nonzero elementwise FLOPs
    and memory traffic, and the PCG while loop must be trip-weighted (the
    walker's whole reason to exist — cost_analysis visits the body once)."""
    from repro.core import gauss_newton as GN
    from repro.core.registration import make_transport_config
    from repro.data import synthetic as S

    n = 12
    pair = S.make_pair(jax.random.PRNGKey(0), (n, n, n), amplitude=0.4)
    v = jnp.zeros((3, n, n, n), jnp.float32)
    cfg = make_transport_config("fd8-cubic", nt=2)
    step = GN._build_step(cfg, GN.GNConfig(max_pcg=6))
    args = (pair.m0, pair.m1, v, jnp.float32(5e-4), jnp.float32(1e-4),
            jnp.float32(0.5))
    text = jax.jit(step).lower(*args).compile().as_text()

    comps, entry = parse_module(text)
    assert entry is not None and entry in comps
    assert any(op.kind == "while" for c in comps.values() for op in c.ops)

    c = analyze_hlo(text)
    assert c.ew_flops > 0
    assert c.mem_bytes > 0
    assert c.coll_bytes == 0.0  # single-device module: no collectives
    kr = kernel_roofline(c.flops + c.ew_flops, c.mem_bytes)
    assert kr.roofline_s > 0 and kr.bound in ("compute", "memory")


def test_kernel_roofline_bound_selection():
    kr = kernel_roofline(flops=1e12, mem_bytes=1e6, collective_bytes=0.0)
    assert kr.bound == "compute"
    assert kr.roofline_s == pytest.approx(1e12 / HW["peak_flops"])
    assert kr.intensity == pytest.approx(1e6)
    kr2 = kernel_roofline(flops=1e6, mem_bytes=1e9, collective_bytes=0.0)
    assert kr2.bound == "memory"
    assert kr2.roofline_s == pytest.approx(1e9 / HW["hbm_bw"])
    kr3 = kernel_roofline(1e6, 1e6, collective_bytes=1e9)
    assert kr3.bound == "collective"
    # achieved fraction: measured at exactly the bound -> 1.0
    assert achieved_fraction(kr2.roofline_s, kr2.roofline_s) == pytest.approx(1.0)
    assert achieved_fraction(1.0, 0.0) == 0.0


def test_roofline_terms_bound_selection():
    r = roofline_terms(hlo_flops_device=1e12, hlo_bytes_device=1e9,
                       collective_bytes_device=1e6, chips=256,
                       model_flops_global=200e12)
    assert r.bound == "compute"
    assert r.compute_s == pytest.approx(1e12 / HW["peak_flops"])
    assert r.useful_ratio == pytest.approx(200e12 / (1e12 * 256))
    r2 = roofline_terms(1e9, 1e10, 1e9, 256, 0.0)
    assert r2.bound == "collective"
