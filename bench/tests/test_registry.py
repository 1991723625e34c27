"""Every entry of BENCHMARK.json resolves through the by-name lookups, and
the file keeps to the benchmark contract's shape rules."""

import re

import pytest

from bench import data
from bench.registry import ROOT, Benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return Benchmark()


def test_top_level_keys(bench):
    assert set(bench.spec) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench.spec["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for p in bench.spec["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p


@pytest.mark.parametrize("entry", Benchmark().spec["configs"], ids=lambda c: c["name"])
def test_config_resolves(bench, entry):
    assert NAME.match(entry["name"])
    cfg = bench.config(entry["name"])
    assert entry["file"].startswith("bench/configs/")
    for key in entry["reduced"]:
        assert NAME.match(key)
        assert key in cfg["published"] and cfg["published"][key] != cfg[key]
    for key in cfg["published"]:
        if key not in entry["reduced"]:
            assert cfg["published"][key] == cfg[key]
    assert cfg["grid"][0] == cfg["grid"][1] == cfg["grid"][2]


@pytest.mark.parametrize("cell", Benchmark().spec["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(bench, cell):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    assert traffic["amplitudes"] and traffic["pool_seed"] >= 0
    assert data.pool_size(traffic) >= 2
    limits = bench.limits(cell["name"])
    assert all(v["max"] > 0 for k, v in limits.items() if isinstance(v, dict))
    names = {m["name"] for m in bench.end_to_end(cell["name"])}
    assert {"setup_s", "pair_s"} <= names
    assert bench.per_layer(cell["name"])
    # A batched cell's wave holds each amplitude once, so each wave does
    # about the same work, and a pass is whole waves.
    assert cfg["batch"] == 1 or len(traffic["amplitudes"]) == cfg["batch"]


@pytest.mark.parametrize("metric", Benchmark().spec["per_layer"] + Benchmark().spec["end_to_end"],
                         ids=lambda m: m["name"])
def test_metric_shape(bench, metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert metric["moves"] in {m["name"] for m in bench.spec["end_to_end"]}
        assert callable(bench.reader(metric["name"]))
    for w in metric.get("workloads", []):
        bench.workload(w)


def test_pair_plans_cover_the_pool():
    from bench import data

    traffic = dict(amplitudes=[0.1, 0.2, 0.3, 0.4], deformations=3)
    seed = 2**33 + 5

    def plans(s):
        return [data.pair_plan(s, traffic, k, 16) for k in range(24)]

    got = plans(seed)
    # Each pass of 12 holds every base pair once; each wave of 4 holds every
    # amplitude once.
    for p in range(2):
        assert sorted(q["base"] for q in got[12 * p:12 * (p + 1)]) == list(range(12))
    for w in range(6):
        assert sorted(q["base"] // 3 for q in got[4 * w:4 * (w + 1)]) == [0, 1, 2, 3]
    assert got != plans(5)
    assert got == plans(seed)
    # The deformations' order depends on the seed.
    orders = {tuple(q["base"] for q in plans(s)[:12]) for s in range(8)}
    assert len(orders) > 1
    assert data.warm_plan(seed, traffic, 2, 16)["base"] == 6
