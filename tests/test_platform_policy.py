"""Where the program runs: no silent CPU fallback, no hidden device.

Platform queries are monkeypatched, so these run on the CPU.
"""

import pathlib
import subprocess

import jax
import pytest
from jax.sharding import AxisType

from repro.core.registration import make_transport_config
from repro.kernels import pencil
from repro.launch import compile_cache, hostenv
from repro.launch.mesh import auto_axes, make_mesh

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of changing the process."""
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/by/deployment")
    assert compile_cache.enable_compile_cache() is None
    assert config_updates == []


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert config_updates == [("jax_compilation_cache_dir", path)]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def _no_child(*args, **kwargs):
    raise AssertionError("ensure_host_devices started a child process")


def test_ensure_host_devices_raises_on_short_accelerator(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(subprocess, "run", _no_child)
    with pytest.raises(RuntimeError, match=r"need 8 devices but the 'tpu' backend has 1"):
        hostenv.ensure_host_devices(8)


def test_ensure_host_devices_reexecs_on_cpu(monkeypatch):
    seen = {}

    def fake_run(cmd, env):
        seen.update(cmd=cmd, env=env)
        return subprocess.CompletedProcess(cmd, 0)

    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.delenv(hostenv._SENTINEL, raising=False)
    monkeypatch.setattr(subprocess, "run", fake_run)
    assert hostenv.ensure_host_devices(4, argv=["prog"]) is True
    assert seen["cmd"] == ["prog"]
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert seen["env"]["XLA_FLAGS"] == "--xla_force_host_platform_device_count=4"


@pytest.mark.parametrize("platform,expected", [("cpu", True), ("tpu", False)])
def test_interpret_mode_only_on_cpu(monkeypatch, platform, expected):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert pencil.interpret_default() is expected


def test_interpret_default_rejects_other_platforms(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError, match="'gpu'"):
        pencil.interpret_default()


@pytest.mark.parametrize("kw", [dict(backend="pallas"),
                                dict(use_fused_matvec=True)],
                         ids=["pallas", "fused_matvec"])
def test_gather_kernels_fail_fast_on_tpu(monkeypatch, kw):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="Only 2D gather is supported"):
        make_transport_config("fd8-cubic", **kw)
    assert make_transport_config("fd8-cubic").backend == "jnp"


def test_meshes_have_auto_axes():
    mesh = make_mesh((1,), ("slab",))
    assert mesh.axis_types == (AxisType.Auto,)
    explicit = jax.make_mesh((1,), ("slab",), axis_types=(AxisType.Explicit,))
    converted = auto_axes(explicit)
    assert converted.axis_types == (AxisType.Auto,)
    assert converted.axis_names == explicit.axis_names
    assert list(converted.devices.flat) == list(explicit.devices.flat)
