"""Host-platform launch environment helpers. CPU-only tools.

Benchmarks and multi-device tests on machines without accelerators force a
multi-device view of the host CPU (``--xla_force_host_platform_device_count``).
XLA reads the flag once at backend init, so a process that already imported
JAX must re-exec itself with the flag set. This module is the one shared
implementation of that trick — plus the optional tcmalloc preload that
stabilizes large-grid host allocations — so the dist/roofline bench modes and
the launch scripts stop rolling their own re-exec logic. ``launch/env.sh`` is
the shell-side equivalent for interactive runs.

Nothing here runs on an accelerator: the parent has already taken the chip
when it counts devices, so a child could not reach it, and a child pinned to
the CPU would hide the device. On a TPU host, :func:`ensure_host_devices`
raises instead of re-executing; ``chip_smoke.py`` never calls this module.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Dict, Optional, Sequence

_SENTINEL = "_REPRO_HOSTENV_CHILD"

TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
)


def find_tcmalloc() -> Optional[str]:
    """Path of an installed tcmalloc shared library, or None."""
    for p in TCMALLOC_PATHS:
        if os.path.exists(p):
            return p
    return None


def host_device_env(devices: int, tcmalloc: bool = False) -> Dict[str, str]:
    """Environment additions forcing ``devices`` host CPU devices.

    Forcing host devices only helps on the CPU backend, so JAX_PLATFORMS is
    pinned alongside the XLA flag.
    """
    env = {
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "JAX_PLATFORMS": "cpu",
    }
    if tcmalloc:
        lib = find_tcmalloc()
        if lib:
            env["LD_PRELOAD"] = lib
    return env


def ensure_host_devices(devices: int, argv: Optional[Sequence[str]] = None,
                        tcmalloc: bool = False) -> bool:
    """Ensure this process sees at least ``devices`` JAX devices.

    Returns False when the requirement already holds (caller proceeds
    normally). On the CPU backend it otherwise re-runs ``argv`` (default:
    ``sys.argv`` under the current interpreter) in a child carrying the
    forced-host-device environment and returns True — the caller should
    return immediately. A sentinel guards against a re-exec loop: a child
    that still sees too few devices aborts instead of forking forever. On any
    other backend too few devices is an error (``RuntimeError``).
    """
    import jax

    if jax.device_count() >= devices:
        return False
    platform = jax.default_backend()
    if platform != "cpu":
        raise RuntimeError(
            f"[launch] need {devices} devices but the {platform!r} backend "
            f"has {jax.device_count()}; forced host devices are a CPU-only "
            "fallback")
    if os.environ.get(_SENTINEL):
        raise SystemExit(
            f"[launch] forced {devices} host devices but jax reports "
            f"{jax.device_count()} ({jax.devices()}); aborting")
    env = dict(os.environ, **host_device_env(devices, tcmalloc=tcmalloc))
    env[_SENTINEL] = "1"
    cmd = list(argv) if argv is not None else [sys.executable] + sys.argv
    print(f"[launch] re-executing under {devices} forced host CPU devices")
    res = subprocess.run(cmd, env=env)
    if res.returncode != 0:
        raise SystemExit(res.returncode)
    return True
