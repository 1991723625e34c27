"""The benchmark's own tests, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
