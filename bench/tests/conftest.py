"""The benchmark's own tests: on the CPU, at the SMOKE width.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
