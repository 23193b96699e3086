"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, with no result, where JAX finds no TPU or fewer chips than
the cell asks for.  JAX's compilation cache is kept in ``.jax_cache/`` at the
root of the checkout (``repro.launch.compile_cache``), so that only a cell's
first run there compiles.
"""
import time

T_START = time.monotonic()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]

import jax  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
