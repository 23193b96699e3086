"""Readings the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--out file]

For each seed, in one process, the readings of :func:`bench.control.readings`:
the program's numbers, the control's and each fault's.  No window is
measured.  Prints one JSON line per seed; exits non-zero without a TPU.
"""
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]

import argparse  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

from bench.control import readings  # noqa: E402
from bench.spec import Spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    spec = Spec()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: JAX found no TPU; nothing was run", file=sys.stderr)
        return 2
    devices = devices[:spec.cell(args.workload)["chips"]]
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        row = readings(spec, args.workload, seed, devices)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
