#!/usr/bin/env python3
"""Readings for a cell's correctness limits, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed: the numbers a benchmark run of the cell compares, for the
program, for the controls (the reference one precision below the
configuration's) and for the planted faults (see harness/control.py).
Prints one JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = common.find_cell(args.workload)
    common.require_chips(cell.chips)

    import jax

    from harness import control, program  # noqa: F401
    from repro.launch.runtime import enable_compile_cache
    from run import seeds_of

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    readings = getattr(control, f"{cell.traffic['kind']}_readings")
    for seed in args.seeds:
        out = readings(cell, seeds_of(seed), args.seconds)
        print(json.dumps({"workload": cell.name, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
