#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json; its configuration,
traffic mix, limits and metric readers are found by name under bench/
(see harness/common.py).  Weights and inputs are made from `--seed`.
Set-up (weights, the program's first steps, every compile) is timed as
`setup_s`; then whole steps run for `--seconds`, and nothing may compile
in that window.  After it the run checks what the timed path produced
against the plain reference, and prints each number compared beside its
limit on the last lines of standard error and in the result line, the
last line of standard output:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, "breakdown": {...}, "checks": {...}}

`--trace 0` reports the cell's end-to-end metrics, `--trace 1` its
per-layer metrics from a run traced by the JAX profiler.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import common  # noqa: E402

TRACE_DIR = common.CHECKOUT / ".bench_trace"
# a traced run traces at most this many seconds of whole steps: enough
# steps for the per-layer shares, and a trace that reads in seconds
TRACE_SECONDS = 10.0


def seeds_of(seed: int) -> dict:
    """Independent 32-bit seeds for each use, from any whole `--seed`."""
    import numpy as np

    w, t, r, c = np.random.SeedSequence(seed).generate_state(4)
    return {"weights": int(w), "traffic": int(t), "rl": int(r % 2**31),
            "check": int(c)}


def tracer_for(trace: bool, cell: str):
    """A context manager for the measured window.  Traced, the profiler
    starts here, in set-up, so that its start and the first calls it
    sees fall outside the window; it marks the window with a span and
    stops as the window closes."""
    if not trace:
        return contextlib.nullcontext
    import jax

    from harness.trace import WINDOW_SPAN

    out = TRACE_DIR / cell
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(str(out))

    @contextlib.contextmanager
    def traced():
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()

    return traced


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = common.find_cell(args.workload)
        devices = common.require_chips(cell.chips)
    except common.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = measure(cell, args.seed, args.seconds, bool(args.trace),
                     devices, common.peaks_for(devices[0].device_kind))
    for name, line in result["checks"].items():
        print(f"bench: check {name} = {line['value']!r} (limit "
              f"{line['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            peaks: dict, t_start: float = T_START) -> dict:
    """One run of `cell`: set-up, the window, the check; the result line
    as a dict."""
    import jax

    from harness import program  # noqa: F401  (puts src/ on the path)
    from harness import trace as trace_mod
    from repro.launch.runtime import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = common.compile_counter()
    common.log(workload=cell.name, seed=seed, compile_cache=cache,
               device=devices[0].device_kind)
    kind = importlib.import_module(f"harness.{cell.traffic['kind']}")
    run, checked, attempted, mem = kind.run(
        cell, seeds_of(seed), min(seconds, TRACE_SECONDS) if trace
        else seconds, tracer_for(trace, cell.name),
        t_start, counter, devices, peaks)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    if trace:
        reduced = trace_mod.reduce(trace_mod.load(TRACE_DIR / cell.name))
        if reduced is None:
            raise common.BenchError("the trace holds no device operation "
                                    "inside the window")
        run.trace = reduced
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        common.log(executables_s=reduced["executables_s"])
    result = {"correct": checked.correct, "attempted": attempted,
              "failed": 0 if checked.correct else attempted,
              "metrics": common.metric_values(cell, run, trace),
              "device": device}
    if trace:
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = checked.lines()
    return result


if __name__ == "__main__":
    sys.exit(main())
