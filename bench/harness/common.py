"""What every cell shares: finding a cell's files by name, the device and
its peaks, the compile counter, the run record the metric readers read,
and the result line.

A cell is one entry of `workloads` in BENCHMARK.json.  Everything that
belongs to one configuration, traffic mix, per-layer metric or cell sits
in files of its own, found by name:

    bench/configs/<config>.json      sizes as run, source, reduced keys
    bench/traffic/<traffic>.json     the mix's parameters and its `kind`
    bench/metrics/<metric>.py        `read(run) -> float | None`
    bench/limits/<workload>.json     the correctness limits of the cell

so a later cell, configuration, mix or metric is added by adding files.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Callable, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent
SRC = CHECKOUT / "src"

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """A run that cannot be measured: no result line is printed."""


def log(**fields) -> None:
    print("bench: " + json.dumps(fields), file=sys.stderr, flush=True)


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: pathlib.Path = CHECKOUT) -> Cell:
    spec_path = root / "BENCHMARK.json"
    if not spec_path.exists():
        raise BenchError(f"no BENCHMARK.json in {root}")
    spec = load_json(spec_path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    bench = root / "bench"
    return Cell(
        name=name, chips=w["chips"],
        config=load_json(bench / "configs" / f"{w['config']}.json"),
        traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(bench / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def reader(metric: str, root: pathlib.Path = BENCH) -> Callable:
    """The `read` function of bench/metrics/<metric>.py."""
    path = root / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "harness" / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r}: add it to "
                         "bench/harness/peaks.json with its source")
    return table[kind]


def require_chips(chips: int):
    """The devices of the run: TPUs, at least `chips` of them, with known
    peaks.  Anything else is an error, never a fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX sees {devices[0].platform}, not a TPU; "
                         "nothing was measured")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    peaks_for(devices[0].device_kind)
    return devices[:chips]


def compile_counter() -> List[float]:
    """A list that grows by one for every executable JAX builds or loads
    from the persistent cache from here on."""
    import jax

    seen: List[float] = []

    def listener(event, duration, **_):
        if event == COMPILE_EVENT:
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


def memory_peak_bytes(devices) -> Optional[int]:
    """The peak device memory of the fullest chip: arrays at their peak
    (`peak_bytes_in_use`) plus what the TPU runtime reserves apart from
    them for the compiled programs' temporaries (`peak_bytes_reserved`),
    which hold `generate`'s paged KV cache and the dequantized weights."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        log(device=d.id, memory_stats=stats)
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# the run record
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a run measured, as the metric readers see it."""

    kind: str                      # the traffic's kind: rl_step | rollout
    setup_s: float
    window_s: float
    steps: List[dict]              # one record per whole step in the window
    peaks: dict
    trace: Optional[dict] = None   # trace.reduce() of the traced run


@dataclasses.dataclass
class Checked:
    """The comparison that decides `correct`: each number beside its
    limit."""

    numbers: Dict[str, float]
    limits: Dict[str, float]

    @property
    def correct(self) -> bool:
        return all(self.numbers[k] <= self.limits[k] for k in self.limits)

    def lines(self) -> Dict[str, dict]:
        return {k: {"value": self.numbers[k], "limit": self.limits[k]}
                for k in self.limits}


def window(step: Callable[[int], dict], seconds: float, tracer,
           counter: List[float]):
    """Run whole steps for `seconds`: step k is `step(k)`, a record with
    its start `t0` and end `t1`.  Another step starts only when one as long
    as the last still ends inside the window, so no step is cut and the
    window never overruns.  Nothing may compile inside it.  Returns the
    step records and the window's length, first start to last end."""
    import time

    built = len(counter)
    steps: List[dict] = []
    with tracer():
        t0 = time.perf_counter()
        while not steps or (time.perf_counter() - t0 + steps[-1]["t1"]
                            - steps[-1]["t0"] <= seconds):
            steps.append(step(len(steps)))
        window_s = steps[-1]["t1"] - t0
    built = len(counter) - built
    log(steps=len(steps), window_s=window_s, compiles_in_window=built)
    if built:
        raise BenchError(f"{built} executables were built or loaded inside "
                         "the measured window")
    return steps, window_s


def metric_values(cell: Cell, run: Run, trace: bool) -> Dict[str, dict]:
    out = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
