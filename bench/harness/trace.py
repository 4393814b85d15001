"""Reduction of a profiler trace to device busy time, executable time and
idle gaps.

`load` reads the `.xplane.pb` that `jax.profiler.trace` writes into plain
lists of (start_ns, end_ns, name) intervals; `reduce` works on those lists
alone, so the tests can hand it a synthetic trace.

- busy: the union of the intervals in which an operation ran on the
  device, inside the traced window (the host span `WINDOW_SPAN`);
- executables: device time per XLA module (jitted program), summed;
- ops: device time per operation name, summed;
- idle gaps: the stretches of the window with no device operation,
  each named by the innermost benchmark span the host was in at its
  midpoint.
"""
from __future__ import annotations

import dataclasses
import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench_window"
# the spans the benchmark puts around its calls into the program
HOST_SPANS = ("train_step", "sync", "generate")

Interval = Tuple[float, float, str]

# control-flow operations span the operations they run; the breakdown
# lists what runs inside them
_CONTAINERS = (" while(", " conditional(", " call(")


@dataclasses.dataclass
class Trace:
    ops: List[Interval]            # device operations
    modules: List[Interval]        # device executables (XLA modules)
    spans: List[Interval]          # host spans of the benchmark
    n_devices: int = 1


def union_ns(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of the union of the intervals, clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: Sequence[Interval], lo: float, hi: float
            ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e, _ in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _label(spans: Sequence[Interval], t: float) -> str:
    inner = None
    for s, e, name in spans:
        if s <= t < e and (inner is None or e - s < inner[1] - inner[0]):
            inner = (s, e, name)
    return inner[2] if inner else "none"


def op_name(hlo: str) -> str:
    """`%fusion.12 = bf16[8,128]{...} fusion(...)` -> `fusion.12 bf16[8,128]`:
    the instruction and its result type, without the operands."""
    head, _, rest = hlo.partition(" = ")
    return (head.lstrip("%") + " " + rest.split("{")[0].split(" ")[0])[:80] \
        if rest else hlo[:80]


def _sum_by_name(intervals, lo, hi) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for s, e, name in intervals:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            out[name] = out.get(name, 0.0) + d
    return out


def window(trace: Trace) -> Optional[Tuple[float, float]]:
    w = [(s, e) for s, e, n in trace.spans if n == WINDOW_SPAN]
    return (min(s for s, _ in w), max(e for _, e in w)) if w else None


def reduce(trace: Trace, top: int = 10) -> Optional[dict]:
    """Busy and window seconds, executable and op seconds, and the longest
    idle gaps, over the traced window; None where the trace holds no
    window or no device operation."""
    win = window(trace)
    if win is None or not trace.ops:
        return None
    lo, hi = win
    busy = union_ns(trace.ops, lo, hi) / trace.n_devices
    if busy <= 0:
        return None
    spans = [sp for sp in trace.spans if sp[2] in HOST_SPANS]
    gaps = sorted(gaps_ns(trace.ops, lo, hi), key=lambda g: g[0] - g[1])
    leaves = [(s, e, op_name(n)) for s, e, n in trace.ops
              if not any(c in n for c in _CONTAINERS)]
    ops = sorted(_sum_by_name(leaves, lo, hi).items(), key=lambda x: -x[1])
    mods = _sum_by_name(trace.modules, lo, hi)
    return {
        "busy_s": busy * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "executables_s": {k: v * 1e-9 for k, v in mods.items()},
        "device_ops": [[n, v * 1e-9] for n, v in ops[:top]],
        "idle_gaps": [[_label(spans, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }


def load(directory: pathlib.Path) -> Trace:
    """Read the newest `.xplane.pb` under `directory`."""
    import jax

    files = sorted(directory.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    ops, modules, spans, devices = [], [], [], 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            devices += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events
                          if e.name == WINDOW_SPAN or e.name in HOST_SPANS]
    return Trace(ops=ops, modules=modules, spans=spans,
                 n_devices=max(devices, 1))
