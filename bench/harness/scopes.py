"""Device time by the program's own scopes, from a profiler trace.

The program names its work twice (`repro.obs.scopes`): device scopes,
which `jax.named_scope` writes into each HLO instruction's `op_name`
metadata (``jit(generate)/while/body/decode/.../kv_read/...``), and
program host spans (`TraceAnnotation`s such as `weight_sync`).  A TPU
trace names each device operation by its HLO instruction only, so
`load` maps it to its scope through the HLO of its executable, which
the profiler stores in the trace itself (the `/host:metadata` plane
holds one `Hlo Proto` per program, named as the `XLA Modules` events
are): operation -> the module execution it ran in -> that module's
instruction of the same name -> its `op_name`.  A fusion carries its
root instruction's `op_name`, so work XLA fuses into its consumer counts
under the consumer's scope, wherever the program issued it: a change
that only fuses differently can move time between scopes.  An
instruction XLA made itself (a layout copy, a loop's carry) has none;
it takes the phase its computation's
named instructions agree on (ignoring those in none) and the scope they
all agree on, so a copy in the decode loop counts in the `decode` phase,
unscoped, and one in the weight sync's program under `quantize`.
On a CPU trace the operations are the host's XLA thunks, which name
their module and instruction in their own stats.

`reduce` works on plain lists, so the tests can hand it a synthetic
trace:

- `scopes_s`: device seconds per phase (`prefill`, `fork`, `decode` or
  `other`: the outermost phase scope on the path) and scope (the
  innermost registered non-phase scope on the path, or `unscoped`),
  over the window's leaf operations; the partition adds up to their
  time exactly;
- `scoped_s`: per executable, [seconds under a registered scope,
  seconds of its leaf operations]: how much of it the scopes name,
  logged with the reduction;
- `top_ops`: the leaf operations with the most time, each with its
  phase and scope; `unscoped_ops`: every unscoped one that takes 1% or
  more of its executable's leaf time;
- `decode_passes`: executions of the decode loop's body, counted as the
  most executions of any one instruction whose own `op_name` puts it in
  the `decode` phase, scoped `sample`, in no loop nested inside it
  (sampling runs once a pass, outside the layer scan).  It stands in for
  the loop's own counter, `Trajectory.decode_passes`, which the rollout
  step records do not carry yet;
- `idle_gaps_program`: `trace.reduce`'s idle gaps, each named by the
  innermost program span the host was in at its midpoint.

`read(run)` is what the metric readers call: the reduction of the
newest trace under `.bench_trace/` for a traced run, else None.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import pathlib
from typing import Dict, List, Optional, Tuple

from . import program  # noqa: F401  (puts src/ on the path)
from .common import CHECKOUT, log
from .trace import _CONTAINERS, WINDOW_SPAN, _label, gaps_ns, op_name

try:
    from repro.obs.scopes import DEVICE_SCOPES, PHASES, PROGRAM_SPANS
except ImportError:        # a program that names none of its work
    DEVICE_SCOPES = PHASES = PROGRAM_SPANS = ()

TRACE_DIR = CHECKOUT / ".bench_trace"
LAYER_SCOPES = tuple(s for s in DEVICE_SCOPES if s not in PHASES)
UNSCOPED = "unscoped"
OTHER = "other"
CONTAINER_CODES = ("while", "conditional", "call")

Interval = Tuple[float, float, str]


@dataclasses.dataclass
class ScopedTrace:
    ops: List[Interval]            # device operations, every device
    op_modules: List[str]          # the module execution each op ran in
    op_paths: List[str]            # each op's HLO op_name ("" unknown)
    spans: List[Interval]          # host spans: the window, program spans
    n_devices: int = 1
    op_codes: List[str] = dataclasses.field(default_factory=list)
    op_inherited: List[bool] = dataclasses.field(default_factory=list)

    def is_container(self, i: int) -> bool:
        """Control flow spans the operations it runs."""
        return (i < len(self.op_codes) and self.op_codes[i] in
                CONTAINER_CODES) or any(c in self.ops[i][2]
                                        for c in _CONTAINERS)


# ---------------------------------------------------------------------------
# scope of an op_name path
# ---------------------------------------------------------------------------

def phase_and_scope(path: str) -> Tuple[str, str]:
    """(outermost phase scope or `other`, innermost registered non-phase
    scope or `unscoped`) of an HLO `op_name` path."""
    parts = path.split("/")
    phase = next((p for p in parts if p in PHASES), OTHER)
    scope = next((p for p in reversed(parts) if p in LAYER_SCOPES),
                 UNSCOPED)
    return phase, scope


def module_base(module: str) -> str:
    """`jit_generate(4646...)` -> `jit_generate`."""
    return module.split("(")[0]


def once_a_pass(path: str) -> bool:
    """Whether an op in the decode phase runs once a decode pass: no loop
    is nested between the `decode` scope and it."""
    parts = path.split("/")
    return "decode" in parts and \
        "while" not in parts[parts.index("decode") + 1:]


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def reduce(t: ScopedTrace, top: int = 10) -> Optional[dict]:
    """Scope seconds, per-executable scoped seconds, the decode passes
    and the program-labelled idle gaps over the traced window; None
    where the trace holds no window or no device operation."""
    w = [(s, e) for s, e, n in t.spans if n == WINDOW_SPAN]
    if not w or not t.ops:
        return None
    lo, hi = min(s for s, _ in w), max(e for _, e in w)
    scopes: Dict[str, Dict[str, float]] = {}
    scoped: Dict[str, List[float]] = {}
    by_op: Dict[Tuple[str, str, str, str], float] = {}
    sample_runs: Dict[Tuple[str, str], int] = {}
    leaf = 0.0
    for i, ((s, e, name), mod, path) in enumerate(
            zip(t.ops, t.op_modules, t.op_paths)):
        d = min(e, hi) - max(s, lo)
        if d <= 0 or t.is_container(i):
            continue
        leaf += d
        phase, scope = phase_and_scope(path)
        by = scopes.setdefault(phase, {})
        by[scope] = by.get(scope, 0.0) + d
        in_module = scoped.setdefault(module_base(mod), [0.0, 0.0])
        in_module[1] += d
        if scope != UNSCOPED:
            in_module[0] += d
        k = (op_name(name), module_base(mod), phase, scope)
        by_op[k] = by_op.get(k, 0.0) + d
        if scope == "sample" and phase == "decode" and once_a_pass(path) \
                and not (i < len(t.op_inherited) and t.op_inherited[i]):
            run = (module_base(mod), name.split(" = ")[0])
            sample_runs[run] = sample_runs.get(run, 0) + 1
    program_spans = [sp for sp in t.spans if sp[2] in PROGRAM_SPANS]
    gaps = sorted(gaps_ns(t.ops, lo, hi), key=lambda g: g[0] - g[1])
    ranked = sorted(by_op.items(), key=lambda x: -x[1])
    return {
        "leaf_s": leaf * 1e-9,
        "scopes_s": {p: {k: v * 1e-9 for k, v in by.items()}
                     for p, by in scopes.items()},
        "scoped_s": {k: [a * 1e-9, b * 1e-9] for k, (a, b) in
                     scoped.items()},
        "top_ops": [[n, m, p, sc, v * 1e-9]
                    for (n, m, p, sc), v in ranked[:top]],
        "unscoped_ops": [[n, m, p, v * 1e-9] for (n, m, p, sc), v in
                         ranked if sc == UNSCOPED
                         and v >= 0.01 * scoped[m][1]],
        "decode_passes": max(sample_runs.values(), default=0)
        // t.n_devices,
        "idle_gaps_program": [[_label(program_spans, (s + e) / 2),
                               (e - s) * 1e-9] for s, e in gaps[:top]],
    }


# ---------------------------------------------------------------------------
# the trace file
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None):
    """(field number, value) of one protobuf message in buf[lo:hi]: an
    int for a varint or fixed field, a (start, end) slice for a
    length-delimited one."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} at {i}")
        yield key >> 3, v


def _first(buf, rng, number):
    return next((v for n, v in _fields(buf, *rng) if n == number), None)


def _text(buf, rng) -> str:
    return buf[rng[0]:rng[1]].decode("utf-8", "replace") if rng else ""


def hlo_instructions(hlo_proto: bytes
                     ) -> Dict[str, Tuple[str, str, bool]]:
    """Instruction name -> (`op_name` metadata, opcode, whether the path
    is inherited), over every computation of a serialized `HloProto`
    (hlo_module 1; module: computations 3; computation: instructions 2;
    instruction: name 1, opcode 2, metadata 7; metadata: op_name 2).  An
    instruction without an `op_name` inherits `<phase>/<scope>`: the
    phase the computation's named instructions agree on, ignoring those
    in none, and the scope every named instruction but the parameters
    (whose `op_name` is their argument's name) agrees on."""
    buf = hlo_proto
    module = _first(buf, (0, len(buf)), 1)
    out: Dict[str, Tuple[str, str, bool]] = {}
    if module is None:
        return out
    for n, comp in _fields(buf, *module):
        if n != 3:
            continue
        rows = []
        for m, ins in _fields(buf, *comp):
            if m != 2:
                continue
            name = meta = code = None
            for k, v in _fields(buf, *ins):
                if k == 1:
                    name = _text(buf, v)
                elif k == 2:
                    code = _text(buf, v)
                elif k == 7:
                    meta = v
            if name is not None:
                rows.append((name, _text(buf, _first(buf, meta, 2))
                             if meta else "", code or ""))
        named = [phase_and_scope(p) for _, p, code in rows
                 if p and code != "parameter"]
        phases = {ph for ph, _ in named} - {OTHER}
        scopes = {sc for _, sc in named} \
            if all(sc != UNSCOPED for _, sc in named) else set()
        inherit = "/".join(x.pop() for x in (phases, scopes) if len(x) == 1)
        for name, path, code in rows:
            out[name] = (path or inherit, code, not path)
    return out


def program_hlo(xspace: bytes
                ) -> Dict[str, Dict[str, Tuple[str, str, bool]]]:
    """Module execution name (`jit_generate(<program id>)`, as the
    `XLA Modules` events name it) -> `hlo_instructions` of it, from
    the `/host:metadata` plane of a serialized XSpace (planes 1; plane:
    name 2, event_metadata 4, stat_metadata 5; map entry: value 2;
    event metadata: name 2, stats 5; stat: metadata_id 1, bytes 6;
    stat metadata: id 1, name 2)."""
    buf = xspace
    out: Dict[str, Dict[str, Tuple[str, str, bool]]] = {}
    for n, plane in _fields(buf):
        if n != 1 or _text(buf, _first(buf, plane, 2)) != "/host:metadata":
            continue
        stat_names, events = {}, []
        for k, v in _fields(buf, *plane):
            if k in (4, 5):
                value = _first(buf, v, 2)
                if value is None:
                    continue
                if k == 5:
                    stat_names[_first(buf, value, 1)] = _text(
                        buf, _first(buf, value, 2))
                else:
                    events.append(value)
        for ev in events:
            name, protos = "", []
            for k, v in _fields(buf, *ev):
                if k == 2:
                    name = _text(buf, v)
                elif k == 5:
                    protos.append(v)
            for st in protos:
                if stat_names.get(_first(buf, st, 1)) == "Hlo Proto":
                    raw = _first(buf, st, 6)
                    if raw:
                        out[name] = hlo_instructions(buf[raw[0]:raw[1]])
    return out


def load(path: pathlib.Path) -> ScopedTrace:
    """Device operations with their module execution, scope path and
    opcode, and the window and program spans, from one `.xplane.pb`."""
    import jax

    raw = path.read_bytes()
    hlo = program_hlo(raw)
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    planes = list(data.planes)
    tpus = [p for p in planes if p.name.startswith("/device:TPU:")
            and p.name[len("/device:TPU:"):].isdigit()]
    ops, mods = [], []
    for plane in tpus:
        lines = {line.name: line for line in plane.lines}
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in (lines["XLA Modules"].events
                                   if "XLA Modules" in lines else ()))
        starts = [m[0] for m in modules]
        for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
            s = e.start_ns
            j = bisect.bisect_right(starts, s) - 1
            ops.append((s, s + e.duration_ns, e.name))
            mods.append(modules[j][2] if j >= 0 and s < modules[j][1]
                        else "")
    names = set(PROGRAM_SPANS) | {WINDOW_SPAN}
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                if e.name in names:
                    spans.append(iv)
                elif not tpus:             # a CPU run: XLA's host thunks
                    st = dict(e.stats)
                    if "hlo_op" in st:
                        ops.append(iv)
                        mods.append(f"{st['hlo_module']}({st['program_id']})")
    found = [hlo.get(mod, {}).get(
        name.split(" = ")[0].lstrip("%").strip(), ("", "", False))
        for (_, _, name), mod in zip(ops, mods)]
    return ScopedTrace(ops=ops, op_modules=mods,
                       op_paths=[f[0] for f in found], spans=spans,
                       n_devices=max(len(tpus), 1),
                       op_codes=[f[1] for f in found],
                       op_inherited=[f[2] for f in found])


def newest_trace(directory: pathlib.Path = TRACE_DIR
                 ) -> Optional[pathlib.Path]:
    files = sorted(directory.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime) \
        if directory.exists() else []
    return files[-1] if files else None


@functools.lru_cache(maxsize=4)
def _reduced(path: str, mtime: float) -> Optional[dict]:
    """A trace it cannot read fails the run, which would otherwise look
    like one of a program without scopes."""
    out = reduce(load(pathlib.Path(path)))
    log(scopes=out)
    return out


def reduction() -> Optional[dict]:
    """`reduce` of the newest trace under `.bench_trace/`, read once."""
    path = newest_trace()
    return None if path is None else _reduced(str(path),
                                              path.stat().st_mtime)


def read(run) -> Optional[dict]:
    """The scope reduction of a traced rollout run's trace, or None (an
    untraced run, another kind, a program without scopes)."""
    if run.kind != "rollout" or not run.trace or not PHASES:
        return None
    out = reduction()
    if not out or not any(s != UNSCOPED for by in out["scopes_s"].values()
                          for s in by):
        return None
    return out


def per_pass_ms(run, *scopes: str) -> Optional[float]:
    """Decode-phase device milliseconds under `scopes` per decode pass,
    over the traced window."""
    r = read(run)
    if r is None or not r["decode_passes"]:
        return None
    decode = r["scopes_s"].get("decode", {})
    if not any(s in decode for s in scopes):
        return None
    return 1e3 * sum(decode.get(s, 0.0) for s in scopes) / r["decode_passes"]
