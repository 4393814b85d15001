"""The program's names for its own work (`repro.obs.scopes`): device
scopes in the compiled HLO of a tiny paged fp8 `generate`, the decode
loop's pass counter, and the program spans of an RL step and of the
serving engine on the profiler's host plane."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_serving_config
from repro.core.precision import FULL_FP8_ROLLOUT
from repro.data import tasks
from repro.models import init_params
from repro.obs import DEVICE_SCOPES, PHASES, PROGRAM_SPANS, scope, span
from repro.rl.rollout import SamplerConfig, generate
from repro.rl.weight_sync import _quantize_params, sync_policy_weights

NEW = 6


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_serving_config()
    params = init_params(cfg, jax.random.key(0))
    roll, _ = sync_policy_weights(params, FULL_FP8_ROLLOUT)
    prompts = jnp.asarray(np.tile(np.arange(1, 17, dtype=np.int32) % 19 + 3,
                                  (2, 1)))
    lengths = jnp.array([8, 12], jnp.int32)
    return cfg, params, roll, prompts, lengths


def _generate(tiny, **sampler):
    cfg, _, roll, prompts, lengths = tiny
    kw = dict(cfg=cfg, precision=FULL_FP8_ROLLOUT,
              sampler=SamplerConfig(max_new_tokens=NEW, **sampler),
              page_size=4, num_samples_per_prompt=2, shared_prefix_blocks=2)
    return roll, prompts, lengths, kw


@pytest.fixture(scope="module")
def hlo(tiny):
    """Compiled HLO text of the tiny generate and of the weight sync."""
    roll, prompts, lengths, kw = _generate(tiny)
    gen = generate.lower(roll, prompts, lengths, jax.random.key(1),
                         **kw).compile().as_text()
    sync = _quantize_params.lower(tiny[1], FULL_FP8_ROLLOUT) \
        .compile().as_text()
    return gen, sync


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def test_every_registered_scope_reaches_the_compiled_hlo(hlo):
    seen = {part for path in _op_names(hlo[0] + hlo[1])
            for part in path.split("/")}
    assert [s for s in DEVICE_SCOPES if s not in seen] == []
    # the sync's scope is its own executable's, not generate's
    assert "quantize" not in {p for path in _op_names(hlo[0])
                              for p in path.split("/")}


def _decode_body(text):
    """(opcode, op_name, root opcode of a fusion's computation) of every
    instruction of the decode loop's body and of the loops nested in it;
    fusion bodies left out."""
    comps = {}
    for block in re.split(r"\n(?=\S)", text):
        m = re.match(r"(?:ENTRY )?%?(\S+)", block)
        if m:
            comps[m.group(1)] = block
    outer = [re.search(r"body=%?([\w.\-]+)", line).group(1)
             for block in comps.values() for line in block.split("\n")
             if " while(" in line and 'op_name="jit(generate)/while"' in line]
    assert len(outer) == 1
    seen, todo, out = set(), outer, []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name].split("\n")[1:]:
            todo += [c for c in re.findall(
                r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)", line)
                if not c.startswith("fused")]
            m = re.match(r"\s*(?:ROOT )?%?\S+ = .*? ([a-z][\w-]*)\(", line)
            if m:
                path = re.search(r'op_name="([^"]*)"', line)
                fused = re.search(r"calls=%?([\w.\-]+)", line)
                root = re.search(r"\n\s*ROOT %?\S+ = .*? ([a-z][\w-]*)\(",
                                 comps[fused.group(1)]) if fused else None
                out.append((m.group(1), path.group(1) if path else "",
                            root.group(1) if root else None))
    return out


# the root opcodes of the fusions XLA builds from instructions it made
# itself (layout copies, bitcasts, dtype converts, the scan carry's slices
# and updates, the CPU backend's rewrites of reductions), which carry no
# op_name
XLA_MADE = {"bitcast", "broadcast", "concatenate", "convert", "copy",
            "dynamic-slice", "dynamic-update-slice", "reduce",
            "reduce-window", "transpose"}


def _xla_named(path):
    """Named instructions that `lax.scan` or XLA made, not the program: a
    scan's own counter and bound (`.../while/body/add`,
    `.../while/cond/lt`), and the converts the CPU backend puts around
    bf16 arithmetic, which take the name of the call they sit in
    (`.../closed_call`)."""
    parts = path.split("/")
    return parts[-1] == "closed_call" or (
        parts[-3] == "while" and parts[-2] in ("body", "cond"))


@pytest.mark.parametrize("opcode", ["dot", "fusion", "unnamed fusion"])
def test_decode_body_ops_carry_their_scopes(hlo, opcode):
    """Every matmul of a decode pass carries the layer scope it belongs
    to; every fusion with an `op_name` carries the decode phase and a
    registered layer scope, unless `lax.scan` or XLA made its root; a
    fusion without one (it takes its root's) is one XLA built from its
    own copies, layout changes and rewrites."""
    body = _decode_body(hlo[0])
    layer = set(DEVICE_SCOPES) - set(PHASES)
    if opcode == "dot":
        ops = [p for code, p, _ in body if code == "dot" or
               code == "fusion" and p.endswith("/dot_general")]
        assert ops
        for path in ops:
            parts = path.split("/")
            assert "decode" in parts and \
                {"matmul", "attention", "head"} & set(parts), path
    elif opcode == "fusion":
        named = [p for code, p, _ in body if code == "fusion" and p]
        assert named
        assert [p for p in named if "decode" not in p.split("/") or
                not (layer & set(p.split("/")) or _xla_named(p))] == []
    else:
        roots = [r for code, p, r in body if code == "fusion" and not p]
        assert roots
        assert {r for r in roots if r not in XLA_MADE} == set()


def test_decode_passes_count_the_loop_when_eos_is_held_off(tiny):
    roll, prompts, lengths, kw = _generate(tiny, eos_id=-1)
    t = generate(roll, prompts, lengths, jax.random.key(1), **kw)
    assert int(t.decode_passes) == NEW
    assert np.all(np.asarray(t.response_lengths) == NEW)


def test_decode_passes_stop_with_the_last_sequence(tiny):
    """Greedy decoding, then an EOS every sequence samples before its
    budget: the loop stops after the last sequence's EOS."""
    roll, prompts, lengths, kw = _generate(tiny, eos_id=-1, temperature=0.0)
    held = generate(roll, prompts, lengths, jax.random.key(1), **kw)
    rows = np.asarray(held.response_tokens)[:, : NEW - 1]
    firsts = {t: max(int(np.argmax(r == t)) for r in rows)
              for t in np.unique(rows) if all((r == t).any() for r in rows)}
    assert firsts, "no token ends every greedy row early"
    eos = min(firsts, key=firsts.get)
    _, _, _, kw = _generate(tiny, eos_id=int(eos), temperature=0.0)
    t = generate(roll, prompts, lengths, jax.random.key(1), **kw)
    lens = np.asarray(t.response_lengths)
    assert int(t.decode_passes) == lens.max() == firsts[eos] + 1 < NEW


def test_scope_and_span_names_are_registered():
    with pytest.raises(AssertionError):
        scope("kv_gather")
    with pytest.raises(AssertionError):
        span("rollout")
    assert len(set(DEVICE_SCOPES)) == len(DEVICE_SCOPES)
    assert set(PHASES) <= set(DEVICE_SCOPES)
    assert len(set(PROGRAM_SPANS)) == len(PROGRAM_SPANS)


# ---------------------------------------------------------------------------
# program spans on the profiler's host plane
# ---------------------------------------------------------------------------

def _host_spans(directory, names):
    import pathlib

    path = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for plane in data.planes if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name in names)


RL_SPANS = ["rl.prompts", "rl.sync", "rl.rollout", "rl.rewards",
            "rl.update", "rl.calibrate", "rl.checkpoint"]


def test_train_step_phases_are_spans_in_order(tmp_path):
    from repro.core.precision import FP8_LINEAR_ROLLOUT
    from repro.rl import RLConfig, RLTrainer

    tr = RLTrainer(tiny_serving_config(), RLConfig(
        precision=FP8_LINEAR_ROLLOUT, prompt_batch=2, n_per_prompt=2,
        max_new_tokens=4, seed=0))
    tr.train_step()                                   # compile outside
    with jax.profiler.trace(str(tmp_path)):
        m = tr.train_step()
    spans = _host_spans(tmp_path, set(RL_SPANS) | {"weight_sync"})
    assert [n for _, _, n in spans if n != "weight_sync"] == RL_SPANS
    sync = [(s, e) for s, e, n in spans if n == "rl.sync"][0]
    ws = [(s, e) for s, e, n in spans if n == "weight_sync"]
    assert len(ws) == 1 and sync[0] <= ws[0][0] <= ws[0][1] <= sync[1]
    # the host-clock phase times are still the trainer's own
    assert m["step_s"] >= m["rollout_s"] + m["update_s"] > 0


def test_engine_steps_are_spans_and_carry_wall_bounds(tmp_path):
    from repro.core import BF16_ROLLOUT
    from repro.obs import StepEvent, StepTracer, chrome_trace
    from repro.serving import ServingEngine

    cfg = tiny_serving_config()
    tracer = StepTracer()
    eng = ServingEngine(init_params(cfg, jax.random.key(0)), cfg,
                        BF16_ROLLOUT, max_slots=2, max_seq_len=32,
                        tracer=tracer)
    for i in range(2):
        eng.submit(tasks.random_prompt(i, 6), max_new=3, rid=i)
    with jax.profiler.trace(str(tmp_path)):
        eng.run(max_steps=50)
    steps = [e for e in tracer.events if isinstance(e, StepEvent)]
    assert len(_host_spans(tmp_path, {"engine.step"})) == len(steps) > 1
    for a, b in zip(steps, steps[1:]):
        assert a.wall_start_ns <= a.wall_end_ns <= b.wall_start_ns

    token = chrome_trace(tracer.events)
    wall = chrome_trace(tracer.events, clock="wall")
    assert token["otherData"]["clock"].startswith("token")
    assert wall["otherData"]["clock"].startswith("host wall clock")
    spans = [r for r in wall["traceEvents"] if r["ph"] == "X"]
    starts = {e.wall_start_ns / 1e3 for e in steps}
    assert spans and all(r["ts"] in starts for r in spans)
    # events without wall bounds keep the whole export on the token clock
    old = [dataclasses.replace(e, wall_start_ns=None, wall_end_ns=None)
           if isinstance(e, StepEvent) else e for e in tracer.events]
    assert chrome_trace(old, clock="wall") == token


def test_serve_writes_its_trace_on_the_wall_clock(tmp_path):
    import json
    import time

    from repro.launch import serve

    out = tmp_path / "serve.trace.json"
    before = time.perf_counter_ns() / 1e3
    serve.main(["--reduced", "--layers", "1", "--requests", "2",
                "--max-new", "3", "--trace-out", str(out)])
    after = time.perf_counter_ns() / 1e3
    spans = [r for r in json.loads(out.read_text())["traceEvents"]
             if r["ph"] == "X"]
    assert spans and all(before <= r["ts"] and r["ts"] + r["dur"] <= after
                         for r in spans)
