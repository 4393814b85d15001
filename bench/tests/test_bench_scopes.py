"""Device time by the program's scopes (`harness/scopes.py`): the
reduction on a synthetic trace, the metric readers, the trace file of a
TPU and of a CPU run."""
from __future__ import annotations

import pathlib

import pytest

import bench_tiny
from harness import common, scopes, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
GEN = "jit_generate(7)"
D = "jit(generate)/while/body/decode/while/body/closed_call"
NEW_METRICS = ["kv_read_ms.rollout", "kv_write_ms.rollout",
               "attention_ms.rollout", "weight_dequant_ms.rollout",
               "matmul_ms.rollout", "unscoped_ms.rollout",
               "sync_device_ms.rollout", "decode_passes.rollout"]


def _synthetic(paths=True):
    """Three decode passes of a two-layer scan, a prefill and a fork, a
    weight sync and an XLA copy; a `while` spanning the passes."""
    ops = []

    def op(s, e, name, mod, path):
        ops.append((s, e, name, mod, path if paths else ""))

    op(0, 4, "fusion.1", GEN, "jit(generate)/prefill/while/body/kv_write/x")
    op(4, 5, "fusion.2", GEN, "jit(generate)/fork/kv_write/scatter")
    op(5, 35, "%while.3 = (s32[]) while((s32[]) %t), body=%b", GEN,
       "jit(generate)/while")
    for p in range(3):
        t = 5 + 10 * p
        for layer in range(2):
            # innermost wins: kv_read inside attention
            op(t, t + 2, "fusion.4", GEN, f"{D}/attention/kv_read/convert")
            op(t + 2, t + 3, "fusion.5", GEN, f"{D}/matmul/dot_general")
            t += 3
        op(t, t + 1, "fusion.6", GEN,
           "jit(generate)/while/body/decode/sample/argmax")
        # a loop nested in sampling runs more than once a pass
        op(t + 1, t + 1.5, "fusion.7", GEN, "jit(generate)/while/body/"
           "decode/sample/jit(_threefry_split)/while/body/add")
        op(t + 1.5, t + 2, "fusion.7", GEN, "jit(generate)/while/body/"
           "decode/sample/jit(_threefry_split)/while/body/add")
        op(t + 2, t + 3, "copy.8", GEN, "decode")     # XLA's carry copy
    for lo, hi in ((38, 40), (41, 42)):
        op(lo, hi, "fusion.9", "jit_quantize_params(9)",
           "jit(quantize_params)/quantize/reduce_max")
    op(60, 70, "fusion.1", GEN, "jit(generate)/prefill/while/body/kv_write")
    return scopes.ScopedTrace(
        ops=[o[:3] for o in ops], op_modules=[o[3] for o in ops],
        op_paths=[o[4] for o in ops],
        spans=[(0, 50, trace.WINDOW_SPAN), (37, 48, "rl.sync"),
               (37.5, 44, "weight_sync"), (30, 50, "not_a_program_span")])


def test_scopes_partition_the_leaf_time_by_phase_and_innermost_scope():
    r = scopes.reduce(_synthetic())
    ns = 1e-9
    assert r["scopes_s"] == {
        "prefill": {"kv_write": pytest.approx(4 * ns)},
        "fork": {"kv_write": pytest.approx(1 * ns)},
        "decode": {"kv_read": pytest.approx(12 * ns),
                   "matmul": pytest.approx(6 * ns),
                   "sample": pytest.approx(6 * ns),
                   "unscoped": pytest.approx(3 * ns)},
        "other": {"quantize": pytest.approx(3 * ns)}}
    # the `while` spans its passes and the op past the window is outside
    assert r["leaf_s"] == pytest.approx(35 * ns)
    assert sum(v for by in r["scopes_s"].values() for v in by.values()) \
        == pytest.approx(r["leaf_s"], rel=1e-12)
    assert r["scoped_s"] == {
        "jit_generate": [pytest.approx(29 * ns), pytest.approx(32 * ns)],
        "jit_quantize_params": [pytest.approx(3 * ns), pytest.approx(3 * ns)]}
    assert r["unscoped_ops"] == [["copy.8", "jit_generate", "decode",
                                  pytest.approx(3 * ns)]]
    assert r["top_ops"][:2] == [
        ["fusion.4", "jit_generate", "decode", "kv_read",
         pytest.approx(12 * ns)],
        ["fusion.5", "jit_generate", "decode", "matmul",
         pytest.approx(6 * ns)]]
    assert r["decode_passes"] == 3


def test_idle_gaps_are_trace_reduces_gaps_named_by_program_spans():
    t = _synthetic()
    r = scopes.reduce(t)
    old = trace.reduce(trace.Trace(ops=t.ops, modules=[], spans=t.spans))
    # trace.reduce keeps its keys; the gaps are its gaps
    assert set(old) == {"busy_s", "window_s", "executables_s", "device_ops",
                        "idle_gaps"}
    assert [g[1] for g in r["idle_gaps_program"]] == \
        [g[1] for g in old["idle_gaps"]]
    # 42-50: midpoint 46 in rl.sync alone; 35-38: 36.5 before any program
    # span; 40-41: 40.5 in weight_sync, inside rl.sync
    assert r["idle_gaps_program"] == [["rl.sync", pytest.approx(8e-9)],
                                      ["none", pytest.approx(3e-9)],
                                      ["weight_sync", pytest.approx(1e-9)]]


@pytest.mark.parametrize("path,want", [
    ("jit(generate)/while/body/decode/while/body/attention/kv_read/x",
     ("decode", "kv_read")),
    ("jit(generate)/prefill/head/matmul/dot_general", ("prefill", "matmul")),
    ("jit(generate)/while/body/decode/while/body/add", ("decode", "unscoped")),
    ("jit(generate)/sample/argmax", ("other", "sample")),
    ("", ("other", "unscoped")),
])
def test_phase_is_outermost_and_scope_innermost(path, want):
    assert scopes.phase_and_scope(path) == want


def test_reduce_finds_nothing_without_a_window_or_device_ops():
    t = _synthetic()
    t.spans = [sp for sp in t.spans if sp[2] != trace.WINDOW_SPAN]
    assert scopes.reduce(t) is None
    assert scopes.reduce(scopes.ScopedTrace([], [], [], [])) is None


def _run(kind="rollout", traced=True, steps=3):
    return common.Run(kind=kind, setup_s=1.0, window_s=1.0,
                      steps=[{"tokens": 1.0}] * steps,
                      peaks=bench_tiny.PEAKS,
                      trace={"busy_s": 1.0} if traced else None)


def test_metric_readers_read_the_scopes(monkeypatch):
    monkeypatch.setattr(scopes, "reduction",
                        lambda: scopes.reduce(_synthetic()))
    r = {m: common.reader(m)(_run(steps=1)) for m in NEW_METRICS}
    per_pass = 1e-9 * 1e3 / 3
    assert r["kv_read_ms.rollout"] == pytest.approx(12 * per_pass)
    assert r["matmul_ms.rollout"] == pytest.approx(6 * per_pass)
    assert r["kv_write_ms.rollout"] is None       # no decode-phase write
    assert r["weight_dequant_ms.rollout"] is None
    assert r["unscoped_ms.rollout"] == pytest.approx(3 * per_pass)
    assert r["sync_device_ms.rollout"] == pytest.approx(3e-6)
    assert r["decode_passes.rollout"] == 3


def test_unscoped_ms_counts_the_decode_phase_and_no_phase(monkeypatch):
    """XLA's carry copies of the decode loop fall in no phase; the
    prefill's unscoped work and every scoped op stay out."""
    monkeypatch.setattr(scopes, "reduction", lambda: {
        "decode_passes": 4,
        "scopes_s": {"decode": {"unscoped": 0.008, "matmul": 1.0},
                     "other": {"unscoped": 0.004, "quantize": 1.0},
                     "prefill": {"unscoped": 1.0}}})
    read = common.reader("unscoped_ms.rollout")
    assert read(_run()) == pytest.approx(3.0)


@pytest.mark.parametrize("case", ["no_scopes", "rl_step", "untraced"])
@pytest.mark.parametrize("metric", NEW_METRICS)
def test_metric_readers_find_nothing_to_read(monkeypatch, metric, case):
    monkeypatch.setattr(scopes, "reduction", lambda: scopes.reduce(
        _synthetic(paths=case != "no_scopes")))
    run = _run(kind="rl_step" if case == "rl_step" else "rollout",
               traced=case != "untraced")
    assert common.reader(metric)(run) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_metric_readers_fail_on_a_trace_they_cannot_read(monkeypatch,
                                                         tmp_path, metric):
    """A broken trace fails the traced run, where falling silent would
    read as a program without scopes."""
    bad = tmp_path / "broken.xplane.pb"
    bad.write_bytes(b"\x0a\xff\xff")
    monkeypatch.setattr(scopes, "newest_trace", lambda: bad)
    with pytest.raises(Exception):
        common.reader(metric)(_run())


def test_load_maps_tpu_ops_to_scopes_through_the_traced_hlo():
    """A TPU v5 lite trace of `data/tpu_scoped_loop.py`: five passes of
    a loop body scoped decode/kv_read and decode/matmul, which XLA fused
    into one matmul-rooted fusion beside a loop-carry copy; the copy has
    no `op_name` and takes the phase of its computation."""
    t = scopes.load(DATA / "tpu_scoped_loop.xplane.pb")
    assert t.n_devices == 1 and len(t.ops) == 16
    assert {m for m in t.op_modules} == {"jit_f(464609533526830781)"}
    body = [p for (_, _, n), p in zip(t.ops, t.op_paths)
            if n.startswith("%fusion.7 ")]
    assert body == ["jit(f)/while/body/closed_call/decode/matmul/"
                    "dot_general"] * 5
    assert [c for (_, _, n), c in zip(t.ops, t.op_codes)
            if n.startswith("%while ")] == ["while"]
    lo = min(s for s, _, _ in t.ops)
    t.spans.append((lo, lo + 10**9, trace.WINDOW_SPAN))
    r = scopes.reduce(t)
    assert set(r["scopes_s"]) == {"decode", "other"}

    def time_of(op):
        return pytest.approx(1e-9 * sum(e - s for (s, e, n) in t.ops
                                        if n.startswith(f"%{op} ")))

    assert r["scopes_s"]["decode"] == {"matmul": time_of("fusion.7"),
                                       "unscoped": time_of("copy.11")}


def test_a_cpu_trace_of_generate_counts_its_decode_passes(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.configs import tiny_serving_config
    from repro.core.precision import FULL_FP8_ROLLOUT
    from repro.models import init_params
    from repro.rl.rollout import SamplerConfig, generate
    from repro.rl.weight_sync import sync_policy_weights

    cfg = tiny_serving_config()
    params = init_params(cfg, jax.random.key(0))
    sampler = SamplerConfig(max_new_tokens=5, eos_id=-1)

    def step():
        roll, _ = sync_policy_weights(params, FULL_FP8_ROLLOUT)
        return jax.block_until_ready(generate(
            roll, jnp.full((2, 8), 5, jnp.int32), jnp.array([5, 8]),
            jax.random.key(1), cfg, FULL_FP8_ROLLOUT, sampler, page_size=4,
            num_samples_per_prompt=2, shared_prefix_blocks=1))

    step()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            traj = step()
    r = scopes.reduce(scopes.load(scopes.newest_trace(tmp_path)))
    assert r["decode_passes"] == int(traj.decode_passes) == 5
    assert sum(v for by in r["scopes_s"].values() for v in by.values()) \
        == pytest.approx(r["leaf_s"], rel=1e-9)
    decode = r["scopes_s"]["decode"]
    assert {"kv_read", "kv_write", "attention", "matmul", "weight_dequant",
            "act_quant", "sample", "head", "commit", "residual",
            "mlp"} <= set(decode)
    assert r["scopes_s"]["other"]["quantize"] > 0
    assert any(label == "weight_sync" for label, _ in r["idle_gaps_program"])
