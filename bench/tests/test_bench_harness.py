"""The harness refuses a machine without a TPU, and finds every file a
cell, configuration, traffic mix or metric of BENCHMARK.json names."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

import bench_tiny
from harness import common

SPEC = common.load_json(common.CHECKOUT / "BENCHMARK.json")


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(common.BENCH / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=common.CHECKOUT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_unknown_device_kind_is_an_error():
    with pytest.raises(common.BenchError):
        common.peaks_for("TPU v9 imaginary")
    assert common.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files(w):
    cell = common.find_cell(w["name"])
    assert cell.traffic["kind"] in ("rollout", "rl_step")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(common.reader(m["name"]))
    for number in cell.limits["numbers"].values():
        assert number["limit"] >= 0


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files_state_their_cut(c):
    f = common.load_json(common.CHECKOUT / c["file"])
    assert f["source"] == c["source"]
    assert f["reduced"] == c["reduced"]
    for key in c["reduced"]:
        assert f[key] < f["published"][key]


def _run(kind, **kw):
    steps = [dict(t0=0.0, t1=0.5, tokens=100.0, flops=1e12, least_s=0.1,
                  sync_ms=10.0, rollout_s=0.2, update_s=0.1, step_s=0.5)] * 4
    return common.Run(kind=kind, setup_s=12.0, window_s=2.0, steps=steps,
                      peaks=bench_tiny.PEAKS, **kw)


def test_readers_read_their_own_kind_and_nothing_else():
    r = common.reader
    assert r("rl_step_s")(_run("rl_step")) == 0.5
    assert r("rl_step_s")(_run("rollout")) is None
    assert r("rollout_tokens_per_s")(_run("rollout")) == 200.0
    assert r("host_s.rl_step")(_run("rl_step")) == pytest.approx(0.19)
    assert r("mfu.rollout")(_run("rollout")) == pytest.approx(
        100 * 4e12 / (2.0 * 197e12))
    # per-layer readers of the trace find nothing in an untraced run
    assert r("idle_share.rollout")(_run("rollout")) is None
    assert r("generate_roofline.rollout")(_run("rollout")) is None
    traced = _run("rollout", trace={"busy_s": 1.5, "window_s": 2.0,
                                    "executables_s": {"jit_generate": 1.0}})
    assert r("idle_share.rollout")(traced) == pytest.approx(25.0)
    assert r("generate_roofline.rollout")(traced) == pytest.approx(40.0)


def _steps_of(monkeypatch, seconds_each, counter=None, build_at=None):
    """The window over steps of the given lengths (one number, or one per
    step), on a clock that only the steps advance."""
    import contextlib
    import itertools
    import time

    now = [100.0]
    monkeypatch.setattr(time, "perf_counter", lambda: now[0])
    lengths = (itertools.repeat(seconds_each)
               if isinstance(seconds_each, float) else iter(seconds_each))

    def step(k):
        t0 = now[0]
        if k == build_at:
            counter.append(0.1)
        now[0] += next(lengths)
        return {"t0": t0, "t1": now[0]}

    return common.window(step, 0.25, contextlib.nullcontext,
                         counter if counter is not None else [])


def test_the_window_runs_whole_steps_and_never_overruns(monkeypatch):
    steps, window_s = _steps_of(monkeypatch, 0.06)
    assert len(steps) == 4 and window_s == pytest.approx(0.24)
    # a step is started only while one as long as the last still fits
    steps, window_s = _steps_of(monkeypatch, [0.1, 0.1, 0.1])
    assert len(steps) == 2 and window_s == pytest.approx(0.2)
    # a step longer than the window still runs, once
    steps, window_s = _steps_of(monkeypatch, 0.3)
    assert len(steps) == 1 and window_s == pytest.approx(0.3)


def test_a_compile_inside_the_window_fails_the_run(monkeypatch):
    with pytest.raises(common.BenchError):
        _steps_of(monkeypatch, 0.05, counter=[], build_at=1)
