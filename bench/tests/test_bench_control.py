"""The controls and planted faults at a size a test run holds: on a tiny
cell each reads well above the program's own reading of the number that
is to catch it (bench/control.py makes the same readings on the chip at
each cell's size, and PERF.md gives them)."""
from __future__ import annotations

import pytest

import bench_tiny
from harness import control

SEEDS = {"weights": 5, "traffic": 6, "rl": 7, "check": 8}


@pytest.fixture(autouse=True)
def _no_cache(monkeypatch):
    yield from bench_tiny.no_compile_cache(monkeypatch)


def test_rollout_controls_and_faults():
    out = control.rollout_readings(bench_tiny.cell("rollout"), SEEDS, 0.0)
    prog = out["program"]
    assert prog["missing_tokens"] == 0
    assert out["int4"]["logp_gap_max"] > 3 * prog["logp_gap_max"]
    assert out["token_altered"]["logp_gap_max"] > 3 * prog["logp_gap_max"]
    assert out["half_batch"]["missing_tokens"] > 0


def test_rl_step_controls_and_faults():
    out = control.rl_step_readings(bench_tiny.cell("rl_step"), SEEDS)
    prog = out["program"]
    assert prog["reward_gap"] == 0
    assert out["fp8"]["grad_gap"] > 3 * prog["grad_gap"]
    assert out["int4"]["logp_gap_max"] > 3 * prog["logp_gap_max"]
    assert out["token_altered"]["logp_gap_max"] > 3 * prog["logp_gap_max"]
    assert out["half_batch"]["loss_gap"] > 3 * prog["loss_gap"]
