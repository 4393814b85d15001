"""The check of each tiny cell passes on the sound program and fails with
the timed path broken underneath: a token altered where it is produced,
half of the batch left out, a step that returns its state unchanged.
(One chip: no exchange between chips to leave out.)"""
from __future__ import annotations

import time

import jax
import numpy as np
import pytest

import bench_tiny

SEED = 2**31 + 11


@pytest.fixture(autouse=True)
def _no_cache(monkeypatch):
    yield from bench_tiny.no_compile_cache(monkeypatch)


def measure(kind):
    run = bench_tiny.run_module()
    return run.measure(bench_tiny.cell(kind), SEED, 0.2, False,
                       jax.devices(), bench_tiny.PEAKS,
                       t_start=time.perf_counter())


def _alter(traj):
    tok = traj.response_tokens
    return traj._replace(response_tokens=tok.at[:, tok.shape[1] // 2].set(
        bench_tiny.CONFIG["vocab_size"] - 1))


def _half(traj):
    n = traj.response_mask.shape[0]
    keep = (np.arange(n) < n // 2)[:, None]
    return traj._replace(response_mask=traj.response_mask * keep)


@pytest.mark.parametrize("fault", [None, "token_altered", "half_batch"])
def test_rollout_check(monkeypatch, fault):
    import repro.rl.rollout as rollout_mod

    real = rollout_mod.generate
    if fault:
        plant = {"token_altered": _alter, "half_batch": _half}[fault]
        monkeypatch.setattr(rollout_mod, "generate",
                            lambda *a, **k: plant(real(*a, **k)))
    result = measure("rollout")
    assert result["correct"] is (fault is None), result["checks"]
    assert result["metrics"]["rollout_tokens_per_s"]["value"] > 0


def _update_fault(fault, real_build):
    def build(cfg, rl):
        real = real_build(cfg, rl)

        def update(params, opt_state, batch):
            if fault == "unchanged":
                copy = jax.tree.map(lambda x: x.copy(), (params, opt_state))
                _, _, stats = real(*copy, batch)
                return params, opt_state, stats
            n = batch["mask"].shape[0]
            keep = (np.arange(n) < n // 2)[:, None]
            return real(params, opt_state, dict(batch,
                                                mask=batch["mask"] * keep))
        return update
    return build


@pytest.mark.parametrize("fault", [None, "token_altered", "half_batch",
                                   "unchanged"])
def test_rl_step_check(monkeypatch, fault):
    import repro.rl.trainer as trainer_mod

    if fault == "token_altered":
        real = trainer_mod.generate
        monkeypatch.setattr(trainer_mod, "generate",
                            lambda *a, **k: _alter(real(*a, **k)))
    elif fault:
        monkeypatch.setattr(trainer_mod, "build_update_fn",
                            _update_fault(fault, trainer_mod.build_update_fn))
    result = measure("rl_step")
    assert result["correct"] is (fault is None), result["checks"]
    assert result["metrics"]["rl_step_s"]["value"] > 0
