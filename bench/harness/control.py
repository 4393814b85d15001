"""Readings that the correctness limits are set from.

For a seed, a reading is each number of the cell's check, computed three
ways against the same float32 reference:

- `program`: the program's timed path, as a benchmark run computes it;
- the controls: the reference put in the program's place one precision
  below what the configuration states (`fp8` below bf16, `int4` below
  fp8), on the same prompts and served tokens;
- the planted faults: a served token altered where it is produced, half
  of the batch left out (for the update, the loss's mean taken over the
  rest).  A step that returns its state unchanged reads 1 by the
  training measure and needs no run.

The benchmark's own runs never run these; `bench/control.py` runs them on
the chip, and `tests/test_bench_control.py` at a small size.
"""
from __future__ import annotations

import time

import numpy as np

from . import rl_reference, rl_step, rollout


def altered(tokens: np.ndarray, vocab: int) -> np.ndarray:
    """The fault `a token altered where it is produced`: the middle
    served token of every response becomes the vocabulary's last id."""
    out = tokens.copy()
    out[:, tokens.shape[1] // 2] = vocab - 1
    return out


def rollout_readings(cell, seeds: dict, seconds: float) -> dict:
    r = rollout.Rollout(cell.config, cell.traffic, seeds)
    r.step(rollout.WARM_STEP, keep=False)
    t0 = time.perf_counter()
    k = 0
    while k == 0 or time.perf_counter() - t0 < seconds:
        r.step(k)
        k += 1
    make = r.make_weights
    r.free()
    group = cell.traffic["samples_per_prompt"]
    picks = rollout.sample_rows(r.kept, cell.traffic["check"]["sequences"],
                                seeds["check"])
    prog, ref = rollout.served_logps(r.kept, picks, group, make, cell.config)
    out = {"program": rollout.gap_numbers(prog, ref)}
    out["program"]["missing_tokens"] = rollout.missing_tokens(r.kept)
    for quant in ("fp8", "int4"):
        _, low = rollout.served_logps(r.kept, picks, group, make,
                                      cell.config, quant)
        out[quant] = rollout.gap_numbers(low, ref)
    bad = [(k, tok, ln, dict(tr, tokens=altered(tr["tokens"],
                                                cell.config["vocab_size"])))
           for k, tok, ln, tr in r.kept]
    _, ref_bad = rollout.served_logps(bad, picks, group, make, cell.config)
    out["token_altered"] = rollout.gap_numbers(prog, ref_bad)
    half = [(k, tok, ln, dict(tr, mask=tr["mask"] * (
        np.arange(tr["mask"].shape[0]) < tr["mask"].shape[0] // 2)[:, None]))
        for k, tok, ln, tr in r.kept]
    out["half_batch"] = dict(out["program"],
                             missing_tokens=rollout.missing_tokens(half))
    return out


def rl_step_readings(cell, seeds: dict, seconds: float = 0.0) -> dict:
    """The RL step's readings come from its checked first steps, which
    set-up runs; they need no window (`seconds` is not used)."""
    import gc

    r = rl_step.RLStep(cell.config, cell.traffic, seeds)
    r.warm()
    served = [r.served(k) for k in range(rl_step.CHECKED_STEPS)]
    make = r.make_weights
    prog = rl_step.program_record(r)
    r.rec.close()
    r.trainer = None
    gc.collect()
    group = cell.traffic["samples_per_prompt"]
    rl = rl_step.reference_rl(cell.traffic)
    batches, means = rl_step.reference_batches(served, group)
    ref = rl_reference.follow(make, batches, cell.config, rl)
    out = {"program": rl_step.compare(prog, served, ref, means)}
    for quant in ("fp8", "int4"):
        low = rl_reference.follow(make, batches, cell.config, rl, quant)
        low["reward_mean"] = means
        out[quant] = rl_step.compare(low, served, ref, means)
    # a faulty program that trains on altered tokens agrees with a reference
    # given those tokens in all but the log-probabilities it reported
    bad = [dict(s, tokens=altered(s["tokens"], cell.config["vocab_size"]))
           for s in served]
    bad_batches, bad_means = rl_step.reference_batches(bad, group)
    ref_bad = rl_reference.follow(make, bad_batches, cell.config, rl)
    out["token_altered"] = rl_step.compare(
        dict(ref_bad, reward_mean=bad_means, logps=prog["logps"]), bad,
        ref_bad, bad_means)
    half = []
    for b in batches:
        keep = (np.arange(b["mask"].shape[0]) < b["mask"].shape[0] // 2)
        half.append(dict(b, mask=b["mask"] * keep[:, None]))
    low = rl_reference.follow(make, half, cell.config, rl)
    low["reward_mean"] = means
    out["half_batch"] = rl_step.compare(low, served, ref, means)
    return out
