"""Traffic of kind `rl_step`: whole RL steps through `RLTrainer.train_step`.

Set-up builds one trainer from the seed, drives it through its first
three steps (the first compiles every program the window will run), and
hands that same trainer to the window.  The benchmark wraps the trainer's
calls to `sync_policy_weights` and `generate` in spans of its own and
records what the rollout served.

The check follows those first three steps with the plain reference:
rewards, advantages, the DAPO loss with TIS and AdamW in float32 from the
same weights and the served tokens.  It compares each step's loss and
reward, the first gradient as the optimizer got it (read from its first
moment after step 1), the change of the parameters after step 3, and the
log-probability the rollout reported for each served token.
"""
from __future__ import annotations

import time

import numpy as np

from . import counts, program, rl_reference, weights
from .common import Checked, Run, log, window

CHECKED_STEPS = 3


class Recorder:
    """Spans around the trainer's sync and rollout, and a record of what
    each rollout was given and served."""

    def __init__(self):
        import repro.rl.trainer as trainer_mod

        self.mod = trainer_mod
        self.calls = []
        self._gen = trainer_mod.generate
        self._sync = trainer_mod.sync_policy_weights
        trainer_mod.generate = self.generate
        trainer_mod.sync_policy_weights = self.sync

    def generate(self, params, prompts, lengths, *args, **kw):
        import jax

        with jax.profiler.TraceAnnotation("generate"):
            traj = self._gen(params, prompts, lengths, *args, **kw)
        self.calls.append((lengths, traj))
        return traj

    def sync(self, *args, **kw):
        import jax

        with jax.profiler.TraceAnnotation("sync"):
            return self._sync(*args, **kw)

    def close(self):
        self.mod.generate = self._gen
        self.mod.sync_policy_weights = self._sync


def rl_config(traffic: dict, seed: int):
    from repro.optim import AdamWConfig
    from repro.rl import RLConfig
    from repro.rl.loss import LossConfig

    o, lo = traffic["optimizer"], traffic["loss"]
    recipe = program.precision(traffic["recipe"]).replace(
        tis_clip=traffic["tis_clip"])
    return RLConfig(
        precision=recipe, prompt_batch=traffic["prompts"],
        n_per_prompt=traffic["samples_per_prompt"],
        max_prompt_len=traffic["max_prompt_len"],
        max_new_tokens=traffic["new_tokens"],
        temperature=traffic["temperature"], seed=seed,
        optimizer=AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"],
                              eps=o["eps"], grad_clip=o["grad_clip"]),
        loss=LossConfig(eps_low=lo["eps_low"], eps_high=lo["eps_high"]))


def _leaf_norms(tree, scale=1.0) -> dict:
    return {k: v * scale for k, v in rl_reference.leaf_norms(tree).items()}


class RLStep:
    """One trainer, stepped by set-up and the window."""

    def __init__(self, config: dict, traffic: dict, seeds: dict):
        from repro.rl import RLTrainer

        self.seeds = seeds
        self.cfg = program.arch(config)
        self.structure = program.structure(self.cfg)
        self.weights_spec = traffic.get("weights")
        self.rl = rl_config(traffic, seeds["rl"])
        self.rec = Recorder()
        self.trainer = RLTrainer(self.cfg, self.rl,
                                 params=self.make_weights())
        self.first_steps = []          # metrics of the checked steps
        self.grad = self.change = None

    def make_weights(self):
        return weights.make(self.structure, self.seeds["weights"],
                            self.weights_spec)

    def step(self) -> dict:
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("train_step"):
            m = self.trainer.train_step()
        t1 = time.perf_counter()
        return dict(m, t0=t0, t1=t1)

    def warm(self):
        """The first three steps: the checked ones, the first of which
        compiles everything."""
        import jax
        import jax.numpy as jnp

        b1 = self.rl.optimizer.b1
        for k in range(CHECKED_STEPS):
            self.first_steps.append(self.step())
            if k == 0:
                self.grad = _leaf_norms(self.trainer.opt_state.m,
                                        1.0 / (1.0 - b1))
        p0 = self.make_weights()
        self.change = _leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            self.trainer.params, p0))
        del p0

    def served(self, k: int) -> dict:
        """Step k's rollout as host arrays."""
        _, traj = self.rec.calls[k]
        return {
            "prompts": np.asarray(traj.prompt_tokens),
            "lengths": np.asarray(traj.prompt_lengths),
            "tokens": np.asarray(traj.response_tokens),
            "mask": np.asarray(traj.response_mask),
            "logps": np.asarray(traj.rollout_logps),
            "rlen": np.asarray(traj.response_lengths)}


def step_flops(dims: counts.Dims, prompt_lengths, response_lengths,
               group: int) -> float:
    """Model operations of one RL step: the rollout's prefill and the
    forward passes that produced each served token after the first, then
    the update's forward and backward over prompt plus response."""
    prompt_lengths = [int(x) for x in prompt_lengths]
    rlen = [int(x) for x in response_lengths]
    per_row_prompt = np.repeat(prompt_lengths, group)
    prefill = counts.forward_flops(dims, sum(prompt_lengths),
                                   counts.causal_attended(prompt_lengths),
                                   len(prompt_lengths))
    decode = sum(counts.forward_flops(dims, 1, int(p) + i, 1)
                 for p, n in zip(per_row_prompt, rlen) for i in range(1, n))
    seqs = [int(p) + n for p, n in zip(per_row_prompt, rlen)]
    return prefill + decode + counts.update_flops(dims, seqs)


def run(cell, seeds: dict, seconds: float, tracer, t_start: float,
        counter: list, devices, peaks: dict) -> tuple:
    from .common import memory_peak_bytes

    r = RLStep(cell.config, cell.traffic, seeds)
    r.warm()
    setup_s = time.perf_counter() - t_start
    steps, window_s = window(lambda k: r.step(), seconds, tracer, counter)
    log(setup_s=setup_s, steps_with_loss=sum(s["loss"] != 0 for s in steps),
        checked_rewards=[s["reward_mean"] for s in r.first_steps],
        checked_losses=[s["loss"] for s in r.first_steps],
        first_steps=[{k: s[k] for k in ("sync_ms", "rollout_s", "update_s",
                                        "step_s")} for s in steps[:3]])
    mem = memory_peak_bytes(devices)
    dims = counts.Dims.from_config(cell.config)
    group = cell.traffic["samples_per_prompt"]
    for s, (lengths, traj) in zip(steps, r.rec.calls[CHECKED_STEPS:]):
        s["flops"] = step_flops(dims, np.asarray(lengths),
                                np.asarray(traj.response_lengths), group)
    run = Run(kind="rl_step", setup_s=setup_s, window_s=window_s,
              steps=steps, peaks=peaks)
    checked = check(r, cell)
    return run, checked, len(steps) * cell.traffic["prompts"] * group, mem


def reference_batches(served: list, group: int):
    """The reference's update inputs for each checked step, and the
    rewards' means as float32."""
    import jax.numpy as jnp

    batches, reward_means = [], []
    for s in served:
        answers = [rl_reference.prompt_answer(s["prompts"][i, : s["lengths"][i]])
                   for i in range(len(s["lengths"]))]
        rewards = np.array([rl_reference.reward(a, s["tokens"][i, : s["rlen"][i]])
                            for i, a in enumerate(answers)], np.float32)
        adv, keep = rl_reference.advantages(rewards, group)
        batches.append({
            "packed": jnp.asarray(rl_reference.pack(
                s["prompts"], s["lengths"], s["tokens"])),
            "lengths": jnp.asarray(s["lengths"]),
            "response_mask": jnp.asarray(s["mask"]),
            "mask": jnp.asarray(s["mask"] * keep[:, None]),
            "advantages": jnp.asarray(adv),
            "rollout_logps": jnp.asarray(s["logps"])})
        reward_means.append(float(rewards.mean()))
    return batches, reward_means


def worst_leaf_gap(prog: dict, ref: dict, keys=None) -> float:
    """The largest gap between the program's and the reference's norm of a
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    keys = list(ref) if keys is None else keys
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys)


def program_record(r: RLStep) -> dict:
    """The program's side of the check: each checked step's loss and mean
    reward, the first gradient as its optimizer got it, the change of its
    parameters after the checked steps, and the rollout's log-probability
    of each served token."""
    return {"loss": [s["loss"] for s in r.first_steps],
            "reward_mean": [s["reward_mean"] for s in r.first_steps],
            "grad": r.grad, "change": r.change,
            "logps": [r.served(k)["logps"] for k in range(CHECKED_STEPS)]}


def compare(rec: dict, served: list, ref: dict, reward_means: list) -> dict:
    """The numbers of the check: a record (the program's, or a control's)
    against the reference's.  Leaves whose first reference gradient is
    under a thousandth of the median leaf's move by round-off alone under
    Adam, and are left out of the change."""
    med = float(np.median(list(ref["grad"].values())))
    moved = [k for k, g in ref["grad"].items() if g >= 1e-3 * med]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(rec["loss"], ref["loss"])),
        "reward_gap": max(abs(a - b) for a, b in
                          zip(rec["reward_mean"], reward_means)),
        "grad_gap": worst_leaf_gap(rec["grad"], ref["grad"]),
        "change_gap": worst_leaf_gap(rec["change"], ref["change"], moved),
        "logp_gap_max": max(
            float(np.max(np.abs(a - b)[s["mask"] > 0]))
            for a, b, s in zip(rec["logps"], ref["logps"], served)),
    }


def check(r: RLStep, cell) -> Checked:
    import gc

    served = [r.served(k) for k in range(CHECKED_STEPS)]
    prog = program_record(r)
    make = r.make_weights
    r.rec.close()
    r.rec.calls.clear()
    r.trainer = None
    gc.collect()
    batches, means = reference_batches(served,
                                       cell.traffic["samples_per_prompt"])
    ref = rl_reference.follow(make, batches, cell.config,
                              reference_rl(cell.traffic))
    numbers = compare(prog, served, ref, means)
    limits = {k: v["limit"] for k, v in cell.limits["numbers"].items()}
    return Checked(numbers=numbers, limits=limits)


def reference_rl(traffic: dict) -> dict:
    return {"optimizer": traffic["optimizer"], "loss": traffic["loss"],
            "tis_clip": traffic["tis_clip"]}
