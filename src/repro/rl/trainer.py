"""The RL trainer: DAPO loop with FP8 rollout (paper Fig 1 workflow).

Per step:
  1. weight sync      — quantize fresh BF16 policy into rollout params
  2. rollout          — n responses per prompt on the FP8 engine
  3. reward           — rule-based verifier (host)
  4. advantage        — group-relative (GRPO) + DAPO dynamic-sampling mask
  5. update           — token-level DAPO loss with TIS/MIS correction
  6. telemetry        — mismatch KL, reward, response length, accuracy
  7. checkpoint       — params + optimizer + data cursor + python rng

Both KV-scale calibration paradigms are supported via
`RLConfig.calibration` ("inference" | "trainer") — see rl/calibration.py.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.core.precision import PrecisionConfig
from repro.data import PromptPipeline
from repro.models import forward_train, init_params
from repro.obs.scopes import span
from repro.optim import AdamWConfig, init as opt_init, update as opt_update
from repro.rl import calibration as calib_mod
from repro.rl import rewards as rewards_mod
from repro.rl.advantage import dynamic_sampling_mask, group_advantages, overlong_penalty
from repro.rl.loss import LossConfig, dapo_token_loss
from repro.rl.rollout import (
    SamplerConfig,
    Trajectory,
    gather_response_logps,
    generate,
    packed_sequences,
)
from repro.rl.weight_sync import WeightSyncer, sync_policy_weights

# Static one-hot width for the fleet's versioned TIS (a jit shape): with
# one weight push per train step every batch sees one or two versions, so
# 4 slots is generous headroom.  Versions are rebased to the batch's
# minimum before entering the loss, so the absolute version counter never
# forces a recompile.
_VERSION_SLOTS = 4


@dataclasses.dataclass(frozen=True)
class RLConfig:
    precision: PrecisionConfig
    prompt_batch: int = 8
    n_per_prompt: int = 4
    max_prompt_len: int = 12
    max_new_tokens: int = 12
    temperature: float = 1.0
    seed: int = 0
    optimizer: AdamWConfig = AdamWConfig(lr=3e-4, b2=0.98, grad_clip=1.0)
    loss: LossConfig = LossConfig()
    moe_aux_coef: float = 1e-2
    dynamic_sampling: bool = True
    overlong_shaping: bool = False
    calibration: str = "inference"       # "inference" | "trainer"
    # rollout backend: "batch" = jitted whole-batch sampler (rl/rollout.py),
    # "fleet" = the live-updating serving fleet (serving/frontend.py) —
    # N engine replicas, per-token weight-version attribution, versioned
    # TIS in the loss
    rollout_backend: str = "batch"
    fleet_replicas: int = 2
    fleet_max_slots: int = 8
    fleet_block_size: int = 4
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    ckpt_keep: int = 2

    @property
    def rollout_batch(self) -> int:
        return self.prompt_batch * self.n_per_prompt


def build_update_fn(cfg, rl: RLConfig):
    """The jitted policy update `(params, opt_state, batch) -> (params,
    opt_state, stats)`: token-level DAPO loss with the rollout correction,
    then AdamW.  The old params and optimizer state die with the update,
    so both are donated — the step holds one copy of each, not two."""
    versioned = rl.rollout_backend == "fleet"

    def update_fn(params, opt_state, batch):
        def loss_fn(p):
            logits_inputs = {"tokens": batch["packed_tokens"]}
            logp_all, aux = _score_logprobs(p, logits_inputs, cfg)
            resp_logps = _gather(logp_all, batch)
            loss, stats = dapo_token_loss(
                logp_theta=resp_logps,
                logp_old=jax.lax.stop_gradient(resp_logps),
                logp_rollout=batch["rollout_logps"],
                advantages=batch["advantages"],
                mask=batch["mask"],
                precision=rl.precision,
                cfg=rl.loss,
                metrics_mask=batch["response_mask"],
                token_versions=(batch["token_versions"]
                                if versioned else None),
                num_versions=_VERSION_SLOTS if versioned else 1,
            )
            if aux.get("moe"):
                aux_losses = [v["aux_loss"].mean()
                              for v in aux["moe"].values()]
                loss = loss + rl.moe_aux_coef * sum(aux_losses)
                stats["moe_aux_loss"] = sum(aux_losses)
            return loss, stats

        (loss, stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        params, opt_state, opt_stats = opt_update(
            params, grads, opt_state, rl.optimizer)
        stats.update(opt_stats)
        stats["loss"] = loss
        return params, opt_state, stats

    return jax.jit(update_fn, donate_argnums=(0, 1))


class RLTrainer:
    def __init__(self, cfg, rl: RLConfig, params=None, metrics_sink=None):
        """cfg: a decoder-only ArchConfig, `reduced()` for CPU runs or
        `cut()` to one chip.  `params`, when given, are donated to the
        first update: the caller's arrays are deleted by it.

        metrics_sink: optional object with a ``write(dict)`` method (e.g.
        `repro.obs.JsonlSink`); every `train_step()` streams its metrics
        dict there — including the per-version mismatch-KL / IS-weight
        rows and the TIS/MIS weight ESS — as they are produced.
        """
        self.cfg = cfg
        self.rl = rl
        self.metrics_sink = metrics_sink
        self.key = jax.random.key(rl.seed)
        self.params = params if params is not None else init_params(
            cfg, jax.random.key(rl.seed + 1))
        self.opt_state = opt_init(self.params, rl.optimizer)
        self.pipeline = PromptPipeline(rl.prompt_batch, rl.max_prompt_len,
                                       seed=rl.seed + 2)
        self.sampler = SamplerConfig(max_new_tokens=rl.max_new_tokens,
                                     temperature=rl.temperature)
        self.step_idx = 0
        self.ckpt = Checkpointer(rl.ckpt_dir, keep=rl.ckpt_keep) \
            if rl.ckpt_dir else None
        self.kv_scales = None            # trainer-side calibration state
        assert rl.rollout_backend in ("batch", "fleet"), rl.rollout_backend
        if rl.rollout_backend == "fleet":
            self.syncer = WeightSyncer(self._rollout_precision())
            self._fleet = None           # built at the first weight push
        self.update_fn = build_update_fn(cfg, rl)

    # ------------------------------------------------------------------
    def _rollout_precision(self) -> PrecisionConfig:
        if self.rl.calibration == "trainer":
            return calib_mod.trainer_side_precision(self.rl.precision)
        return self.rl.precision

    # ------------------------------------------------------------------
    # fleet rollout backend
    # ------------------------------------------------------------------
    def _build_fleet(self, rollout_params, version: int):
        """N engine replicas behind one streaming front-end.  Built once,
        at the first weight push; later pushes hot-swap in place."""
        from repro.serving import ServingEngine, ServingFrontend
        rl = self.rl
        max_seq = rl.max_prompt_len + rl.max_new_tokens
        engines = [
            ServingEngine(
                rollout_params, self.cfg, self._rollout_precision(),
                max_slots=rl.fleet_max_slots,
                max_seq_len=max_seq,
                temperature=rl.temperature,
                seed=rl.seed + 100 + i,     # replicas sample independently
                prompt_pad=max(16, rl.max_prompt_len),
                block_size=rl.fleet_block_size,
                want_logps=True,
                weight_version=version,
            )
            for i in range(rl.fleet_replicas)
        ]
        return ServingFrontend(engines)

    def _fleet_rollout(self, batch):
        """GRPO group rollout through the fleet.  Submission order matches
        the batch backend's np.repeat layout: sample s of prompt i is row
        i * n_per_prompt + s, so rewards/advantages group identically."""
        rl = self.rl
        g = rl.max_new_tokens
        rids = []
        lengths_np = np.asarray(batch.lengths)
        tokens_np = np.asarray(batch.tokens)
        for i in range(len(lengths_np)):
            ids = tokens_np[i, : lengths_np[i]]
            for _ in range(rl.n_per_prompt):
                rids.append(self._fleet.submit(ids, max_new=g))
        report = self._fleet.run(max_steps=100_000)
        if report.stalled:
            raise RuntimeError(
                "fleet rollout stalled — replica KV pools too small for "
                "the prompt batch (raise fleet_max_slots or shrink "
                "prompt_batch)")
        by_rid = {o.rid: o for o in report.outputs}
        b = len(rids)
        resp = np.full((b, g), self.sampler.pad_id, np.int32)
        mask = np.zeros((b, g), np.float32)
        logps = np.zeros((b, g), np.float32)
        versions = np.zeros((b, g), np.int32)
        rlens = np.zeros((b,), np.int32)
        for r, rid in enumerate(rids):
            out = by_rid[rid].output
            n = len(out.token_ids)
            resp[r, :n] = out.token_ids
            mask[r, :n] = 1.0
            logps[r, :n] = out.logps
            versions[r, :n] = out.versions
            rlens[r] = n
        traj = Trajectory(
            prompt_tokens=jnp.asarray(
                np.repeat(tokens_np, rl.n_per_prompt, axis=0)),
            prompt_lengths=jnp.asarray(
                np.repeat(lengths_np, rl.n_per_prompt)),
            response_tokens=jnp.asarray(resp),
            response_mask=jnp.asarray(mask),
            rollout_logps=jnp.asarray(logps),
            response_lengths=jnp.asarray(rlens),
            routing=None, kv_scales=None)
        # rebase absolute weight versions to the batch minimum so the
        # loss's one-hot width (_VERSION_SLOTS) is a stable jit shape
        base = int(versions[mask > 0].min()) if mask.any() else 0
        rel = np.where(mask > 0, versions - base, 0).astype(np.int32)
        return traj, jnp.asarray(rel)

    # ------------------------------------------------------------------
    def train_step(self) -> dict:
        rl, cfg = self.rl, self.cfg
        t_start = time.perf_counter()

        # 1. prompts (over-provisioned groups double as straggler headroom)
        with span("rl.prompts"):
            batch = self.pipeline.next_batch()
            problems = [p for p in batch.problems for _ in range(rl.n_per_prompt)]

        # 2. weight sync (paper Fig 1 phase 2).  The fleet backend pushes a
        # version-stamped snapshot and hot-swaps it into every replica at a
        # step boundary — in-flight requests (none here, but the same code
        # path serves the async case) are not drained
        with span("rl.sync"):
            rollout_precision = self._rollout_precision()
            token_versions = None
            if rl.rollout_backend == "fleet":
                if self._fleet is None:
                    vw = self.syncer.push(self.params)
                    self._fleet = self._build_fleet(vw.params, vw.version)
                else:
                    # failure-aware push: the version is minted only after
                    # the fleet accepts the install (bounded retry inside),
                    # so a failed sync never desyncs trainer vs fleet
                    vw = self.syncer.push_to(self.params, self._fleet)
                sync_stats = vw.stats
            else:
                rollout_params, sync_stats = sync_policy_weights(
                    self.params, rollout_precision)

        # 3. rollout on the FP8 engine — GRPO group sampling prefills each
        # prompt once and forks per-sample block tables, so the group's
        # prompt KV is stored once instead of n_per_prompt times; the
        # shared-prefix width follows the shortest prompt in the batch
        # (static arg: recompiles at most once per distinct value)
        with span("rl.rollout"):
            self.key, k_gen = jax.random.split(self.key)
            t_roll = time.perf_counter()
            if rl.rollout_backend == "fleet":
                traj, token_versions = self._fleet_rollout(batch)
            else:
                page_size = 8
                traj = generate(
                    rollout_params, jnp.asarray(batch.tokens),
                    jnp.asarray(batch.lengths), k_gen,
                    cfg, rollout_precision, self.sampler,
                    want_routing=rl.precision.rollout_router_replay,
                    kv_scales=self.kv_scales,
                    page_size=page_size,
                    num_samples_per_prompt=rl.n_per_prompt,
                    shared_prefix_blocks=int(np.min(batch.lengths)) // page_size,
                )
                jax.block_until_ready(traj)
                del rollout_params        # free the fp8 copy before the update
            rollout_s = time.perf_counter() - t_roll
            gen_tokens = float(traj.response_mask.sum())

        # 4. rewards + advantages
        with span("rl.rewards"):
            resp = np.asarray(traj.response_tokens)
            rlen = np.asarray(traj.response_lengths)
            rewards = rewards_mod.batch_rewards(problems, resp, rlen)
            if rl.overlong_shaping:
                rewards = rewards + np.asarray(
                    overlong_penalty(traj.response_lengths, rl.max_new_tokens))
            adv = group_advantages(jnp.asarray(rewards), rl.n_per_prompt)
            mask = traj.response_mask
            if rl.dynamic_sampling:
                ds = dynamic_sampling_mask(jnp.asarray(rewards), rl.n_per_prompt)
                mask = mask * ds[:, None]

        # 5. update
        with span("rl.update"):
            update_batch = {
                "packed_tokens": packed_sequences(traj),
                "prompt_lengths": traj.prompt_lengths,
                "rollout_logps": traj.rollout_logps,
                "advantages": adv,
                "mask": mask,
                "response_mask": traj.response_mask,
            }
            if token_versions is not None:
                update_batch["token_versions"] = token_versions
            t_upd = time.perf_counter()
            self.params, self.opt_state, stats = jax.block_until_ready(
                self.update_fn(self.params, self.opt_state, update_batch))
            update_s = time.perf_counter() - t_upd

        # 6. trainer-side calibration for the *next* rollout (paper §B.2)
        with span("rl.calibrate"):
            if rl.calibration == "trainer" and not cfg.attention_free:
                calib = {
                    "tokens": update_batch["packed_tokens"][: rl.prompt_batch],
                    "lengths": (traj.prompt_lengths
                                + traj.response_lengths)[: rl.prompt_batch],
                }
                self.kv_scales = calib_mod.calibrate_kv_scales(
                    self.params, calib, cfg)

        self.step_idx += 1
        # scalars -> float; per-version stat vectors (mismatch_kl_per_
        # version & co from versioned_mismatch_stats) -> lists, so the
        # monitoring stream keeps the version breakdown instead of
        # crashing or silently dropping it
        metrics = {
            k: (np.asarray(v).astype(float).tolist()
                if np.ndim(v) else float(v))
            for k, v in stats.items()
        }
        metrics.update(
            step=self.step_idx,
            reward_mean=float(rewards.mean()),
            accuracy=float((rewards >= 1.0).mean()),
            response_len_mean=float(rlen.mean()),
            rollout_s=rollout_s,
            rollout_tokens_per_s=gen_tokens / max(rollout_s, 1e-9),
            update_s=update_s,
            step_s=time.perf_counter() - t_start,
            sync_ms=sync_stats.get("sync_ms", 0.0),
        )
        if self.metrics_sink is not None:
            self.metrics_sink.write(metrics)

        # 7. checkpoint
        with span("rl.checkpoint"):
            if self.ckpt and self.step_idx % rl.ckpt_every == 0:
                self.save_checkpoint()
        return metrics

    # ------------------------------------------------------------------
    def evaluate(self, n_problems: int = 64, seed: int = 9999) -> float:
        """Greedy decoding accuracy on held-out problems (AIME24 analogue)."""
        pipeline = PromptPipeline(n_problems, self.rl.max_prompt_len,
                                  seed=seed)
        batch = pipeline.next_batch()
        rollout_params, _ = sync_policy_weights(
            self.params, self._rollout_precision())
        sampler = dataclasses.replace(self.sampler, temperature=0.0)
        traj = generate(rollout_params, jnp.asarray(batch.tokens),
                        jnp.asarray(batch.lengths), jax.random.key(seed),
                        self.cfg, self._rollout_precision(), sampler,
                        kv_scales=self.kv_scales)
        return rewards_mod.exact_match_accuracy(
            batch.problems, np.asarray(traj.response_tokens),
            np.asarray(traj.response_lengths))

    # ------------------------------------------------------------------
    def save_checkpoint(self):
        assert self.ckpt is not None
        tree = {"params": self.params, "opt": self.opt_state,
                "key": jax.random.key_data(self.key)}
        self.ckpt.save(self.step_idx, tree, extra={
            "pipeline": self.pipeline.state_dict(),
            "step_idx": self.step_idx,
        })

    def restore_checkpoint(self) -> bool:
        """Resume from the latest committed checkpoint (fault recovery)."""
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return False
        like = {"params": self.params, "opt": self.opt_state,
                "key": jax.random.key_data(self.key)}
        tree, extra, step = self.ckpt.restore(like)
        self.params = jax.tree.map(jnp.asarray, tree["params"])
        self.opt_state = jax.tree.map(jnp.asarray, tree["opt"])
        self.key = jax.random.wrap_key_data(jnp.asarray(tree["key"]))
        self.pipeline.load_state_dict(extra["pipeline"])
        self.step_idx = extra["step_idx"]
        return True


# ---------------------------------------------------------------------------
# scoring helpers (jit-inlined)
# ---------------------------------------------------------------------------

def _score_logprobs(params, inputs, cfg):
    logits, aux = forward_train(params, inputs, cfg)
    tokens = inputs["tokens"]
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    out = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return out, aux


def _gather(logp_all, batch):
    tr = Trajectory(
        prompt_tokens=batch["packed_tokens"],   # only lengths used below
        prompt_lengths=batch["prompt_lengths"],
        response_tokens=batch["rollout_logps"],  # only shape used
        response_mask=batch["response_mask"],
        rollout_logps=batch["rollout_logps"],
        response_lengths=None, routing=None, kv_scales=None)
    return gather_response_logps(logp_all, tr)
