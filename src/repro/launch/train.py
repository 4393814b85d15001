"""RL training launcher.

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen3-8b --reduced --steps 50 --precision fp8 --tis

On a CPU you want --reduced (full configs are exercised through the
dry-run).  On one TPU chip, keep every published width and cut only the
depth and the vocabulary rows (the task's ids are the leading rows):

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b \
        --layers 4 --vocab-size 18992 --steps 3 --precision fp8 --tis
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.configs import get_config
from repro.core.precision import (
    BF16_ROLLOUT,
    E2E_FP8,
    FP8_KV_ONLY_ROLLOUT,
    FP8_LINEAR_ROLLOUT,
    FULL_FP8_ROLLOUT,
    RolloutCorrection,
)
from repro.data import tasks
from repro.launch.runtime import describe_cut, enable_compile_cache
from repro.obs import JsonlSink
from repro.optim import AdamWConfig
from repro.rl import RLConfig, RLTrainer

PRECISIONS = {
    "bf16": BF16_ROLLOUT,
    "fp8": FULL_FP8_ROLLOUT,
    "fp8-linear": FP8_LINEAR_ROLLOUT,
    "fp8-kv": FP8_KV_ONLY_ROLLOUT,
    "e2e-fp8": E2E_FP8,
}


def model_config(args):
    """(published config, the config this run builds).  --reduced shrinks
    every width for CPU runs; otherwise --layers / --vocab-size cut only
    depth and vocabulary rows."""
    full = get_config(args.arch)
    if args.reduced:
        n_layers = 2 if args.layers is None else args.layers
        return full, full.reduced(vocab_size=tasks.VOCAB_SIZE,
                                  n_layers=n_layers, d_model=args.d_model)
    cfg = full.cut(n_layers=args.layers, vocab_size=args.vocab_size)
    if cfg.vocab_size < tasks.VOCAB_SIZE:
        raise ValueError(f"--vocab-size {cfg.vocab_size} drops task ids "
                         f"(needs >= {tasks.VOCAB_SIZE})")
    return full, cfg


def rl_config(args) -> RLConfig:
    precision = PRECISIONS[args.precision]
    correction = RolloutCorrection.TIS if args.tis else (
        RolloutCorrection.MIS if args.mis else RolloutCorrection.NONE)
    precision = precision.replace(correction=correction,
                                  rollout_router_replay=args.rrr)
    return RLConfig(
        precision=precision,
        prompt_batch=args.prompt_batch,
        n_per_prompt=args.n_per_prompt,
        max_new_tokens=args.max_new_tokens,
        optimizer=AdamWConfig(lr=args.lr, b2=0.98, grad_clip=1.0),
        calibration=args.calibration,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        seed=args.seed,
    )


def build_trainer(args, metrics_sink=None) -> RLTrainer:
    return RLTrainer(model_config(args)[1], rl_config(args),
                     metrics_sink=metrics_sink)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N layers (default: 2 with "
                         "--reduced, else the published depth)")
    ap.add_argument("--d-model", type=int, default=128,
                    help="model width with --reduced")
    ap.add_argument("--vocab-size", type=int, default=None,
                    help="keep the leading N vocabulary rows (without "
                         "--reduced; must hold the task's ids)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--precision", choices=sorted(PRECISIONS), default="fp8")
    ap.add_argument("--tis", action="store_true", default=True)
    ap.add_argument("--no-tis", dest="tis", action="store_false")
    ap.add_argument("--mis", action="store_true")
    ap.add_argument("--rrr", action="store_true")
    ap.add_argument("--calibration", choices=("inference", "trainer"),
                    default="inference")
    ap.add_argument("--prompt-batch", type=int, default=8)
    ap.add_argument("--n-per-prompt", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=10)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="stream per-step metrics as JSONL (one step per "
                         "line, written as each step completes — incl. "
                         "mismatch-KL, per-version KL breakdowns and "
                         "TIS/MIS weight ESS)")
    ap.add_argument("--run-id", default=None, metavar="ID",
                    help="stamp this id on every metrics row; launch the "
                         "serving side (repro.launch.serve --run-id) with "
                         "the SAME id to join trainer steps to the serving "
                         "steps that produced their rollout batches")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    print(describe_cut(*model_config(args)), file=sys.stderr, flush=True)
    sink = JsonlSink(args.metrics_out, run_id=args.run_id) \
        if args.metrics_out else None
    trainer = build_trainer(args, metrics_sink=sink)
    if args.resume and trainer.restore_checkpoint():
        print(f"resumed from step {trainer.step_idx}")

    history = []
    try:
        for _ in range(args.steps):
            m = trainer.train_step()
            history.append(m)
            if m["step"] % args.eval_every == 0 or m["step"] == 1:
                m["eval_accuracy"] = trainer.evaluate(n_problems=32)
            print(json.dumps({k: round(v, 5) if isinstance(v, float) else v
                              for k, v in m.items()}), flush=True)
    finally:
        if sink is not None:
            sink.close()
    return history


if __name__ == "__main__":
    main()
