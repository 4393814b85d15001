"""Serving launcher: continuous batching with FP8 weights + FP8 KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
        --requests 16 --precision fp8 --prefill-chunk 8 --eviction lru

At published widths on one chip, cut only the depth (`--layers`):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --layers 4 \
        --precision fp8 --kernel-config all --block-size 16 \
        --prefill-chunk 16 --requests 8

Every layer pattern in the zoo serves: hybrid/SSM archs
(`--arch jamba-1.5-large-398b --reduced`, `--arch mamba2-780m --reduced`)
swap their recurrent state to host on preemption, and enc-dec archs
(`--arch seamless-m4t-medium --reduced`) get synthetic source frames per
request (real frontends would feed frame embeddings through the same
`submit(..., frames=...)` path).
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import numpy as np

from repro.configs import get_config
from repro.data import tasks
from repro.launch.runtime import describe_cut, enable_compile_cache
from repro.launch.train import PRECISIONS
from repro.obs import JsonlSink, StepTracer, chrome_trace
from repro.models import init_params
from repro.rl import WeightSyncer, sync_policy_weights
from repro.serving import (
    EVICTION_POLICIES,
    CrashFault,
    FaultInjector,
    FaultPlan,
    ServingEngine,
    ServingFrontend,
    SpecConfig,
    StepBudget,
    kv_bytes_per_token,
    request_state_bytes,
)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N layers, every width kept "
                         "(with or without --reduced)")
    ap.add_argument("--precision", choices=sorted(PRECISIONS), default="fp8")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--budget-tokens", type=int, default=None)
    ap.add_argument("--block-size", type=int, default=4,
                    help="paged KV block size in tokens")
    ap.add_argument("--admission", choices=("reserve", "ondemand"),
                    default="reserve",
                    help="reserve: worst-case block reservation; "
                         "ondemand: vLLM-style growth + swap preemption")
    ap.add_argument("--eviction", choices=sorted(EVICTION_POLICIES),
                    default="youngest",
                    help="preemption victim-selection policy")
    ap.add_argument("--host-kv-blocks", type=int, default=0,
                    help="host-tier reservation (blocks) for demoted "
                         "cache blocks: evicted prefix entries demote to "
                         "host and revive by copy-in instead of dying "
                         "(0 = single-tier drop-on-evict)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill width in tokens (default: "
                         "legacy batch-1 prefill at --prompt-pad width)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="max prefill tokens scheduled per engine step")
    ap.add_argument("--decode-kernel", choices=("gather", "paged"),
                    default="gather",
                    help="legacy spelling of --kernel-config decode")
    ap.add_argument("--kernel-config",
                    choices=("off", "decode", "prefill", "all"),
                    default=None,
                    help="Pallas attention hot path: decode routes the "
                         "fused decode through fp8_paged_decode_attention, "
                         "prefill routes chunked-prefill chunks through "
                         "fp8_paged_prefill_attention, all does both "
                         "(interpret on CPU, compiled on TPU)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="speculative decoding: draft up to K tokens per "
                         "verify via the n-gram prompt-lookup proposer "
                         "(attention-only decoders; greedy stays "
                         "bit-exact vs non-speculative decode)")
    ap.add_argument("--src-pad", type=int, default=8,
                    help="enc-dec: source-frame capacity per slot "
                         "(requests carry up to this many frames)")
    ap.add_argument("--shrink-at", type=int, default=None,
                    help="shrink the byte budget after N engine steps "
                         "(the RL reality: the trainer reclaims HBM at a "
                         "weight sync) — forces swap even on attention-"
                         "free archs whose KV usage is zero")
    ap.add_argument("--shrink-frac", type=float, default=0.5,
                    help="fraction of the budget kept after --shrink-at")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas behind the "
                         "streaming front-end (1 = the classic "
                         "single-engine path)")
    ap.add_argument("--update-every", type=int, default=None,
                    help="hot-swap a fresh FP8 weight version into every "
                         "replica each N front-end steps (simulates the "
                         "RL trainer's weight pushes; in-flight requests "
                         "keep running, their tokens carry the version "
                         "live at each decode step)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the run, "
                         "its steps on the host wall clock (load in "
                         "Perfetto / chrome://tracing; enables the step "
                         "tracer)")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="write the raw typed event log as JSONL (one "
                         "event per line; enables the step tracer)")
    ap.add_argument("--run-id", default=None, metavar="ID",
                    help="stamp this id on every --events-out row; launch "
                         "the trainer (repro.launch.train --run-id) with "
                         "the SAME id to join its metrics stream to these "
                         "serving steps")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="fleet chaos: derive a deterministic random "
                         "fault schedule (replica crashes) from this seed "
                         "via FaultPlan.random and inject it into every "
                         "replica; the frontend fails work over with "
                         "exactly-once token delivery (requires "
                         "--replicas >= 2)")
    ap.add_argument("--crash-replica", type=int, default=None,
                    metavar="I",
                    help="fleet chaos: crash exactly replica I (instead "
                         "of a --chaos-seed random schedule)")
    ap.add_argument("--crash-step", type=int, default=2, metavar="N",
                    help="engine-local step at which --crash-replica "
                         "fires (0-based count of step() entries)")
    ap.add_argument("--crash-transient", action="store_true",
                    help="make the --crash-replica crash transient: the "
                         "replica rejoins after --crash-down-steps once "
                         "it reinstalls the fleet weight version")
    ap.add_argument("--crash-down-steps", type=int, default=3,
                    help="front-end steps a transient crash stays down")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.src_pad < 1:
        ap.error("--src-pad must be >= 1 (frames per enc-dec request)")
    if args.kernel_config is not None and args.decode_kernel != "gather":
        ap.error("--decode-kernel and --kernel-config are mutually "
                 "exclusive (use --kernel-config decode)")
    if args.chaos_seed is not None and args.crash_replica is not None:
        ap.error("--chaos-seed and --crash-replica are mutually "
                 "exclusive (random schedule vs one explicit crash)")
    chaos = args.chaos_seed is not None or args.crash_replica is not None
    if chaos and args.replicas < 2:
        ap.error("fault injection needs --replicas >= 2: a single-replica "
                 "fleet has nowhere to fail work over to")

    enable_compile_cache()
    full = get_config(args.arch)
    cfg = full.reduced(vocab_size=tasks.VOCAB_SIZE) if args.reduced else full
    cfg = cfg.cut(n_layers=args.layers)
    print(describe_cut(full, cfg), file=sys.stderr, flush=True)
    precision = PRECISIONS[args.precision]
    params = init_params(cfg, jax.random.key(args.seed))
    rollout_params, sync_stats = sync_policy_weights(params, precision)

    state_bytes = request_state_bytes(
        cfg, precision, src_len=args.src_pad if cfg.is_encdec else 0)
    budget = None
    if args.budget_tokens:
        budget = args.budget_tokens * max(
            kv_bytes_per_token(cfg, precision), 1) \
            + args.slots * state_bytes
    step_budget = StepBudget(prefill_tokens=args.prefill_budget) \
        if args.prefill_budget else None
    fleet = args.replicas > 1 or args.update_every is not None
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if fleet and args.shrink_at is not None:
        ap.error("--shrink-at applies to the single-engine path only")

    tracing = args.trace_out is not None or args.events_out is not None
    tracers = []

    # one shared injector: faults are keyed on each engine's replica_index
    # (assigned by the frontend), so every replica sees the same plan and
    # only its own entries fire
    faults = None
    if args.crash_replica is not None:
        if not 0 <= args.crash_replica < args.replicas:
            ap.error(f"--crash-replica {args.crash_replica} out of range "
                     f"for --replicas {args.replicas}")
        faults = FaultInjector(FaultPlan(crashes=(
            CrashFault(replica=args.crash_replica, step=args.crash_step,
                       transient=args.crash_transient,
                       down_steps=args.crash_down_steps),)))
    elif args.chaos_seed is not None:
        # max_step=4: short launcher runs drain in a handful of steps, so
        # schedule the crash early enough to actually fire
        faults = FaultInjector(FaultPlan.random(
            args.chaos_seed, replicas=args.replicas, max_step=4,
            down_steps=args.crash_down_steps))

    rng = np.random.default_rng(args.seed)
    requests = []             # (prompt ids, enc-dec frames or None)
    for i in range(args.requests):
        prob = tasks.sample_problem(rng)
        frames = None
        if cfg.is_encdec:
            # synthetic frame embeddings stand in for the audio frontend
            n = int(rng.integers(min(3, args.src_pad), args.src_pad + 1))
            frames = tasks.random_frames(args.seed * 1000 + i, n,
                                         cfg.d_model)
        requests.append((prob.prompt_ids, frames))
    # room for the longest prompt, its new tokens and a verify's drafts
    max_seq_len = max(len(ids) for ids, _ in requests) + args.max_new \
        + (args.spec_k or 0)

    def mk_engine(i: int) -> ServingEngine:
        tracer = None
        if tracing:
            tracer = StepTracer(replica=i)
            tracers.append(tracer)
        return ServingEngine(rollout_params, cfg, precision,
                             tracer=tracer, faults=faults,
                             max_slots=args.slots, max_seq_len=max_seq_len,
                             kv_budget_bytes=budget, seed=args.seed + i,
                             block_size=args.block_size,
                             admission=args.admission,
                             eviction=args.eviction,
                             host_kv_blocks=args.host_kv_blocks,
                             prefill_chunk=args.prefill_chunk,
                             step_budget=step_budget,
                             decode_kernel=args.decode_kernel,
                             kernel_config=args.kernel_config,
                             max_src_len=args.src_pad,
                             spec=SpecConfig(num_draft_tokens=args.spec_k)
                             if args.spec_k else None)

    def submit_all(target):
        for i, (ids, frames) in enumerate(requests):
            target.submit(ids, max_new=args.max_new, rid=i, frames=frames)

    def write_traces():
        if not tracing:
            return
        if args.events_out:
            with JsonlSink(args.events_out, run_id=args.run_id) as sink:
                for t in tracers:
                    for e in t.events:
                        row = e.to_dict()
                        row.setdefault("replica", t.replica)
                        sink.write(row)
        if args.trace_out:
            rows = []
            for t in tracers:
                rows.extend(chrome_trace(t.events, replica=t.replica,
                                         clock="wall")["traceEvents"])
            with open(args.trace_out, "w") as f:
                json.dump({"traceEvents": rows}, f)

    if fleet:
        frontend = ServingFrontend([mk_engine(i)
                                    for i in range(args.replicas)])
        submit_all(frontend)
        syncer = WeightSyncer(precision)
        perturb = jax.random.split(jax.random.key(args.seed + 7), 1)[0]
        steps = 0
        while frontend.has_work() and steps < 1000:
            if args.update_every and steps and \
                    steps % args.update_every == 0:
                # the RL reality: the trainer's policy moved, requantize
                # and push.  A small parameter nudge stands in for the
                # gradient step.
                perturb, sub = jax.random.split(perturb)
                params = jax.tree.map(
                    lambda x: x * (1.0 + 1e-3) if hasattr(x, "dtype")
                    else x, params)
                frontend.update_weights(syncer.push(params))
            frontend.step()
            steps += 1
        report = frontend.run(max_steps=1000)  # drain + final accounting
        versions = sorted({v for o in report.outputs
                           for v in o.output.versions})
        write_traces()
        out = {
            "replicas": args.replicas,
            "completed": len(report.outputs),
            "steps": report.steps,
            "clock_tokens": report.clock_tokens,
            "emitted_tokens": report.emitted_tokens,
            "tokens_per_clock": round(report.tokens_per_clock, 4),
            "weight_version": report.weight_version,
            "versions_seen": versions,
            "stalled": report.stalled,
            "kv_pressure": [round(p, 4) for p in report.kv_pressure],
            "sync_ms": round(sync_stats.get("sync_ms", 0.0), 2),
        }
        if chaos:
            out["chaos"] = {
                "healthy_replicas": report.healthy_replicas,
                "quarantined_replicas": report.quarantined_replicas,
                "redispatches": report.redispatches,
                "replayed_tokens": report.replayed_tokens,
                "aborted": report.aborted,
                "injected": dict(faults.injected),
            }
        if report.latency is not None:
            out["latency"] = report.latency
        print(json.dumps(out, indent=2))
        return out

    eng = mk_engine(0)
    submit_all(eng)
    if args.shrink_at is not None:
        full = eng.budget_tokens
        for _ in range(args.shrink_at):
            eng.step()
        eng.budget_tokens = int(full * args.shrink_frac)
    report = eng.run()
    write_traces()
    out = {
        "completed": len(report.completed),
        "steps": report.steps,
        "preemptions": report.preemptions,
        "swap_outs": report.swap_outs,
        "swap_ins": report.swap_ins,
        "wasted_tokens": report.wasted_tokens,
        "prefill_chunks": report.prefill_chunks,
        "emitted_tokens": report.emitted_tokens,
        "mean_occupancy": round(report.mean_occupancy, 4),
        "useful_token_rate": round(report.useful_token_rate, 4),
        "spec_steps": report.spec_steps,
        "accepted_tokens": report.accepted_tokens,
        "spec_tokens_per_step": round(report.spec_tokens_per_step, 3),
        "stalled": report.stalled,
        "budget_tokens": report.budget_tokens,
        "kv_bytes_per_token": kv_bytes_per_token(cfg, precision),
        "state_bytes_per_request": state_bytes,
        "sync_ms": round(sync_stats.get("sync_ms", 0.0), 2),
    }
    if report.latency is not None:
        out["latency"] = report.latency
    print(json.dumps(out, indent=2))
    return out


if __name__ == "__main__":
    main()
