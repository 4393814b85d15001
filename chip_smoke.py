#!/usr/bin/env python3
"""Bring-up smoke run of the FP8 RL stack on TPU, at qwen3-8b widths.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: phase (a), then (f) only

(a) platform   fail unless JAX's first device is a TPU; print its kind,
               the device count and the compile-cache directory.
(b) kernels    every Pallas kernel compiled (never interpreted) at
               qwen3-8b widths, its lowering holding `tpu_custom_call`,
               checked against the pure-jnp oracles of `kernels/ref.py`.
(c) serve      `launch.serve`'s path: qwen3-8b at 4 of 36 layers, full
               vocabulary, fp8 weights + fp8 KV, both attention kernels;
               8 requests must complete without a stall.  Then one decode
               step's logits, kernel path against gather path: in f32
               within 1e-4 with the same argmax, and in bf16 no further
               from the f32 gather than a small multiple of the bf16
               gather path's own distance.
(d) train      `launch.train`'s path: qwen3-8b at 4 layers and an eighth
               of the vocabulary, fp8 rollout with TIS, 3 RL steps with
               finite loss and grad norm, no compile in steps 2-3.  Then
               one more update through the same compiled step, on a batch
               with nonzero advantages, must move the params.
(f) sharded    the RL update under `ShardingRules` (ZeRO-3) on a 2x2
               (data, model) mesh against the same update on one device.

Everything runs in this one process: a chip belongs to one process, so no
child may touch JAX.  Any failure exits non-zero and prints no result; the
last stdout line of a passing run is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Weights and data are random, made from `--seed`.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-8b"
LAYERS = 4                 # of 36: depth is the only cut
SERVE_REQUESTS = 8
PAGE = 16                  # serving KV page size (tokens)
CHUNK = 16                 # chunked-prefill width (tokens)
TRAIN_STEPS = 3
ATTN_TOL = dict(rtol=2e-2, atol=2e-2)   # tests/test_paged_kernels.py
GEMM_TOL = dict(rtol=2e-2, atol=1e-3)   # tests/test_kernels.py
# decode logits, kernel vs gather path.  In f32 at highest matmul
# precision the TPU reads 3.8e-6 (PERF.md); rounding q, K or V to bf16
# inside the kernel reads orders of magnitude more.
F32_LOGIT_TOL = dict(rtol=1e-4, atol=1e-4)
# In bf16 each path rounds differently; the kernel path's distance to
# the f32 gather path is bounded by this multiple of the bf16 gather
# path's distance to it.
BF16_NOISE_MULTIPLE = 2.0
SHARD_RTOL = 2e-2          # sharded vs one-device update statistics


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok, message) -> None:
    """Fail the run (unlike `assert`, kept under `python -O`)."""
    if not ok:
        raise SmokeFailure(message)


def _close(name, got, want, rtol, atol):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, (name, got.shape, want.shape))
    check(np.isfinite(got).all(), f"{name}: non-finite output")
    err = np.abs(got - want)
    bad = int((err > atol + rtol * np.abs(want)).sum())
    check(bad == 0, f"{name}: {bad} elements outside rtol={rtol} atol={atol}")
    return float(err.max())


def _payload_flips(name, q, q_ref):
    """fp8 payloads must agree except for rare round-to-nearest flips at a
    bucket edge (division is not correctly rounded on every backend): at
    most 1e-3 of the elements, each one fp8 step apart."""
    import numpy as np
    q = np.asarray(q, np.float32)
    q_ref = np.asarray(q_ref, np.float32)
    diff = q != q_ref
    step = 0.125 * np.maximum(np.abs(q), np.abs(q_ref)) + 2.0 ** -9
    check((np.abs(q - q_ref)[diff] <= step[diff]).all(),
          f"{name}: payload differs by more than one fp8 step")
    check(diff.mean() <= 1e-3, f"{name}: {int(diff.sum())} payload flips")
    return int(diff.sum())


# ---------------------------------------------------------------------------
# (b) kernels
# ---------------------------------------------------------------------------

def phase_kernels(cfg, *, interpret: bool = False, seed: int = 0,
                  m: int = 256, batch: int = 8, pages: int = 64,
                  table: int = 8, ctx: int = 1024) -> None:
    """Each kernel at `cfg`'s widths (K = d_model, N = d_ff, KV heads,
    GQA group, head dim) against its `ref.py` oracle."""
    import jax
    import jax.numpy as jnp
    from repro.core import quant as cq
    from repro.kernels import fp8_gemm, fp8_quant, ref
    from repro.kernels import fp8_kv_attention as attn

    kvh, d = cfg.n_kv_heads, cfg.d_head
    g = cfg.n_heads // kvh
    k_dim, n_dim = cfg.d_model, cfg.d_ff
    keys = iter(jax.random.split(jax.random.key(seed), 16))

    def normal(shape, dtype=jnp.float32):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def run(name, fn, *args):
        f = jax.jit(fn)
        text = f.lower(*args).as_text()
        if not interpret:
            check("tpu_custom_call" in text, f"{name}: no Mosaic kernel")
        t0 = time.perf_counter()
        out = jax.block_until_ready(f(*args))
        return out, time.perf_counter() - t0

    def oracle(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    # fused quantizers
    x = normal((m, k_dim), jnp.bfloat16) * 3
    (qa, sa), s = run("quantize_activation", lambda a: fp8_quant
                      .quantize_activation_kernel(a, interpret=interpret), x)
    qa_r, sa_r = oracle(ref.quantize_activation_ref, x)
    flips = _payload_flips("quantize_activation", qa, qa_r)
    _close("quantize_activation scales", sa, sa_r, 1e-6, 0.0)
    log("kernels", kernel="quantize_activation", shape=[m, k_dim],
        payload_flips=flips, first_call_s=s)

    w = normal((k_dim, n_dim), jnp.bfloat16) * 0.1
    (qw, sw), s = run("quantize_weight", lambda a: fp8_quant
                      .quantize_weight_kernel(a, interpret=interpret), w)
    qw_r, sw_r = oracle(ref.quantize_weight_ref, w)
    flips = _payload_flips("quantize_weight", qw, qw_r)
    _close("quantize_weight scales", sw, sw_r, 1e-6, 0.0)
    log("kernels", kernel="quantize_weight", shape=[k_dim, n_dim],
        payload_flips=flips, first_call_s=s)

    # blockwise gemm on the oracle-quantized operands
    xq, xs = oracle(ref.quantize_activation_ref, normal((m, k_dim)))
    wq, ws = oracle(ref.quantize_weight_ref, normal((k_dim, n_dim)))
    y, s = run("fp8_gemm", lambda *a: fp8_gemm.fp8_gemm(
        *a, bm=min(m, fp8_gemm.DEFAULT_BM), interpret=interpret),
        xq, wq, xs, ws)
    err = _close("fp8_gemm", y, oracle(ref.fp8_gemm_ref, xq, wq, xs, ws),
                 **GEMM_TOL)
    log("kernels", kernel="fp8_gemm", shape=[m, k_dim, n_dim],
        max_abs_err=err, first_call_s=s)

    # attention over an fp8 pool: paged decode, paged prefill, contiguous
    k_f, v_f = normal((pages, PAGE, kvh, d)), normal((pages, PAGE, kvh, d))
    k_s = jnp.abs(k_f).max() / 448.0
    v_s = jnp.abs(v_f).max() / 448.0
    k_pool = cq.quantize_per_tensor(k_f, k_s)
    v_pool = cq.quantize_per_tensor(v_f, v_s)
    tables = jax.random.permutation(next(keys), pages)[:batch * table] \
        .reshape(batch, table).astype(jnp.int32)
    lengths = jax.random.randint(next(keys), (batch,), 1, table * PAGE + 1)
    q = normal((batch, kvh, g, d), jnp.bfloat16)
    args = (q, k_pool, v_pool, k_s, v_s, tables, lengths)
    out, s = run("paged_decode", lambda *a: attn.fp8_paged_decode_attention(
        *a, interpret=interpret), *args)
    err = _close("paged_decode", out,
                 oracle(ref.fp8_paged_decode_attention_ref, *args),
                 **ATTN_TOL)
    log("kernels", kernel="fp8_paged_decode_attention",
        shape=[batch, kvh, g, d], page=PAGE, max_abs_err=err,
        first_call_s=s)

    qc = normal((batch, CHUNK, kvh, g, d), jnp.bfloat16)
    start = jnp.maximum(lengths - CHUNK // 2, 0)
    args = (qc, k_pool, v_pool, k_s, v_s, tables, start, lengths)
    out, s = run("paged_prefill", lambda *a: attn.fp8_paged_prefill_attention(
        *a, interpret=interpret), *args)
    err = _close("paged_prefill", out,
                 oracle(ref.fp8_paged_prefill_attention_ref, *args),
                 **ATTN_TOL)
    log("kernels", kernel="fp8_paged_prefill_attention",
        shape=[batch, CHUNK, kvh, g, d], page=PAGE, max_abs_err=err,
        first_call_s=s)

    k_c = cq.quantize_per_tensor(normal((batch, ctx, kvh, d)), k_s)
    v_c = cq.quantize_per_tensor(normal((batch, ctx, kvh, d)), v_s)
    lengths_c = jax.random.randint(next(keys), (batch,), 1, ctx + 1)
    args = (q, k_c, v_c, k_s, v_s, lengths_c)
    out, s = run("decode", lambda *a: attn.fp8_decode_attention(
        *a, interpret=interpret), *args)
    err = _close("decode", out, oracle(ref.fp8_decode_attention_ref, *args),
                 **ATTN_TOL)
    log("kernels", kernel="fp8_decode_attention",
        shape=[batch, ctx, kvh, d], max_abs_err=err, first_call_s=s)


# ---------------------------------------------------------------------------
# (c) serve
# ---------------------------------------------------------------------------

def phase_serve(serve_argv, cfg, *, seed: int = 0) -> None:
    """`launch.serve` end to end, then one decode step's logits through
    the paged kernel against the jnp table-gather path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import FP8_KV_ONLY_ROLLOUT
    from repro.data import tasks
    from repro.launch import serve
    from repro.models import decode_step, init_cache, init_params, prefill

    t0 = time.perf_counter()
    out = serve.main(serve_argv)
    wall = time.perf_counter() - t0
    gc.collect()              # the server's weights leave the device
    check(out["completed"] == SERVE_REQUESTS and not out["stalled"], out)
    log("serve", completed=out["completed"], requests=SERVE_REQUESTS,
        stalled=out["stalled"], steps=out["steps"],
        emitted_tokens=out["emitted_tokens"],
        wall_s_with_compile=wall)

    # Kernel vs gather at fp8 KV (quantized attention math is a jnp-path
    # feature), as in the scheduler's parity test.  In f32 at highest
    # matmul precision the attention implementation is the only
    # difference.  In bf16 the residual stream's rounding lets two correct
    # implementations drift apart layer by layer (PERF.md), so each bf16
    # path is measured against the f32 gather path instead.
    precision = FP8_KV_ONLY_ROLLOUT
    rng = np.random.default_rng(seed)
    prompts = [tasks.sample_problem(rng).prompt_ids for _ in range(4)]
    width = max(map(len, prompts))
    toks = np.full((len(prompts), width), tasks.PAD, np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    batch = {"tokens": jnp.asarray(toks),
             "lengths": jnp.asarray([len(p) for p in prompts])}
    tok = jnp.asarray(rng.integers(0, tasks.VOCAB_SIZE, len(prompts)),
                      jnp.int32)

    def gather_and_kernel_logits(params):
        cache = init_cache(cfg, len(prompts), 4 * PAGE, precision,
                           page_size=PAGE)
        _, cache = prefill(params, batch, cache, cfg, precision)
        return [np.asarray(decode_step(params, tok, cache, cfg, precision,
                                       use_kernel=kernel)[0], np.float32)
                for kernel in (False, True)]

    params = init_params(cfg, jax.random.key(seed), dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref, lg_kernel = gather_and_kernel_logits(params)
    err = _close("decode logits f32", lg_kernel, ref, **F32_LOGIT_TOL)
    check((ref.argmax(-1) == lg_kernel.argmax(-1)).all(),
          "decode logits f32: kernel and gather argmax differ")
    log("serve", check="decode logits kernel vs gather", dtype="float32",
        matmul_precision="highest", shape=list(ref.shape),
        max_abs_err=err, limit=F32_LOGIT_TOL, argmax_agree=1.0)

    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    dist = {}
    for path, lg in zip(("gather", "kernel"),
                        gather_and_kernel_logits(params)):
        check(np.isfinite(lg).all(), f"decode logits bf16 {path}: "
                                     "non-finite output")
        d = np.abs(lg - ref)
        dist[path] = float(d.max())
        log("serve", check="decode logits bf16 vs f32 gather", path=path,
            max_abs_dist=dist[path], rms_dist=float(np.sqrt((d * d).mean())),
            argmax_agree=float((lg.argmax(-1) == ref.argmax(-1)).mean()))
    check(dist["kernel"] <= BF16_NOISE_MULTIPLE * dist["gather"],
          f"decode logits bf16: kernel path {dist['kernel']} from the f32 "
          f"gather, over {BF16_NOISE_MULTIPLE}x the bf16 gather path's "
          f"{dist['gather']}")


# ---------------------------------------------------------------------------
# (d) train
# ---------------------------------------------------------------------------

# JAX records this event around every executable it builds or loads from
# the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def count_compiles():
    """A list that grows by one per XLA executable built (or loaded from
    the persistent cache) from here on."""
    import jax
    seen = []

    def listener(event, duration, **_):
        if event == COMPILE_EVENT:
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


def phase_train(train_argv, steps: int = TRAIN_STEPS, *,
                seed: int = 0) -> None:
    import math
    import jax
    import jax.numpy as jnp
    from repro.launch import train
    from repro.launch.runtime import describe_cut

    args = train.build_parser().parse_args(train_argv)
    log("train", **json.loads(describe_cut(*train.model_config(args))))
    trainer = train.build_trainer(args)
    compiles = count_compiles()
    for _ in range(steps):
        before = len(compiles)
        m = trainer.train_step()
        n_compiles = len(compiles) - before
        log("train", step=m["step"], reward_mean=m["reward_mean"],
            loss=m["loss"],
            grad_norm=m["grad_norm"], sync_ms=m["sync_ms"],
            rollout_s=m["rollout_s"], update_s=m["update_s"],
            step_s=m["step_s"], compiles=n_compiles)
        check(math.isfinite(m["loss"]) and math.isfinite(m["grad_norm"]), m)
        if m["step"] == 1:
            check(n_compiles > 0, f"no {COMPILE_EVENT} event in step 1")
        else:
            check(n_compiles == 0, f"step {m['step']} compiled {n_compiles}")

    # A random policy earns no reward, so the DAPO dynamic-sampling mask
    # zeroes every token and the steps above leave the params as they
    # were.  One more update through the same compiled step, on a batch of
    # the trainer's shapes with nonzero advantages, must move them.
    batch = _synthetic_batch(trainer.rl, seed + 1)
    before = jax.tree.map(jnp.copy, trainer.params)
    n0 = len(compiles)
    trainer.params, trainer.opt_state, stats = jax.block_until_ready(
        trainer.update_fn(trainer.params, trainer.opt_state, batch))
    n_compiles = len(compiles) - n0
    delta_sum, delta_abs = jax.jit(_update_delta)(trainer.params, before)
    del before
    m = {"loss": float(stats["loss"]), "grad_norm": float(stats["grad_norm"]),
         "delta_sum": float(delta_sum), "delta_abs": float(delta_abs)}
    log("train", update="synthetic batch", compiles=n_compiles, **m)
    check(all(map(math.isfinite, m.values())), m)
    check(m["grad_norm"] > 0 and m["delta_abs"] > 0,
          f"the update moved nothing: {m}")
    check(n_compiles == 0, f"the synthetic-batch update compiled "
                           f"{n_compiles}: not the trainer's batch layout")


# ---------------------------------------------------------------------------
# (f) sharded update (four chips)
# ---------------------------------------------------------------------------

def _synthetic_batch(rl, seed: int):
    """A rollout batch of the trainer's shapes, made from `seed`."""
    import jax
    import jax.numpy as jnp
    from repro.data import tasks
    b, g, p = rl.rollout_batch, rl.max_new_tokens, rl.max_prompt_len
    ks = jax.random.split(jax.random.key(seed), 4)
    mask = (jax.random.uniform(ks[2], (b, g)) < 0.8).astype(jnp.float32)
    return {
        "packed_tokens": jax.random.randint(ks[0], (b, p + g), 0,
                                            tasks.VOCAB_SIZE),
        "prompt_lengths": jnp.full((b,), p // 2, jnp.int32),
        "rollout_logps": -jnp.abs(jax.random.normal(ks[1], (b, g))),
        "advantages": jax.random.normal(ks[3], (b,)),
        "mask": mask,
        "response_mask": mask,
    }


def _update_delta(new, old):
    """Sum and sum of |.| of new - old over every param, in f32."""
    import jax
    import jax.numpy as jnp
    deltas = [a.astype(jnp.float32) - b.astype(jnp.float32)
              for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))]
    return (sum(jnp.sum(x) for x in deltas),
            sum(jnp.sum(jnp.abs(x)) for x in deltas))


def sharded_update_stats(cfg, rl, *, seed: int = 0, mesh=None) -> dict:
    """Loss, grad norm and a checksum of the update (sum and sum of |.| of
    new - init over every param) of one RL update; with `mesh`, params,
    optimizer state and batch are placed by `ShardingRules(zero3=True)`."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed import ShardingRules
    from repro.models import init_params
    from repro.optim import init as opt_init
    from repro.rl.trainer import build_update_fn

    def fresh_params():
        params = init_params(cfg, jax.random.key(seed))
        return params if mesh is None else jax.device_put(params, pspec)

    batch = _synthetic_batch(rl, seed + 1)
    if mesh is not None:
        pspec = ShardingRules(mesh, zero3=True).params(
            jax.eval_shape(lambda: init_params(cfg, jax.random.key(seed))))
        batch = jax.device_put(batch, NamedSharding(mesh, P("data")))
    params = fresh_params()
    opt = opt_init(params, rl.optimizer)
    if mesh is not None:
        opt = opt._replace(m=jax.device_put(opt.m, pspec),
                           v=jax.device_put(opt.v, pspec))
    new, opt, stats = build_update_fn(cfg, rl)(params, opt, batch)
    del opt
    delta_sum, delta_abs = jax.jit(_update_delta)(new, fresh_params())
    return {"loss": float(stats["loss"]),
            "grad_norm": float(stats["grad_norm"]),
            "delta_sum": float(delta_sum), "delta_abs": float(delta_abs)}


def phase_sharded(cfg, rl, mesh, *, seed: int = 0) -> None:
    one = sharded_update_stats(cfg, rl, seed=seed)
    log("sharded", run="one device", **one)
    many = sharded_update_stats(cfg, rl, seed=seed, mesh=mesh)
    log("sharded", run=f"mesh {dict(mesh.shape)} zero3", **many)
    for key in ("loss", "grad_norm", "delta_abs"):
        rel = abs(many[key] - one[key]) / max(abs(one[key]), 1e-12)
        check(rel <= SHARD_RTOL, (key, one[key], many[key]))
    # delta_sum cancels: judge it against the size of the update itself
    check(abs(many["delta_sum"] - one["delta_sum"])
          <= SHARD_RTOL * one["delta_abs"], (one, many))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def serve_argv(seed: int) -> list:
    return ["--arch", ARCH, "--layers", str(LAYERS), "--precision", "fp8",
            "--kernel-config", "all", "--block-size", str(PAGE),
            "--prefill-chunk", str(CHUNK), "--requests", str(SERVE_REQUESTS),
            "--slots", str(SERVE_REQUESTS), "--seed", str(seed)]


def train_argv(full_vocab: int, seed: int) -> list:
    return ["--arch", ARCH, "--layers", str(LAYERS),
            "--vocab-size", str(full_vocab // 8), "--precision", "fp8",
            "--tis", "--steps", str(TRAIN_STEPS), "--seed", str(seed)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded RL update on a 2x2 mesh "
                         "and the one-device update it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.runtime import enable_compile_cache

    cache = enable_compile_cache()
    log("platform", platform=devices[0].platform,
        kind=devices[0].device_kind, count=len(devices),
        compile_cache=cache)
    full = get_config(ARCH)
    cfg = full.cut(n_layers=LAYERS)
    t0 = time.perf_counter()
    if args.chips == 4:
        from repro.launch import train
        from repro.launch.mesh import make_test_mesh
        targs = train.build_parser().parse_args(
            train_argv(full.vocab_size, args.seed))
        trainer_cfg = train.model_config(targs)[1]
        rl = train.rl_config(targs)
        phase_sharded(trainer_cfg, rl, make_test_mesh(dp=2, tp=2),
                      seed=args.seed)
    else:
        check(not ops._interpret(), "kernels would run interpreted")
        phase_kernels(cfg, seed=args.seed)
        phase_serve(serve_argv(args.seed), cfg, seed=args.seed)
        gc.collect()
        phase_train(train_argv(full.vocab_size, args.seed), seed=args.seed)
    log("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
