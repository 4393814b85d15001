"""Traffic of kind `rollout`: GRPO rollout steps.

One step is what an RL trainer's rollout phase does: re-quantize the
policy for the rollout (`sync_policy_weights`) and sample a group of
responses per prompt (`rl.rollout.generate`, one jitted while-loop), with
the arguments `RLTrainer.train_step` passes: page 8, group sampling, and
the shared prefix width min(prompt lengths) // page.

Every step has the same prompt lengths, in another order, so every seed
does the same work.  The check compares the log-probability the rollout
reported for each served token of a sample of responses with the plain
reference's, at the same prompt and tokens.
"""
from __future__ import annotations

import functools
import json
import time

import numpy as np

from . import counts, program, reference, weights
from .common import Checked, Run, log, window

BOS, PAD = 1, 0
WARM_STEP = 1 << 30        # the warm-up's prompts, apart from the window's


def prompts(traffic: dict, vocab: int, seed: int, step: int):
    """Host arrays (tokens (B, P), lengths (B,)) and a key seed for one
    step: ids drawn from the configuration's vocabulary past the task's
    special ids, lengths a permutation of the mix's fixed set."""
    rng = np.random.default_rng([seed, step])
    lengths = rng.permutation(np.asarray(traffic["prompt_lengths"],
                                         np.int32))
    tokens = rng.integers(traffic["first_id"], vocab,
                          size=(len(lengths), traffic["prompt_pad"]),
                          dtype=np.int32)
    tokens[:, 0] = BOS
    tokens[np.arange(tokens.shape[1])[None, :] >= lengths[:, None]] = PAD
    return tokens, lengths, int(rng.integers(0, 2**31 - 1))


def step_work(traffic: dict, dims: counts.Dims, recipe) -> list:
    kv = 1 if recipe.kv_quantized else 2
    lin = 1 if recipe.quantize_linears else 2
    lengths = traffic["prompt_lengths"]
    return counts.generate_work(
        dims, lengths, traffic["samples_per_prompt"], traffic["new_tokens"],
        traffic["page"], min(lengths) // traffic["page"], lin, kv)


class Rollout:
    """The timed path of one cell, built once and stepped by the window."""

    def __init__(self, config: dict, traffic: dict, seeds: dict):
        import jax

        from repro.rl.rollout import SamplerConfig

        self.c, self.t = config, traffic
        self.cfg = program.arch(config)
        self.recipe = program.precision(traffic["recipe"])
        self.seeds = seeds
        self.structure = program.structure(self.cfg)
        self.weights_spec = traffic.get("weights")
        self.master = self.make_weights()
        jax.block_until_ready(self.master)
        self.sampler = SamplerConfig(
            max_new_tokens=traffic["new_tokens"],
            temperature=traffic["temperature"])
        self.shared = min(traffic["prompt_lengths"]) // traffic["page"]
        self.kept = []                 # (step, prompts, lengths, traj)

    def make_weights(self):
        return weights.make(self.structure, self.seeds["weights"],
                            self.weights_spec)

    def step(self, k: int, keep: bool = True) -> dict:
        import jax
        import jax.numpy as jnp

        from repro.rl.rollout import generate
        from repro.rl.weight_sync import sync_policy_weights

        tokens, lengths, key = prompts(self.t, self.c["vocab_size"],
                                       self.seeds["traffic"], k)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("sync"):
            rollout_params, _ = sync_policy_weights(self.master, self.recipe)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("generate"):
            traj = generate(
                rollout_params, jnp.asarray(tokens), jnp.asarray(lengths),
                jax.random.key(key), self.cfg, self.recipe, self.sampler,
                page_size=self.t["page"],
                num_samples_per_prompt=self.t["samples_per_prompt"],
                shared_prefix_blocks=self.shared)
            jax.block_until_ready(traj)
        del rollout_params
        n_tokens = float(traj.response_mask.sum())
        t2 = time.perf_counter()
        if keep:
            self.kept.append((k, tokens, lengths, traj))
        return {"t0": t0, "t1": t2, "sync_ms": (t1 - t0) * 1e3,
                "generate_s": t2 - t1, "tokens": n_tokens,
                "sequences": int(traj.response_mask.shape[0])}

    def free(self):
        """Drop everything the program made; keep the served tokens and
        log-probabilities of the kept steps on the host."""
        self.kept = [(k, tok, ln, {
            "tokens": np.asarray(tr.response_tokens),
            "logps": np.asarray(tr.rollout_logps),
            "mask": np.asarray(tr.response_mask)})
            for k, tok, ln, tr in self.kept]
        del self.master


def sample_rows(kept, n: int, seed: int):
    """n responses drawn from the seed among every kept step's, with the
    longest among them."""
    rows = [(i, r) for i, (_, _, _, tr) in enumerate(kept)
            for r in range(tr["mask"].shape[0])]
    lens = np.array([kept[i][3]["mask"][r].sum() for i, r in rows])
    rng = np.random.default_rng(seed)
    longest = int(np.argmax(lens))
    rest = [j for j in rng.permutation(len(rows)) if j != longest]
    return [rows[j] for j in [longest] + rest[: n - 1]]


def served_logps(kept, picks, group: int, make_weights, c, quant=None):
    """(program, reference) log-probabilities of the picked responses'
    served tokens, flattened over the tokens the rollout produced.  The
    reference runs layer by layer, one jitted call a layer."""
    import jax.numpy as jnp

    rows, prog, masks = [], [], []
    for i, r in picks:
        _, tok, ln, tr = kept[i]
        p = r // group
        rows.append(np.concatenate([tok[p, : ln[p]], tr["tokens"][r]]))
        prog.append(tr["logps"][r])
        masks.append(tr["mask"][r] > 0)
    t = max(len(x) for x in rows)
    packed = np.stack([np.pad(x, (0, t - len(x))) for x in rows])
    ref = np.asarray(layerwise_logps(make_weights(), jnp.asarray(packed),
                                     c, quant))
    g = prog[0].shape[0]
    out_p, out_r = [], []
    for j, (i, r) in enumerate(picks):
        n = len(rows[j]) - g                       # the prompt's length
        out_r.append(ref[j, n - 1: n - 1 + g][masks[j]])
        out_p.append(prog[j][masks[j]])
    return np.concatenate(out_p), np.concatenate(out_r)


def layerwise_logps(w, tokens, c, quant=None):
    """`reference.token_logps`, one jitted call a layer, so that only one
    layer's float32 weights exist at a time."""
    import jax.numpy as jnp

    layer, head = _reference_fns(json.dumps(c, sort_keys=True), quant)
    x = jnp.take(w["emb"], tokens, axis=0).astype(jnp.float32)
    for i in range(c["num_hidden_layers"]):
        x = layer(x, reference.layer_params(w, i))
    return head(x, w["final_norm_scale"], w["lm_head"], tokens)


@functools.lru_cache(maxsize=None)
def _reference_fns(c_json: str, quant):
    """The reference's jitted layer and head for a configuration, built
    once per process."""
    import jax
    import jax.numpy as jnp

    c = json.loads(c_json)
    eps = c["rms_norm_eps"]

    @jax.jit
    def layer(x, p):
        b, t, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        x = x + reference.attention(
            reference.rms_norm(x, p["attn"]["norm_scale"], eps), p["attn"],
            c, positions, quant)
        return x + reference.mlp(
            reference.rms_norm(x, p["mlp"]["norm_scale"], eps), p["mlp"],
            quant)

    @jax.jit
    def head(x, final, lm_head, tokens):
        h = reference.rms_norm(x, final, eps)[:, :-1]
        logits = jnp.matmul(h, lm_head.astype(jnp.float32),
                            precision=reference.HIGHEST)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]

    return layer, head


def run(cell, seeds: dict, seconds: float, tracer, t_start: float,
        counter: list, devices, peaks: dict) -> tuple:
    from .common import memory_peak_bytes

    r = Rollout(cell.config, cell.traffic, seeds)
    r.step(WARM_STEP, keep=False)          # warm every shape of the window
    setup_s = time.perf_counter() - t_start
    steps, window_s = window(r.step, seconds, tracer, counter)
    log(setup_s=setup_s, first_steps=[
        {k: s[k] for k in ("sync_ms", "generate_s", "tokens")}
        for s in steps[:3]])
    mem = memory_peak_bytes(devices)
    work = step_work(cell.traffic, counts.Dims.from_config(cell.config),
                     r.recipe)
    flops = sum(w.flops for w in work)
    least = sum(w.least_seconds(peaks["bf16_flops"], peaks["hbm_bytes_per_s"])
                for w in work)
    for s in steps:
        s.update(flops=flops, least_s=least)
    run = Run(kind="rollout", setup_s=setup_s, window_s=window_s,
              steps=steps, peaks=peaks)
    checked = check(r, cell, seeds)
    return run, checked, sum(s["sequences"] for s in steps), mem


def gap_numbers(prog, ref) -> dict:
    gap = np.abs(prog - ref)
    return {"logp_gap_max": float(gap.max()),
            "logp_gap_mean": float(gap.mean())}


def missing_tokens(kept) -> float:
    """Responses cut short: the mix holds EOS off, so every response of
    every step must run its full `new_tokens`."""
    return float(sum(tr["mask"].size - tr["mask"].sum()
                     for _, _, _, tr in kept))


def check(r: Rollout, cell, seeds: dict) -> Checked:
    make = r.make_weights
    r.free()
    import gc
    gc.collect()
    picks = sample_rows(r.kept, cell.traffic["check"]["sequences"],
                        seeds["check"])
    prog, ref = served_logps(r.kept, picks, cell.traffic["samples_per_prompt"],
                             make, cell.config)
    numbers = gap_numbers(prog, ref)
    numbers["missing_tokens"] = missing_tokens(r.kept)
    limits = {k: v["limit"] for k, v in cell.limits["numbers"].items()}
    return Checked(numbers=numbers, limits=limits)
