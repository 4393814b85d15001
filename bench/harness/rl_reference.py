"""The plain reference of one RL step's update: rewards, GRPO advantages,
the DAPO token loss with truncated importance sampling, and AdamW.

Written from the method's published description (GRPO group-normalised
advantages; DAPO token-level loss with clip-higher and dynamic sampling;
TIS weights min(pi_theta / pi_rollout, C)), in float32, with parameters
stored in bfloat16 between steps as the configuration states.  It imports
nothing of the program.  Its inputs are the prompts and the tokens the
program's rollout served, with the rollout's log-probabilities, which the
update consumes as the TIS denominator.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import reference

# the arithmetic task's token ids: PAD, BOS, EOS, ANS, digits 0-9, then
# "+", "-", "*", "=", " "
PAD, BOS, EOS, ANS = 0, 1, 2, 3
DIGIT0 = 4
PLUS, MINUS = 14, 15
TASK_VOCAB = 19


def prompt_answer(prompt_ids) -> str:
    """The answer of an `a+b=` or `a-b=` prompt, from its tokens."""
    text = ""
    for t in prompt_ids:
        t = int(t)
        if DIGIT0 <= t < DIGIT0 + 10:
            text += str(t - DIGIT0)
        elif t in (PLUS, MINUS):
            text += "+-"[t - PLUS]
    op = "+" if "+" in text else "-"
    a, b = text.split(op)
    return str(int(a) + int(b) if op == "+" else int(a) - int(b))


def reward(answer: str, response_ids) -> float:
    """1 for `<ans>` then exactly the answer's digits then EOS; 0.1 for a
    well-formed wrong number; otherwise 0.  Ids outside the task's
    vocabulary, PAD and BOS are not part of the text."""
    ids = [int(i) for i in response_ids]
    if ANS not in ids:
        return 0.0
    start = ids.index(ANS) + 1
    if EOS not in ids[start:]:
        return 0.0
    end = ids.index(EOS, start)
    text = ""
    for i in ids[start:end]:
        if i == ANS:
            text += "<ans>"
        elif 4 <= i < TASK_VOCAB:
            text += "0123456789+-*= "[i - DIGIT0]
    if text == answer:
        return 1.0
    return 0.1 if text.lstrip("-").isdigit() else 0.0


def advantages(rewards: np.ndarray, group: int):
    """GRPO advantages and DAPO's dynamic-sampling mask, per row."""
    g = rewards.reshape(-1, group).astype(np.float64)
    std = g.std(axis=1, keepdims=True)
    adv = (g - g.mean(axis=1, keepdims=True)) / (std + 1e-6)
    keep = np.repeat((std[:, 0] > 1e-6).astype(np.float32), group)
    return adv.reshape(-1).astype(np.float32), keep


def pack(prompts, lengths, responses):
    """prompt[:L] followed by the response, per row: (B, P + G)."""
    b, p = prompts.shape
    g = responses.shape[1]
    out = np.zeros((b, p + g), np.int32)
    for i in range(b):
        n = int(lengths[i])
        row = np.concatenate([prompts[i, :n], responses[i]])
        out[i, : len(row)] = row
        out[i, len(row):] = responses[i, -1]
    return out


def response_logps(weights, packed, lengths, g, c, quant=None):
    """log-probabilities of each response token under `weights`: (B, G)."""
    lp = reference.token_logps(weights, packed, c, quant)
    idx = lengths[:, None] + jnp.arange(g)[None, :] - 1
    return jnp.take_along_axis(lp, idx, axis=1)


def loss_fn(weights, batch, c, rl, quant=None):
    """DAPO token loss with TIS, as the value and gradient of one step."""
    g = batch["mask"].shape[1]
    logp = response_logps(weights, batch["packed"], batch["lengths"], g, c,
                          quant) * batch["response_mask"]
    old = jax.lax.stop_gradient(logp)
    ratio = jnp.exp(logp - old)
    adv = batch["advantages"][:, None]
    lo, hi = rl["loss"]["eps_low"], rl["loss"]["eps_high"]
    pg = -jnp.minimum(ratio * adv, jnp.clip(ratio, 1 - lo, 1 + hi) * adv)
    w = jax.lax.stop_gradient(
        jnp.minimum(jnp.exp(old - batch["rollout_logps"]), rl["tis_clip"]))
    mask = batch["mask"]
    loss = (pg * w * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss, old


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(params, m, v, grads, step, hp):
    """One AdamW step with global-norm clipping.  Also returns the norm of
    each leaf's clipped gradient, the gradient as the optimizer gets it."""
    gnorm = global_norm(grads)
    scale = jnp.where(gnorm > hp["grad_clip"],
                      hp["grad_clip"] / (gnorm + 1e-9), 1.0)
    t = step.astype(jnp.float32)
    b1, b2 = hp["b1"], hp["b2"]

    def one(p, m, v, g):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        d = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + hp["eps"])
        return (p.astype(jnp.float32) - hp["lr"] * d).astype(p.dtype), m, v

    out = jax.tree.map(one, params, m, v, grads)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    norms = jax.tree.map(
        lambda g: jnp.sqrt(jnp.sum(jnp.square(g * scale))), grads)
    return pick(0), pick(1), pick(2), norms


_norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
    x.astype(jnp.float32)))) for x in xs])


def leaf_norms(tree) -> dict:
    """Per-leaf L2 norms, by leaf path, as host floats."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = _norms([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, norms)}


@functools.lru_cache(maxsize=None)
def _grads_fn(c_json: str, rl_json: str, quant):
    """The loss and its float32 gradient, jitted once per configuration."""
    c, rl = json.loads(c_json), json.loads(rl_json)

    @jax.jit
    def grads_fn(w, batch):
        wf = jax.tree.map(lambda x: x.astype(jnp.float32), w)
        return jax.value_and_grad(loss_fn, has_aux=True)(
            wf, batch, c, rl, quant)

    return grads_fn


def follow(make_weights, steps, c, rl, quant=None):
    """Run the reference through the recorded steps from the weights that
    `make_weights()` returns.  Returns per-step losses and response
    log-probabilities, the first step's clipped gradient norms by leaf and
    the norm of every leaf's change after the last step.

    The gradients are taken in float32 on the accelerator; AdamW's float32
    moments and the bfloat16 parameters live on the host's CPU device,
    where the optimizer step runs, so that the accelerator holds one
    float32 copy of the parameters and their gradient at a time."""
    cpu = jax.devices("cpu")[0]
    accel = jax.devices()[0]
    p = jax.device_put(make_weights(), cpu)
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32, device=cpu), p)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32, device=cpu), p)
    hp = jax.device_put({k: jnp.float32(rl["optimizer"][k])
                         for k in ("lr", "b1", "b2", "eps", "grad_clip")}, cpu)

    grads_fn = _grads_fn(json.dumps(c, sort_keys=True),
                         json.dumps(rl, sort_keys=True), quant)
    losses, logps, first = [], [], None
    for k, batch in enumerate(steps):
        (loss, logp), grads = grads_fn(jax.device_put(p, accel), batch)
        losses.append(float(loss))
        logps.append(np.asarray(logp))
        grads = jax.device_put(grads, cpu)
        p, m, v, norms = _adam(p, m, v, grads,
                               jax.device_put(jnp.int32(k + 1), cpu), hp)
        if first is None:
            first = {jax.tree_util.keystr(path): float(n) for path, n in
                     jax.tree_util.tree_flatten_with_path(norms)[0]}
        del grads
    del m, v
    p0 = jax.device_put(make_weights(), cpu)
    change = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))
    return {"loss": losses, "logps": logps, "grad": first, "change": change}
