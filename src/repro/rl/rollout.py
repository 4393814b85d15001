"""Rollout engine: batched autoregressive generation on the (FP8) policy.

This is the inference-engine role of the paper's stack (vLLM/SGLang):
  * consumes the synced rollout params (fp8 payloads + scales),
  * prefill recalibrates KV scales when `calculate_kv_scales` is on
    (inference-side calibration, Fig 7) or uses trainer-provided scales,
  * decodes with a `while_loop` that stops as soon as every sequence hit
    EOS — plus a hard token budget, the straggler-mitigation cutoff,
  * returns per-token *rollout* logprobs (the pi^FP8 side of TIS),
  * optionally records MoE expert choices per token for RRR,
  * GRPO group sampling (`num_samples_per_prompt` > 1) prefills each
    prompt ONCE and forks per-sample block tables: samples of a group
    share the physical KV blocks of their common prefix (read-only) and
    the partially-filled boundary block is copied into per-sample private
    blocks before the first divergent append — copy-on-write on the same
    paged pool the serving engine manages with refcounts.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.precision import PrecisionConfig
from repro.core.sampling import sample as _sample
from repro.data import tasks
from repro.models import decode_step, init_cache, prefill
from repro.models import attention as attn_mod
from repro.models import blocks as blocks_mod
from repro.obs.scopes import scope


class Trajectory(NamedTuple):
    """One rollout batch (B sequences)."""

    prompt_tokens: jax.Array     # (B, P)
    prompt_lengths: jax.Array    # (B,)
    response_tokens: jax.Array   # (B, G) PAD after EOS
    response_mask: jax.Array     # (B, G) 1.0 through EOS inclusive
    rollout_logps: jax.Array     # (B, G) log pi^FP8 of sampled tokens
    response_lengths: jax.Array  # (B,)
    routing: Optional[dict]      # RRR: prefill/decode expert choices
    kv_scales: Optional[dict]    # per-slot (R,) k/v scales after calibration
    decode_passes: Optional[jax.Array] = None  # () int32 decode loop passes


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    max_new_tokens: int = 24
    temperature: float = 1.0
    top_k: int = 0              # 0 = full softmax
    eos_id: int = tasks.EOS
    pad_id: int = tasks.PAD


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "precision", "sampler", "want_routing",
                     "page_size", "num_samples_per_prompt",
                     "shared_prefix_blocks"))
def generate(
    rollout_params,
    prompts: jax.Array,          # (B, P) right-padded
    prompt_lengths: jax.Array,   # (B,)
    key: jax.Array,
    cfg,
    precision: PrecisionConfig,
    sampler: SamplerConfig = SamplerConfig(),
    want_routing: bool = False,
    extra_inputs: Optional[dict] = None,
    kv_scales: Optional[dict] = None,    # trainer-side calibration scales
    page_size: int = 8,                  # paged-KV block size (tokens)
    num_samples_per_prompt: int = 1,     # GRPO group size (shared prefix)
    shared_prefix_blocks: Optional[int] = None,
) -> Trajectory:
    """Sample `num_samples_per_prompt` responses per prompt.

    With a group size of 1 every sequence owns a contiguous run of blocks
    (identity tables).  With a larger group the prompts are prefilled ONCE
    (batch B) and the resulting KV blocks are shared read-only by all
    samples of the group through forked block tables; the pool holds
    B*shared + B*G*private blocks instead of B*G*ceil(max_len/page) — the
    paged-attention gather makes the dedup invisible to the model.

    `shared_prefix_blocks` sets the shared region, and the safe value
    depends on runtime data the trace cannot see: a sample's first
    divergent append must never land inside a shared block, so it must
    not exceed min(prompt_lengths) // page_size (pass that — it is a
    static python int).  The default of None shares NOTHING (every block
    private, correct for any lengths); the prefill is still done once per
    prompt, but pool dedup only happens when the caller vouches for the
    bound.  Trajectory rows come back grouped: sample s of prompt i is
    row i * num_samples_per_prompt + s (np.repeat order).
    """
    b, p = prompts.shape
    g = sampler.max_new_tokens
    group = num_samples_per_prompt
    assert group >= 1
    n = b * group
    max_len = p + g + 1
    src_len = 0
    inputs = {"tokens": prompts, "lengths": prompt_lengths}
    if extra_inputs:
        inputs.update(extra_inputs)
        if "frames" in extra_inputs:
            src_len = extra_inputs["frames"].shape[1]

    # Paged KV layout — the same attention/gather path the serving engine
    # drives with a real allocator, so rollout exercises the paged code.
    # group == 1: identity block tables (sequence i owns a contiguous run).
    # group > 1 : prompt i's first `fp` blocks are physically shared by its
    #             G samples; the rest are per-sample private rows.
    with scope("prefill"):
        if group == 1:
            cache = init_cache(cfg, b, max_len, precision, src_len=src_len,
                               page_size=page_size)
        else:
            fp, priv, w = _group_layout(p, g, page_size, shared_prefix_blocks)
            cache = init_cache(cfg, b, max_len, precision, src_len=src_len,
                               page_size=page_size,
                               num_pages=b * fp + n * priv)
            cache["block_tables"] = _prefill_tables(b, group, w, fp, priv)
        if kv_scales is not None:
            from repro.rl.calibration import apply_kv_scales
            cache = apply_kv_scales(cache, kv_scales)
        out = prefill(rollout_params, inputs, cache, cfg, precision,
                      want_routing=want_routing)
        if want_routing:
            logits0, cache, prefill_routing = out
        else:
            logits0, cache = out
            prefill_routing = None

    if group > 1:
        with scope("fork"):
            # fork: CoW the boundary blocks, share the rest, tile logits and
            # per-sequence state so every sample decodes independently
            cache = _fork_group(cache, b, group, p, page_size, fp, priv, w)
            logits0 = jnp.repeat(logits0, group, axis=0)
            prompts = jnp.repeat(prompts, group, axis=0)
            prompt_lengths = jnp.repeat(prompt_lengths, group, axis=0)

    with scope("sample"):
        key, k0 = jax.random.split(key)
        tok0, logp0 = _sample(logits0, k0, sampler.temperature, sampler.top_k)

    pattern = blocks_mod.layer_pattern(cfg)
    moe_slots = [f"s{j}" for j, s in enumerate(pattern) if s.ffn == "moe"]
    repeats = blocks_mod.n_repeats(cfg)

    def routing_buf():
        if not (want_routing and moe_slots):
            return None
        return {name: jnp.zeros((g, repeats, n, 1, cfg.top_k), jnp.int32)
                for name in moe_slots}

    state0 = dict(
        i=jnp.int32(0),
        tok=tok0,
        logp=logp0,
        done=jnp.zeros((n,), bool),
        key=key,
        cache=cache,
        resp=jnp.full((n, g), sampler.pad_id, jnp.int32),
        logps=jnp.zeros((n, g), jnp.float32),
        mask=jnp.zeros((n, g), jnp.float32),
        routing=routing_buf(),
    )

    def cond(s):
        return jnp.logical_and(s["i"] < g, ~jnp.all(s["done"]))

    @scope("decode")
    def body(s):
        i = s["i"]
        # Ordering invariant: the token sampled in the previous iteration is
        # committed FIRST (EOS included — mask=1 through EOS, making EOS the
        # last masked token), and only THEN does `done` absorb it; a done
        # sequence commits PAD/0 from here on.  The decode step below runs
        # unconditionally (fixed shapes) — its writes for done rows are
        # masked out by `response_mask` downstream.
        with scope("commit"):
            resp = s["resp"].at[:, i].set(
                jnp.where(s["done"], sampler.pad_id, s["tok"]))
            logps = s["logps"].at[:, i].set(
                jnp.where(s["done"], 0.0, s["logp"]))
            mask = s["mask"].at[:, i].set(jnp.where(s["done"], 0.0, 1.0))
            done = s["done"] | (s["tok"] == sampler.eos_id)
            passes = i + 1

        logits, cache, aux = decode_step(
            rollout_params, s["tok"], s["cache"], cfg, precision,
            want_routing=want_routing)
        with scope("sample"):
            key, kk = jax.random.split(s["key"])
            tok, logp = _sample(logits, kk, sampler.temperature, sampler.top_k)
        routing = s["routing"]
        if routing is not None:
            routing = {name: routing[name].at[i].set(aux["routing"][name])
                       for name in routing}
        return dict(i=passes, tok=tok, logp=logp, done=done, key=key,
                    cache=cache, resp=resp, logps=logps, mask=mask,
                    routing=routing)

    state = jax.lax.while_loop(cond, body, state0)

    resp_lengths = state["mask"].sum(axis=1).astype(jnp.int32)
    routing = None
    if want_routing and moe_slots:
        # with group > 1 the prefill routing stays per-*prompt* (B rows):
        # the prefix compute is genuinely shared across the group
        routing = {"prefill": prefill_routing, "decode": state["routing"]}

    kv_scales = _collect_kv_scales(state["cache"], pattern)
    return Trajectory(
        prompt_tokens=prompts,
        prompt_lengths=prompt_lengths,
        response_tokens=state["resp"],
        response_mask=state["mask"],
        rollout_logps=state["logps"],
        response_lengths=resp_lengths,
        routing=routing,
        kv_scales=kv_scales,
        decode_passes=state["i"],
    )


# ---------------------------------------------------------------------------
# GRPO group sampling: shared-prefix pool layout + fork/copy-on-write
# ---------------------------------------------------------------------------

def _group_layout(p: int, g: int, page_size: int,
                  shared_prefix_blocks: Optional[int]):
    """Static pool geometry for group sampling.

    fp   : blocks shared by all samples of a prompt (read-only prefix)
    priv : private blocks per sample (boundary block + decode region)
    w    : block-table width (blocks per sequence)
    """
    w = -(-(p + g + 1) // page_size)
    # None -> share nothing: sharing block j is only sound when every
    # prompt's true length covers it, which only the caller can promise
    fp = 0 if shared_prefix_blocks is None else shared_prefix_blocks
    fp = max(0, min(fp, p // page_size))
    return fp, w - fp, w


def _prefill_tables(b: int, group: int, w: int, fp: int, priv: int
                    ) -> jax.Array:
    """(B, W) tables for the single shared prefill: prompt i writes its
    shared rows [i*fp, (i+1)*fp) and spills the non-shared tail (the
    partially-filled boundary block) into sample i*G's private rows —
    the donor copy that `_fork_group` CoWs to the siblings."""
    ii = jnp.arange(b)[:, None]
    jj = jnp.arange(w)[None, :]
    pool0 = b * fp                       # start of the private region
    donor = pool0 + (ii * group) * priv + (jj - fp)
    return jnp.where(jj < fp, ii * fp + jj, donor).astype(jnp.int32)


def _fork_group(cache: dict, b: int, group: int, p: int, page_size: int,
                fp: int, priv: int, w: int) -> dict:
    """Fork the prefilled B-prompt cache into B*G per-sample sequences.

    Copy-on-write: the prompt rows prefill wrote beyond the shared region
    (at minimum the partially-filled boundary block) live in sample 0's
    private rows; they are copied to every sibling's private rows NOW —
    before the first divergent append lands — so each sample mutates only
    its own copy.  Shared rows are never written again: the first decode
    position is >= the prompt length >= fp*page_size (the
    `shared_prefix_blocks` contract), so every later scatter stays in
    private rows.  Per-sequence state (lengths, SSM, cross-KV) is tiled
    G-fold; the KV pools are shared by construction.
    """
    n = b * group
    pool0 = b * fp
    n_cow = -(-p // page_size) - fp      # donor rows holding prompt tokens
    if n_cow > 0 and group > 1:
        src, dst = [], []
        for i in range(b):
            for s in range(1, group):
                for r in range(n_cow):
                    src.append(pool0 + (i * group) * priv + r)
                    dst.append(pool0 + (i * group + s) * priv + r)
        slots = {}
        for name, sd in cache["slots"].items():
            nd = dict(sd)
            if "kv" in sd:
                nd["kv"] = attn_mod.paged_copy_rows(sd["kv"], src, dst)
            slots[name] = nd
        cache = dict(cache, slots=slots)

    # per-sample tables: shared prefix rows + own private run
    with scope("kv_write"):
        ii = (jnp.arange(n) // group)[:, None]
        jj = jnp.arange(w)[None, :]
        own = pool0 + jnp.arange(n)[:, None] * priv + (jj - fp)
        tables = jnp.where(jj < fp, ii * fp + jj, own).astype(jnp.int32)

    def tile(a):
        return jnp.repeat(a, group, axis=1) \
            if hasattr(a, "ndim") and a.ndim >= 2 else a

    slots = {}
    for name, sd in cache["slots"].items():
        nd = {}
        for key, state in sd.items():
            # KV pools have no batch dim (shared); SSM / cross state is
            # (R, B, ...) — tile the batch axis
            nd[key] = state if key == "kv" else jax.tree.map(tile, state)
        slots[name] = nd
    cache = dict(cache, slots=slots, block_tables=tables,
                 lengths=jnp.repeat(cache["lengths"], group, axis=0))
    if "src_lengths" in cache:
        cache["src_lengths"] = jnp.repeat(cache["src_lengths"], group,
                                          axis=0)
    return cache


def _collect_kv_scales(cache, pattern) -> dict:
    out = {}
    for j, spec in enumerate(pattern):
        slot = cache["slots"].get(f"s{j}", {})
        if "kv" in slot:
            out[f"s{j}"] = {"k_scale": slot["kv"].k_scale,
                            "v_scale": slot["kv"].v_scale}
    return out


# ---------------------------------------------------------------------------
# scoring-side alignment helpers
# ---------------------------------------------------------------------------

def packed_sequences(traj: Trajectory) -> jax.Array:
    """(B, P+G): prompt[:L_i] immediately followed by the response — the
    teacher-forced scoring input (no PAD gap for short prompts)."""
    b, p = traj.prompt_tokens.shape
    g = traj.response_tokens.shape[1]
    pos = jnp.arange(p + g)[None, :]
    lens = traj.prompt_lengths[:, None]
    prompt_part = jnp.take_along_axis(
        traj.prompt_tokens,
        jnp.broadcast_to(jnp.clip(pos, 0, p - 1), (b, p + g)), axis=1)
    resp_idx = jnp.clip(pos - lens, 0, g - 1)
    resp_part = jnp.take_along_axis(traj.response_tokens,
                                    jnp.broadcast_to(resp_idx, (b, p + g)),
                                    axis=1)
    return jnp.where(pos < lens, prompt_part, resp_part)


def gather_response_logps(score_logps: jax.Array, traj: Trajectory
                          ) -> jax.Array:
    """Align scoring-model logprobs (B, T-1) with rollout response tokens.

    The response token k of row i sits at packed position L_i + k and is
    predicted at logprob index L_i + k - 1.  Returns (B, G) masked like
    `traj.response_mask`."""
    b, g = traj.response_tokens.shape
    idx = traj.prompt_lengths[:, None] + jnp.arange(g)[None, :] - 1
    idx = jnp.clip(idx, 0, score_logps.shape[1] - 1)
    out = jnp.take_along_axis(score_logps, idx, axis=1)
    return out * traj.response_mask
