"""Model assembly: init / train-forward / prefill / decode for every family.

All depth is `jax.lax.scan` over pattern repeats (blocks.py); caches are
scan xs/ys so the same code path serves 4-layer smoke models and the
88-layer dry-run configs.

Public surface:
    init_params(cfg, key)                 -> params pytree
    forward_train(params, inputs, ...)    -> (logits, aux)
    token_logprobs(params, tokens, ...)   -> per-token logprobs (TIS / KL)
    init_cache(cfg, batch, max_len, ...)  -> rollout cache pytree
    prefill(params, inputs, cache, ...)   -> (last_logits, cache)
    decode_step(params, tokens, cache,...) -> (logits, cache, aux)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.fp8_linear import linear
from repro.core.precision import PrecisionConfig
from repro.models import attention as attn_mod
from repro.models import blocks as blocks_mod
from repro.models import ssm as ssm_mod
from repro.models.common import KeyGen, constrain, embed_init, dense_init, rms_norm
from repro.obs.scopes import scope

BF16 = jnp.bfloat16

# ---------------------------------------------------------------------------
# scan-unroll context: XLA's cost_analysis counts a `while` body ONCE, so the
# dry-run's cost-accounting variants trace with fully-unrolled layer stacks
# (roofline/analysis extrapolates total = outside + R * per_layer).
# ---------------------------------------------------------------------------

_SCAN_CTX = threading.local()


@contextlib.contextmanager
def scan_unroll(value: bool = True):
    prev = getattr(_SCAN_CTX, "unroll", False)
    _SCAN_CTX.unroll = value
    try:
        yield
    finally:
        _SCAN_CTX.unroll = prev


def _scan(body, init, xs):
    return jax.lax.scan(body, init, xs,
                        unroll=getattr(_SCAN_CTX, "unroll", False))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stacked_slots(key, cfg, pattern, repeats, dtype, decoder=True):
    keys = jax.random.split(key, repeats)

    def init_one(k):
        kg = KeyGen(k)
        return {f"s{j}": blocks_mod.init_slot_params(kg, spec, cfg, dtype)
                for j, spec in enumerate(pattern)}

    return jax.vmap(init_one)(keys)


def init_params(cfg, key, dtype=BF16) -> dict:
    kg = KeyGen(key)
    pattern = blocks_mod.layer_pattern(cfg)
    repeats = blocks_mod.n_repeats(cfg)
    params = {
        "emb": embed_init(kg(), (cfg.vocab_size, cfg.d_model), dtype),
        "blocks": _stacked_slots(kg(), cfg, pattern, repeats, dtype),
        "final_norm_scale": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            kg(), (cfg.d_model, cfg.vocab_size), cfg.d_model, dtype)
    if cfg.is_encdec:
        enc_pattern = tuple(
            blocks_mod.SlotSpec(mixer=s.mixer, ffn=s.ffn, cross=False)
            for s in blocks_mod.layer_pattern(cfg, decoder=False))
        params["enc"] = {
            "blocks": _stacked_slots(kg(), cfg, enc_pattern,
                                     blocks_mod.n_repeats(cfg, decoder=False),
                                     dtype, decoder=False),
            "final_norm_scale": jnp.ones((cfg.d_model,), dtype),
        }
    if cfg.frontend is not None:
        params["frontend"] = {
            "w_patch": dense_init(kg(), (cfg.d_model, cfg.d_model),
                                  cfg.d_model, dtype)}
    return params


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

@scope("embed")
def _embed(params, tokens):
    return jnp.take(params["emb"], tokens, axis=0)


@scope("head")
def _unembed(params, x, cfg, precision):
    x = rms_norm(x, params["final_norm_scale"], cfg.norm_eps)
    head = params["emb"].T if cfg.tie_embeddings else params["lm_head"]
    # lm_head is never quantized (paper §2.1.1)
    logits = linear(x, head, precision=precision, quantized=False)
    # logits are the single biggest activation (B,T,V f32): shard T over the
    # model axis so CE stays local (sequence-parallel loss)
    logits = constrain(logits.astype(jnp.float32), "logits")
    return logits


def _decoder_inputs(params, inputs, cfg, precision):
    """Returns (x (B,T,D), prefix_len).  VLM: patches prefix + text tokens."""
    tokens = inputs["tokens"]
    x = _embed(params, tokens)
    prefix_len = 0
    if cfg.frontend == "vision_patches":
        patches = inputs["patches"]                        # (B, P, D)
        proj = linear(patches, params["frontend"]["w_patch"],
                      precision=precision)
        x = jnp.concatenate([proj, x], axis=1)
        prefix_len = patches.shape[1]
    return x, prefix_len


def _train_mask(b, t, prefix_len, lengths=None):
    mask = jnp.tril(jnp.ones((t, t), bool))[None]
    if prefix_len:
        # prefix-LM: multimodal prefix is fully visible
        col = jnp.arange(t)[None, None, :]
        mask = jnp.logical_or(mask, col < prefix_len)
    if lengths is not None:
        mask = jnp.logical_and(mask,
                               (jnp.arange(t)[None] < lengths[:, None])[:, None])
    return mask


def _encode(params, frames, cfg, precision, src_lengths=None):
    """Bidirectional encoder over (projected) frame embeddings."""
    x = frames
    if cfg.frontend == "audio_frames":
        x = linear(x, params["frontend"]["w_patch"], precision=precision)
    enc_pattern = tuple(
        blocks_mod.SlotSpec(mixer=s.mixer, ffn=s.ffn, cross=False)
        for s in blocks_mod.layer_pattern(cfg, decoder=False))
    s_src = x.shape[1]
    mask = None
    if src_lengths is not None:
        valid = jnp.arange(s_src)[None] < src_lengths[:, None]
        mask = valid[:, None, :] & valid[:, :, None]

    def body(carry, slot_params):
        h = carry
        for j, spec in enumerate(enc_pattern):
            h, _, _, _ = blocks_mod.apply_slot_full(
                h, slot_params[f"s{j}"], spec, cfg, precision,
                mask=mask, causal=False, use_rope=True)
        return h, None

    x, _ = _scan(jax.checkpoint(body), x, params["enc"]["blocks"])
    return rms_norm(x, params["enc"]["final_norm_scale"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# training / scoring forward
# ---------------------------------------------------------------------------

def forward_train(
    params,
    inputs: dict,
    cfg,
    precision: Optional[PrecisionConfig] = None,
    *,
    forced_routing: Optional[dict] = None,   # {"slot_name": (R,B,T,K)} RRR
    want_routing: bool = False,
    remat: bool = True,
):
    """Full teacher-forced forward.  Returns (logits (B,T,V), aux)."""
    pattern = blocks_mod.layer_pattern(cfg)
    enc_out = None
    src_lengths = inputs.get("src_lengths")
    if cfg.is_encdec:
        enc_out = _encode(params, inputs["frames"], cfg, precision, src_lengths)

    x, prefix_len = _decoder_inputs(params, inputs, cfg, precision)
    b, t, _ = x.shape
    mask = _train_mask(b, t, prefix_len, inputs.get("lengths"))
    positions = jnp.arange(t)[None, :]
    x = constrain(x, "act_btd")

    moe_slots = [f"s{j}" for j, s in enumerate(pattern) if s.ffn == "moe"]

    def body(carry, xs):
        h = carry
        slot_params, forced = xs
        auxes = {}
        routing = {}
        for j, spec in enumerate(pattern):
            name = f"s{j}"
            h, aux, _, _ = blocks_mod.apply_slot_full(
                h, slot_params[name], spec, cfg, precision,
                mask=mask, positions=positions,
                enc_out=enc_out, src_lengths=src_lengths,
                lengths=inputs.get("lengths"), prefix_len=prefix_len,
                forced_topk=forced.get(name) if forced else None,
            )
            if spec.ffn == "moe":
                routing[name] = aux.pop("topk_idx")
                auxes[name] = aux
        ys = {"aux": auxes}
        if want_routing:
            ys["routing"] = routing
        return h, ys

    forced_xs = forced_routing if forced_routing is not None else \
        {name: None for name in moe_slots}
    if forced_routing is None:
        forced_xs = None
    body_fn = jax.checkpoint(body) if remat else body
    x, ys = _scan(body_fn, x, (params["blocks"], forced_xs))

    logits = _unembed(params, x, cfg, precision)
    aux = {"moe": ys.get("aux", {})}
    if want_routing:
        aux["routing"] = ys["routing"]
    if prefix_len:
        aux["prefix_len"] = prefix_len
    return logits, aux


def token_logprobs(params, inputs, cfg, precision=None, **kw):
    """log p(token_t | tokens_<t) for t >= 1 — the trainer-side scoring pass
    used for TIS ratios and mismatch KL (paper §2.1.3)."""
    logits, aux = forward_train(params, inputs, cfg, precision, **kw)
    tokens = inputs["tokens"]
    prefix = aux.get("prefix_len", 0)
    logits = logits[:, prefix:, :]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0], aux


# ---------------------------------------------------------------------------
# rollout cache
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, precision: PrecisionConfig,
               dtype=BF16, src_len: int = 0,
               page_size: Optional[int] = None,
               num_pages: Optional[int] = None) -> dict:
    """Rollout cache.  Default layout: one contiguous (B, max_len) region
    per sequence.  With `page_size` the self-attention KV entries become a
    *paged* pool (vLLM layout): per-layer pools of `num_pages` blocks of
    `page_size` tokens plus a per-sequence block table under
    cache["block_tables"] (W = ceil(max_len / page_size) entries each).

    When `num_pages` is omitted each sequence owns a contiguous run of
    blocks (identity tables) — the jit-friendly rollout configuration.
    When given, tables start at -1 (unmapped) and an external allocator
    (serving.BlockManager) assigns physical blocks.

    SSM states and cross-attention caches are per-sequence constant-size
    state and stay batch-indexed in either layout.
    """
    pattern = blocks_mod.layer_pattern(cfg)
    repeats = blocks_mod.n_repeats(cfg)

    def stack(make):
        one = make()
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (repeats,) + a.shape),
                            one)

    paged = page_size is not None
    if paged:
        pages_per_seq = -(-max_len // page_size)
        self_owned = num_pages is None
        if self_owned:
            num_pages = batch * pages_per_seq

    slots = {}
    has_kv = False
    for j, spec in enumerate(pattern):
        slot = {}
        if spec.mixer == "attn":
            has_kv = True
            if paged:
                slot["kv"] = stack(lambda: attn_mod.init_paged_kv_cache(
                    num_pages, page_size, cfg.n_kv_heads, cfg.d_head,
                    precision, dtype))
            else:
                slot["kv"] = stack(lambda: attn_mod.init_kv_cache(
                    batch, max_len, cfg.n_kv_heads, cfg.d_head, precision,
                    dtype))
        else:
            slot["ssm"] = stack(lambda: ssm_mod.init_ssm_state(batch, cfg, dtype))
        if spec.cross:
            slot["cross"] = stack(lambda: attn_mod.init_kv_cache(
                batch, max(src_len, 1), cfg.n_kv_heads, cfg.d_head, precision,
                dtype))
        slots[f"s{j}"] = slot
    cache = {
        "slots": slots,
        "lengths": jnp.zeros((batch,), jnp.int32),
    }
    if paged and has_kv:
        if self_owned:
            cache["block_tables"] = jnp.arange(
                batch * pages_per_seq, dtype=jnp.int32).reshape(
                batch, pages_per_seq)
        else:
            cache["block_tables"] = jnp.full(
                (batch, pages_per_seq), -1, jnp.int32)
    if cfg.is_encdec:
        cache["src_lengths"] = jnp.full((batch,), max(src_len, 1), jnp.int32)
    return cache


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def prefill(
    params,
    inputs: dict,
    cache: dict,
    cfg,
    precision: PrecisionConfig,
    *,
    want_routing: bool = False,
    remat: bool = True,
):
    """Process the prompt, fill caches, return logits at the last valid
    position (B, V).  `inputs["lengths"]` gives per-sequence prompt lengths."""
    pattern = blocks_mod.layer_pattern(cfg)
    lengths = inputs["lengths"]
    src_lengths = cache.get("src_lengths")

    if cfg.is_encdec:
        enc_out = _encode(params, inputs["frames"], cfg, precision,
                          inputs.get("src_lengths"))
        if inputs.get("src_lengths") is not None:
            src_lengths = inputs["src_lengths"]
        # build cross caches (quantized once — DESIGN §6); the previous
        # cache's per-layer scales seed the fresh one so post-calibration
        # prefills reuse the calibrated globals (vLLM scale semantics)
        for j, spec in enumerate(pattern):
            if spec.cross:
                cross_params = jax.tree.map(
                    lambda a: a, params["blocks"][f"s{j}"]["cross"])
                old = cache["slots"][f"s{j}"].get("cross")
                if old is not None:
                    cache["slots"][f"s{j}"]["cross"] = jax.vmap(
                        lambda p, ks, vs: attn_mod.cross_attention_cache(
                            enc_out, p, cfg, precision, k_scale=ks,
                            v_scale=vs)
                    )(cross_params, old.k_scale, old.v_scale)
                else:
                    cache["slots"][f"s{j}"]["cross"] = jax.vmap(
                        lambda p: attn_mod.cross_attention_cache(
                            enc_out, p, cfg, precision)
                    )(cross_params)
        cache["src_lengths"] = src_lengths

    x, prefix_len = _decoder_inputs(params, inputs, cfg, precision)
    b, t, _ = x.shape
    positions = jnp.arange(t)[None, :]
    eff_lengths = lengths + prefix_len
    block_tables = cache.get("block_tables")

    def body(carry, xs):
        h = carry
        slot_params, slot_caches = xs
        new_caches = {}
        routing = {}
        for j, spec in enumerate(pattern):
            name = f"s{j}"
            sc = slot_caches.get(name, {})
            h, aux, new_kv, new_ssm = blocks_mod.apply_slot_full(
                h, slot_params[name], spec, cfg, precision,
                positions=positions, lengths=eff_lengths,
                kv_cache=sc.get("kv"),
                ssm_state=sc.get("ssm"), want_ssm_state=True,
                cross_cache=sc.get("cross"), src_lengths=src_lengths,
                block_tables=block_tables,
            )
            nc = {}
            if new_kv is not None:
                nc["kv"] = new_kv
            if new_ssm is not None:
                nc["ssm"] = new_ssm
            if "cross" in sc:
                nc["cross"] = sc["cross"]
            new_caches[name] = nc
            if spec.ffn == "moe" and want_routing:
                routing[name] = aux["topk_idx"]
        ys = {"caches": new_caches}
        if want_routing:
            ys["routing"] = routing
        return h, ys

    body_fn = jax.checkpoint(body) if remat else body
    x, ys = _scan(body_fn, x, (params["blocks"], cache["slots"]))
    cache = dict(cache, slots=ys["caches"], lengths=eff_lengths)

    idx = jnp.clip(eff_lengths - 1, 0, t - 1)
    x_last = x[jnp.arange(b), idx]                            # (B, D)
    logits = _unembed(params, x_last, cfg, precision)
    out = (logits, cache)
    if want_routing:
        out = out + (ys["routing"],)
    return out


def prefill_chunk(
    params,
    tokens: jax.Array,        # (B, C) one chunk of prompt tokens
    start: jax.Array,         # (B,) tokens already written to the cache
    chunk_lengths: jax.Array,  # (B,) valid tokens in this chunk (<= C)
    cache: dict,
    cfg,
    precision: PrecisionConfig,
    *,
    use_kernel: bool = False,
    want_all_logits: bool = False,
):
    """Process one prompt chunk of a *paged* cache (continuous-batching
    chunked prefill): scatter the chunk's KV at positions
    [start, start+chunk_lengths) and return logits at the chunk's last
    valid position — or, with `want_all_logits=True`, at EVERY chunk
    position (B, C, V).  The all-logits form is the speculative-decoding
    scorer: the verify pass feeds [pending, draft_1..draft_k] as one
    chunk and needs the target distribution at each of the k+1 positions
    to run rejection sampling (`core.sampling.rejection_sample`).

    Attention reads earlier chunks back from the pool through the block
    table — `use_kernel=True` routes it through the Pallas
    `fp8_paged_prefill_attention` (scalar-prefetched tables, in-kernel
    dequant; interpret-mode on CPU, compiled on TPU), otherwise a jnp
    gather — so a prompt of any length streams through one fixed-width
    (C) trace instead of one fixed-width-`prompt_pad` trace per
    admission.
    SSM slots carry their recurrent state chunk-to-chunk (padded positions
    in a ragged final chunk are state no-ops — see `ssm_forward`), so
    hybrid and attention-free models stream through this path too;
    enc-dec/VLM inputs are not supported (they prefill one-shot).
    """
    pattern = blocks_mod.layer_pattern(cfg)
    has_attn = any(s.mixer == "attn" for s in pattern)
    assert not has_attn or cache.get("block_tables") is not None, \
        "chunked prefill needs a paged cache with block tables"
    assert not cfg.is_encdec and cfg.frontend is None, \
        "chunked prefill serves decoder-only text models"
    x = _embed(params, tokens)
    b, c, _ = x.shape
    new_lengths = start + chunk_lengths
    block_tables = cache.get("block_tables")

    def body(carry, xs):
        h = carry
        slot_params, slot_caches = xs
        new_caches = {}
        for j, spec in enumerate(pattern):
            name = f"s{j}"
            sc = slot_caches.get(name, {})
            h, _, new_kv, new_ssm = blocks_mod.apply_slot_full(
                h, slot_params[name], spec, cfg, precision,
                lengths=new_lengths, kv_cache=sc.get("kv"),
                ssm_state=sc.get("ssm"), want_ssm_state=True,
                block_tables=block_tables, chunk_start=start,
                use_kernel=use_kernel,
            )
            nc = {}
            if new_kv is not None:
                nc["kv"] = new_kv
            if new_ssm is not None:
                nc["ssm"] = new_ssm
            new_caches[name] = nc
        return h, {"caches": new_caches}

    x, ys = _scan(body, x, (params["blocks"], cache["slots"]))
    cache = dict(cache, slots=ys["caches"], lengths=new_lengths)

    if want_all_logits:
        return _unembed(params, x, cfg, precision), cache     # (B, C, V)
    idx = jnp.clip(chunk_lengths - 1, 0, c - 1)
    x_last = x[jnp.arange(b), idx]                            # (B, D)
    logits = _unembed(params, x_last, cfg, precision)
    return logits, cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def decode_step(
    params,
    tokens: jax.Array,        # (B,) last sampled token ids
    cache: dict,
    cfg,
    precision: PrecisionConfig,
    *,
    want_routing: bool = False,
    use_kernel: bool = False,
):
    """One autoregressive step.  Returns (logits (B,V), cache, aux).

    `use_kernel=True` routes attention through the Pallas decode kernels
    (`fp8_paged_decode_attention` for paged caches) — interpret-mode on
    CPU, compiled on TPU.
    """
    pattern = blocks_mod.layer_pattern(cfg)
    lengths = cache["lengths"]
    src_lengths = cache.get("src_lengths")
    block_tables = cache.get("block_tables")
    x = _embed(params, tokens)[:, None, :]                    # (B,1,D)

    def body(carry, xs):
        h = carry
        slot_params, slot_caches = xs
        new_caches = {}
        routing = {}
        for j, spec in enumerate(pattern):
            name = f"s{j}"
            sc = slot_caches.get(name, {})
            h, aux, new_kv, new_ssm = blocks_mod.apply_slot_decode(
                h, slot_params[name], spec, cfg, precision,
                kv_cache=sc.get("kv"), ssm_state=sc.get("ssm"),
                cross_cache=sc.get("cross"), src_lengths=src_lengths,
                lengths=lengths, block_tables=block_tables,
                use_kernel=use_kernel,
            )
            nc = {}
            if new_kv is not None:
                nc["kv"] = new_kv
            if new_ssm is not None:
                nc["ssm"] = new_ssm
            if "cross" in sc:
                nc["cross"] = sc["cross"]
            new_caches[name] = nc
            if spec.ffn == "moe" and want_routing:
                routing[name] = aux["topk_idx"]
        ys = {"caches": new_caches}
        if want_routing:
            ys["routing"] = routing
        return h, ys

    x, ys = _scan(body, x, (params["blocks"], cache["slots"]))
    with scope("kv_write"):
        lengths = lengths + 1
    cache = dict(cache, slots=ys["caches"], lengths=lengths)
    logits = _unembed(params, x[:, 0], cfg, precision)
    aux = {"routing": ys["routing"]} if want_routing else {}
    return logits, cache, aux
