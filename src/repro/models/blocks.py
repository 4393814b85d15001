"""Layer-pattern assembly: maps an ArchConfig onto a repeating block pattern.

A model is `n_layers = R * len(pattern)` layers; the pattern captures the
within-period layer structure (jamba: 1 attention per 8 layers, MoE every
2nd layer; dense: a single attn+mlp slot) so the whole depth is a
`jax.lax.scan` over R repeats — keeping HLO size O(pattern), which is what
makes the 88-/72-layer dry-runs compile in seconds.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import KeyGen, constrain, rms_norm
from repro.obs.scopes import scope


@dataclasses.dataclass(frozen=True)
class SlotSpec:
    mixer: str                 # "attn" | "ssm"
    ffn: Optional[str]         # "mlp" | "moe" | None
    cross: bool = False        # enc-dec decoder slot


def layer_pattern(cfg, decoder: bool = True) -> Tuple[SlotSpec, ...]:
    period = 1
    if cfg.attn_period > 1:
        period = cfg.attn_period
    if cfg.n_experts and cfg.moe_period > 1:
        period = math.lcm(period, cfg.moe_period)
    n = cfg.n_layers if decoder else cfg.n_enc_layers
    assert n % period == 0, (n, period, cfg.name)
    slots = []
    for j in range(period):
        if cfg.attention_free:
            mixer = "ssm"
        elif cfg.ssm_state and not cfg.is_attn_layer(j):
            mixer = "ssm"
        else:
            mixer = "attn"
        if cfg.family == "ssm":
            ffn = None                      # mamba2 blocks have no MLP
        elif cfg.is_moe_layer(j):
            ffn = "moe"
        else:
            ffn = "mlp"
        slots.append(SlotSpec(mixer=mixer, ffn=ffn,
                              cross=decoder and cfg.is_encdec))
    return tuple(slots)


def n_repeats(cfg, decoder: bool = True) -> int:
    n = cfg.n_layers if decoder else cfg.n_enc_layers
    return n // len(layer_pattern(cfg, decoder))


def init_slot_params(keygen: KeyGen, spec: SlotSpec, cfg, dtype) -> dict:
    p = {}
    if spec.mixer == "attn":
        p["attn"] = attn_mod.init_attn_params(keygen, cfg, dtype)
    else:
        p["ssm"] = ssm_mod.init_ssm_params(keygen, cfg, dtype)
    if spec.cross:
        p["cross"] = attn_mod.init_attn_params(keygen, cfg, dtype, cross=True)
    if spec.ffn == "mlp":
        p["mlp"] = mlp_mod.init_mlp_params(keygen, cfg, dtype)
    elif spec.ffn == "moe":
        p["moe"] = moe_mod.init_moe_params(keygen, cfg, dtype)
    return p


# ---------------------------------------------------------------------------
# Slot application — full sequence (train / score / encoder / prefill)
# ---------------------------------------------------------------------------

@scope("residual")
def _norm(x, p, cfg):
    """A sublayer's pre-norm."""
    return rms_norm(x, p["norm_scale"], cfg.norm_eps)


@scope("residual")
def _residual(x, h):
    return x + h


def apply_slot_full(
    x, slot_params, spec: SlotSpec, cfg, precision,
    *,
    mask=None, positions=None, causal=True,
    kv_cache=None,                 # KVCache -> prefill mode
    ssm_state=None, want_ssm_state=False,
    cross_cache=None, src_lengths=None, enc_out=None,
    lengths=None,
    prefix_len=0,
    forced_topk=None,
    use_rope=True,
    block_tables=None,             # (B, W) when kv_cache is paged
    chunk_start=None,              # (B,) -> chunked prefill of [start, start+C)
    use_kernel=False,              # chunk attention through the Pallas kernel
):
    """Returns (x, aux_dict, new_kv_cache, new_ssm_state)."""
    aux = {}
    new_kv = None
    new_ssm = None

    if spec.mixer == "attn":
        p = slot_params["attn"]
        xn = _norm(x, p, cfg)
        if kv_cache is not None and chunk_start is not None:
            h, new_kv = attn_mod.attention_prefill_chunk(
                xn, p, cfg, kv_cache, precision, start=chunk_start,
                lengths=lengths, block_tables=block_tables,
                use_rope=use_rope, use_kernel=use_kernel)
        elif kv_cache is not None:
            h, new_kv = attn_mod.attention_prefill(
                xn, p, cfg, kv_cache, precision, lengths=lengths,
                positions=positions, use_rope=use_rope,
                block_tables=block_tables)
        else:
            h = attn_mod.attention_forward(
                xn, p, cfg, precision, positions=positions, mask=mask,
                causal=causal, use_rope=use_rope,
                prefix_len=prefix_len, lengths=lengths)
        x = _residual(x, h)
    else:
        p = slot_params["ssm"]
        xn = _norm(x, p, cfg)
        # when the recurrent state is carried out (prefill / chunked
        # prefill), padded positions must be state no-ops: `lengths` is the
        # total valid length, so inside a chunk starting at `chunk_start`
        # the valid region is the first (lengths - chunk_start) positions
        ssm_lengths = None
        if want_ssm_state and lengths is not None:
            ssm_lengths = lengths - chunk_start if chunk_start is not None \
                else lengths
        h, new_ssm = ssm_mod.ssm_forward(
            xn, p, cfg, precision, state=ssm_state,
            return_state=want_ssm_state, lengths=ssm_lengths)
        x = _residual(x, h)

    if spec.cross and enc_out is not None or (spec.cross and cross_cache is not None):
        p = slot_params["cross"]
        xn = _norm(x, p, cfg)
        if cross_cache is None:
            # training path: direct cross attention over encoder output
            src_mask = None
            if src_lengths is not None:
                s_src = enc_out.shape[1]
                src_mask = (jnp.arange(s_src)[None] < src_lengths[:, None])[:, None, :]
            h = attn_mod.attention_forward(
                xn, p, cfg, precision, mask=src_mask, causal=False,
                kv_src=enc_out, use_rope=False)
        else:
            h = attn_mod.cross_attention_decode(
                xn, p, cfg, cross_cache, src_lengths, precision)
        x = _residual(x, h)

    if spec.ffn == "mlp":
        p = slot_params["mlp"]
        xn = _norm(x, p, cfg)
        x = _residual(x, mlp_mod.mlp_forward(xn, p, cfg, precision))
    elif spec.ffn == "moe":
        p = slot_params["moe"]
        xn = _norm(x, p, cfg)
        h, moe_aux = moe_mod.moe_forward(
            xn, p, cfg, precision, forced_topk_idx=forced_topk)
        x = _residual(x, h)
        aux.update(moe_aux)
    x = constrain(x, "act_btd")
    return x, aux, new_kv, new_ssm


# ---------------------------------------------------------------------------
# Slot application — single-token decode
# ---------------------------------------------------------------------------

def apply_slot_decode(
    x, slot_params, spec: SlotSpec, cfg, precision,
    *,
    kv_cache=None, ssm_state=None,
    cross_cache=None, src_lengths=None,
    lengths=None,
    forced_topk=None,
    block_tables=None,             # (B, W) when kv_cache is paged
    use_kernel=False,              # route attention through the Pallas kernel
):
    aux = {}
    new_kv, new_ssm = kv_cache, ssm_state

    if spec.mixer == "attn":
        p = slot_params["attn"]
        xn = _norm(x, p, cfg)
        h, new_kv = attn_mod.attention_decode(
            xn, p, cfg, kv_cache, lengths, precision,
            block_tables=block_tables, use_kernel=use_kernel)
        x = _residual(x, h)
    else:
        p = slot_params["ssm"]
        xn = _norm(x, p, cfg)
        h, new_ssm = ssm_mod.ssm_decode(xn, p, cfg, ssm_state, precision)
        x = _residual(x, h)

    if spec.cross:
        p = slot_params["cross"]
        xn = _norm(x, p, cfg)
        x = _residual(x, attn_mod.cross_attention_decode(
            xn, p, cfg, cross_cache, src_lengths, precision))

    if spec.ffn == "mlp":
        p = slot_params["mlp"]
        xn = _norm(x, p, cfg)
        x = _residual(x, mlp_mod.mlp_forward(xn, p, cfg, precision))
    elif spec.ffn == "moe":
        p = slot_params["moe"]
        xn = _norm(x, p, cfg)
        h, moe_aux = moe_mod.moe_forward(
            xn, p, cfg, precision, forced_topk_idx=forced_topk)
        x = _residual(x, h)
        aux.update(moe_aux)
    return x, aux, new_kv, new_ssm
