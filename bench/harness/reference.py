"""The plain reference of the dense decoders the benchmark runs.

A Qwen3 or Mistral decoder written out in `jax.numpy` in float32 at the
highest matmul precision: pre-norm RMSNorm, GQA attention with RoPE
(rotate-half, as the published models), per-head RMSNorm on q and k where
the configuration has it, a SwiGLU MLP, a final RMSNorm and an untied
output head.  It imports nothing of the program; it reads the weight tree
by its leaf names (stacked over layers on the leading axis), and those
weights are the benchmark's own (`weights.py`).

`quant` puts the reference in a lower precision for the controls: "fp8"
(E4M3) or "int4", applied where the program's fp8 recipe quantizes: the
layer linears with 128 x 128 weight blocks and 1 x 128 activation tiles,
and q, K, V and the attention probabilities per tensor.  The output head
is never quantized.  Gradients pass straight through the rounding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
BLOCK = 128


def _round(x, quant):
    """Round x (already divided by its scale) to the grid of `quant`."""
    if quant == "fp8":
        return jnp.clip(x, -E4M3_MAX, E4M3_MAX).astype(
            jnp.float8_e4m3fn).astype(F32)
    if quant == "int4":
        return jnp.clip(jnp.round(x), -8, 7)
    raise ValueError(quant)


def _grid_max(quant) -> float:
    return E4M3_MAX if quant == "fp8" else 7.0


def qdq(x, quant, tile=None):
    """Quantize-dequantize x with one absmax scale per tile of the last
    two axes (`tile` = (rows, cols)), or per tensor when tile is None.
    Straight-through: the gradient is the identity."""
    if quant is None:
        return x
    xf = x.astype(F32)
    if tile is None:
        s = jnp.max(jnp.abs(xf)) / _grid_max(quant)
        s = jnp.where(s > 0, s, 1.0)
        q = _round(xf / s, quant) * s
    else:
        r, c = tile
        *lead, m, n = xf.shape
        r = min(r, m)
        c = min(c, n)
        pm, pn = (-m) % r, (-n) % c
        xp = jnp.pad(xf, [(0, 0)] * len(lead) + [(0, pm), (0, pn)])
        mb, nb = (m + pm) // r, (n + pn) // c
        blocks = xp.reshape(*lead, mb, r, nb, c)
        s = jnp.max(jnp.abs(blocks), axis=(-3, -1), keepdims=True) \
            / _grid_max(quant)
        s = jnp.where(s > 0, s, 1.0)
        q = (_round(blocks / s, quant) * s).reshape(
            *lead, m + pm, n + pn)[..., :m, :n]
    return xf + jax.lax.stop_gradient(q - xf)


def linear(x, w, quant=None):
    w = w.astype(F32)
    if quant is not None:
        w = qdq(w, quant, (BLOCK, BLOCK))
        x = qdq(x, quant, (1, BLOCK))
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, g, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def rope(x, positions, theta):
    """Rotate-half RoPE.  x (B, T, H, D); positions (B, T)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions[..., None].astype(F32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, p, c, positions, quant):
    b, t, _ = x.shape
    h, kvh, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    q = linear(x, p["wq"], quant).reshape(b, t, h, dh)
    k = linear(x, p["wk"], quant).reshape(b, t, kvh, dh)
    v = linear(x, p["wv"], quant).reshape(b, t, kvh, dh)
    if c.get("qk_norm"):
        q = rms_norm(q, p["q_norm_scale"], c["rms_norm_eps"])
        k = rms_norm(k, p["k_norm_scale"], c["rms_norm_eps"])
    q = rope(q, positions, c["rope_theta"])
    k = rope(k, positions, c["rope_theta"])
    if quant is not None:
        q, k, v = qdq(q, quant), qdq(k, quant), qdq(v, quant)
    qg = q.reshape(b, t, kvh, h // kvh, dh)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k, precision=HIGHEST) \
        * dh ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    if quant is not None:
        pr = qdq(pr, quant)
    o = jnp.einsum("bkgts,bskd->btkgd", pr, v, precision=HIGHEST)
    return linear(o.reshape(b, t, h * dh), p["wo"], quant)


def mlp(x, p, quant):
    g = linear(x, p["wg"], quant)
    u = linear(x, p["wu"], quant)
    return linear(jax.nn.silu(g) * u, p["wd"], quant)


def layer_params(weights, i):
    return jax.tree.map(lambda a: a[i], weights["blocks"]["s0"])


def hidden(weights, tokens, c, quant=None):
    """Final-norm hidden states (B, T, D) of a causal pass over tokens."""
    eps = c["rms_norm_eps"]
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    x = jnp.take(weights["emb"], tokens, axis=0).astype(F32)

    # each layer is recomputed in the backward pass, so the float32 copies
    # of only one layer's weights are alive at a time
    @jax.checkpoint
    def layer(x, p):
        x = x + attention(rms_norm(x, p["attn"]["norm_scale"], eps),
                          p["attn"], c, positions, quant)
        return x + mlp(rms_norm(x, p["mlp"]["norm_scale"], eps), p["mlp"],
                       quant)

    for i in range(c["num_hidden_layers"]):
        x = layer(x, layer_params(weights, i))
    return rms_norm(x, weights["final_norm_scale"], eps)


def token_logps(weights, tokens, c, quant=None):
    """log p(tokens[:, t+1] | tokens[:, :t+1]) for every t: (B, T-1)."""
    hs = hidden(weights, tokens, c, quant)[:, :-1]
    logits = jnp.matmul(hs, weights["lm_head"].astype(F32),
                        precision=HIGHEST)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
