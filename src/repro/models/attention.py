"""GQA attention with a quantizable KV cache (paper §2.3).

The KV cache stores fp8 payloads plus per-layer k/v scales.  Scales are
recalibrated at prefill time when `precision.calculate_kv_scales` is set —
the inference-side calibration paradigm (paper fig 7): the first forward
pass after each weight sync observes the fresh policy's K/V amax.  The
trainer-side paradigm passes pre-computed scales in through `KVCache`.

"Full FP8" (paper §2.3.2) additionally quantizes the attention *compute*:
Q/K/V and the softmax output P go through E4M3 QDQ before the matmuls.
"""
from __future__ import annotations

import contextlib
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.fp8_linear import linear
from repro.core.precision import E4M3, PrecisionConfig
from repro.core.quant import (
    calibrate_scale,
    dequantize_per_tensor,
    qdq,
    quantize_per_tensor,
)
from repro.models.common import apply_rope, constrain, dense_init, rms_norm

_NEG_INF = -1e30


class KVCache(NamedTuple):
    """Single-layer KV cache.  When layers are scanned the whole structure is
    stacked along a leading layer axis by `jax.lax.scan`."""

    k: jax.Array          # (B, S_max, KVH, D) fp8 or bf16
    v: jax.Array          # (B, S_max, KVH, D)
    k_scale: jax.Array    # () f32
    v_scale: jax.Array    # () f32

    @property
    def quantized(self) -> bool:
        return self.k.dtype in (jnp.float8_e4m3fn, jnp.float8_e5m2)


def init_kv_cache(batch: int, max_len: int, n_kv_heads: int, d_head: int,
                  precision: PrecisionConfig, dtype=jnp.bfloat16) -> KVCache:
    kv_dtype = E4M3 if precision.kv_quantized else dtype
    shape = (batch, max_len, n_kv_heads, d_head)
    return KVCache(
        k=jnp.zeros(shape, kv_dtype),
        v=jnp.zeros(shape, kv_dtype),
        k_scale=jnp.ones((), jnp.float32),
        v_scale=jnp.ones((), jnp.float32),
    )


class PagedKVCache(NamedTuple):
    """Single-layer *paged* KV cache: a pool of fixed-size token blocks
    shared by all sequences, addressed through per-sequence block tables
    (vLLM's PagedAttention layout).

    The pool carries one extra block at index `num_blocks` — the *trash
    block*: writes for invalid table entries (-1) and padded prompt
    positions are routed there so a fused scatter needs no branching, and
    reads from it are masked out by `lengths`.
    """

    k: jax.Array          # (N+1, BS, KVH, D) fp8 or bf16; row N = trash
    v: jax.Array          # (N+1, BS, KVH, D)
    k_scale: jax.Array    # () f32 (per-layer, shared by every block)
    v_scale: jax.Array    # () f32

    @property
    def quantized(self) -> bool:
        return self.k.dtype in (jnp.float8_e4m3fn, jnp.float8_e5m2)

    @property
    def block_size(self) -> int:
        return self.k.shape[1]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[0] - 1          # minus the trash block


def init_paged_kv_cache(num_blocks: int, block_size: int, n_kv_heads: int,
                        d_head: int, precision: PrecisionConfig,
                        dtype=jnp.bfloat16) -> PagedKVCache:
    kv_dtype = E4M3 if precision.kv_quantized else dtype
    shape = (num_blocks + 1, block_size, n_kv_heads, d_head)
    return PagedKVCache(
        k=jnp.zeros(shape, kv_dtype),
        v=jnp.zeros(shape, kv_dtype),
        k_scale=jnp.ones((), jnp.float32),
        v_scale=jnp.ones((), jnp.float32),
    )


def _paged_physical(cache: PagedKVCache, block_tables: jax.Array) -> jax.Array:
    """Map logical table entries to physical pool rows (-1 -> trash)."""
    trash = cache.k.shape[0] - 1
    return jnp.where(block_tables < 0, trash, block_tables)


def _live_blocks(context_lengths, w: int, bs: int) -> int:
    """Static count of leading table entries that can hold live context:
    `ceil(max(context_lengths) / bs)` when the lengths are concrete (the
    serving engine's eager hot loop — decode then stops paying
    `max_seq_len` bytes per step), the full width `w` under tracing
    (jit: shapes must stay static, e.g. the rollout while-loop)."""
    if isinstance(context_lengths, jax.core.Tracer):
        return w
    m = int(jnp.max(context_lengths)) if context_lengths.size else 0
    return max(1, min(w, -(-m // bs)))


def paged_write(cache: PagedKVCache, block_tables: jax.Array,
                positions: jax.Array, valid: jax.Array,
                kq: jax.Array, vq: jax.Array) -> PagedKVCache:
    """Scatter quantized K/V rows into the pool through the block table.

    block_tables (B, W); positions (B, S) token positions; valid (B, S)
    write mask (invalid rows land in the trash block); kq/vq (B, S, KVH, D)
    already in the cache dtype.
    """
    bs = cache.block_size
    w = block_tables.shape[1]
    blk = jnp.clip(positions // bs, 0, w - 1)
    off = positions % bs
    entry = jnp.take_along_axis(block_tables, blk, axis=1)      # (B, S)
    trash = cache.k.shape[0] - 1
    phys = jnp.where(jnp.logical_and(valid, entry >= 0), entry, trash)
    return cache._replace(
        k=cache.k.at[phys, off].set(kq),
        v=cache.v.at[phys, off].set(vq),
    )


def paged_copy_rows(cache: PagedKVCache, src, dst) -> PagedKVCache:
    """Copy physical pool rows `src` -> `dst` — the device half of
    copy-on-write: duplicate a shared block's K/V into a writer's private
    block *before* its first divergent append lands.

    Indexes the pool-row axis from the right so it works on both a single
    layer's cache (N+1, BS, KVH, D) and the scan-stacked engine form
    (R, N+1, BS, KVH, D).  Scales are per-layer globals and stay put.
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return cache._replace(
        k=cache.k.at[..., dst, :, :, :].set(cache.k[..., src, :, :, :]),
        v=cache.v.at[..., dst, :, :, :].set(cache.v[..., src, :, :, :]),
    )


def init_attn_params(keygen, cfg, dtype=jnp.bfloat16, cross: bool = False) -> dict:
    d, h, kvh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    p = {
        "wq": dense_init(keygen(), (d, h * dh), d, dtype),
        "wk": dense_init(keygen(), (d, kvh * dh), d, dtype),
        "wv": dense_init(keygen(), (d, kvh * dh), d, dtype),
        "wo": dense_init(keygen(), (h * dh, d), h * dh, dtype),
        "norm_scale": jnp.ones((d,), dtype),
    }
    if cfg.qk_norm and not cross:
        p["q_norm_scale"] = jnp.ones((dh,), dtype)
        p["k_norm_scale"] = jnp.ones((dh,), dtype)
    return p


def _project_qkv(x, params, cfg, precision, kv_src=None):
    """Returns q (B,S,H,D), k/v (B,S',KVH,D) in bf16 (pre-RoPE)."""
    b, s, _ = x.shape
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = linear(x, params["wq"], precision=precision).reshape(b, s, h, dh)
    src = x if kv_src is None else kv_src
    sk = src.shape[1]
    k = linear(src, params["wk"], precision=precision).reshape(b, sk, kvh, dh)
    v = linear(src, params["wv"], precision=precision).reshape(b, sk, kvh, dh)
    if cfg.qk_norm and "q_norm_scale" in params:
        q = rms_norm(q, params["q_norm_scale"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm_scale"], cfg.norm_eps)
    # head-parallel (or seq-parallel fallback) so the O(S^2) score tensor
    # shards over the model axis — see ShardingRules.activation("act_qkv");
    # K/V sharding must stay compatible with q's (act_kv rule)
    q = constrain(q, "act_qkv")
    k = constrain(k, "act_kv", n_heads=cfg.n_heads)
    v = constrain(v, "act_kv", n_heads=cfg.n_heads)
    return q, k, v


# ---------------------------------------------------------------------------
# attention implementation selector (§Perf iteration: "chunked" computes
# online-softmax attention over KV blocks — the score matrix never
# materializes at (S, S), killing the memory-roofline term and the peak-HBM
# blowup of long-context train/prefill).  Default "naive" is the baseline.
# ---------------------------------------------------------------------------

_IMPL_CTX = threading.local()


@contextlib.contextmanager
def attention_impl(name: str):
    # naive   — (kvh, g)-grouped scores (baseline)
    # chunked — online-softmax over KV blocks (kills (S,S) materialization)
    # repeat  — repeat_kv to flat heads: the (kvh,g) reshape cannot be
    #           head-sharded when kvh < tp; repeating K/V to n_heads keeps
    #           a clean flat head axis that tp divides (§Perf iteration 4)
    assert name in ("naive", "chunked", "repeat"), name
    prev = getattr(_IMPL_CTX, "impl", "naive")
    _IMPL_CTX.impl = name
    try:
        yield
    finally:
        _IMPL_CTX.impl = prev


def _impl() -> str:
    return getattr(_IMPL_CTX, "impl", "naive")


def _sdpa_chunked(q, k, v, precision, cfg, *, prefix_len: int = 0,
                  lengths: Optional[jax.Array] = None, kv_chunk: int = 1024):
    """Online-softmax attention over KV chunks (causal [+ prefix / lengths]).

    q (B,S,H,D); k/v (B,S',KVH,D).  Equivalent to the naive path up to f32
    accumulation order; scores exist only at (..., S, C) per chunk.
    """
    b, s, h, dh = q.shape
    s_kv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    if precision is not None and precision.quantize_attention:
        q, k, v = qdq(q), qdq(k), qdq(v)
    c = min(kv_chunk, s_kv)
    pad = (-s_kv) % c
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (s_kv + pad) // c
    kc = k.reshape(b, nc, c, kvh, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, c, kvh, dh).transpose(1, 0, 2, 3, 4)
    qg = q.reshape(b, s, kvh, g, dh)
    q_pos = jnp.arange(s)[:, None]

    def body(carry, inp):
        m, l, acc = carry
        k_blk, v_blk, idx = inp                        # (B,C,KVH,D), scalar
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k_blk).astype(
            jnp.float32) * (dh ** -0.5)                # (B,KVH,G,S,C)
        k_pos = idx * c + jnp.arange(c)[None, :]
        mask = k_pos <= q_pos                          # causal (S, C)
        if prefix_len:
            mask = jnp.logical_or(mask, k_pos < prefix_len)
        mask = jnp.broadcast_to(mask, (b, 1, 1, s, c))
        if lengths is not None:
            mask = jnp.logical_and(
                mask, (k_pos[None] < lengths[:, None, None])[:, None, None])
        scores = jnp.where(mask, scores, _NEG_INF)
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(scores - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if precision is not None and precision.quantize_attention:
            # fp8 PV matmul: quantize the (unnormalized) probabilities per
            # chunk — same E4M3 cast as the naive path applies per full row
            p = qdq(p.astype(jnp.bfloat16)).astype(jnp.float32)
        pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(v_blk.dtype), v_blk)
        acc_new = acc * alpha[..., 0][..., None] + pv.astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, g, s, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, s, 1), jnp.float32)
    acc0 = jnp.zeros((b, kvh, g, s, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kc, vc, jnp.arange(nc)))
    out = acc / jnp.maximum(l[..., 0][..., None], 1e-30)
    # (B,KVH,G,S,D) -> (B,S,H*D)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, h * dh)
    return out.astype(q.dtype)


def _sdpa(q, k, v, mask, precision: Optional[PrecisionConfig], cfg):
    """q (B,S,H,D), k/v (B,S',KVH,D) bf16; mask broadcast (B,1,S,S') or None."""
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    if precision is not None and precision.quantize_attention:
        q, k, v = qdq(q), qdq(k), qdq(v)
    if _impl() == "repeat" and g > 1:
        # flat-head attention: duplicate K/V across the group dim so the
        # score tensor keeps a single head axis that tp can shard evenly
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        k = constrain(k, "act_qkv")
        v = constrain(v, "act_qkv")
        scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32)
        scores = scores * (dh ** -0.5)
        if mask is not None:
            scores = jnp.where(mask[:, None], scores, _NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        if precision is not None and precision.quantize_attention:
            p = qdq(p.astype(jnp.bfloat16)).astype(jnp.float32)
        out = jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v)
        return out.reshape(b, s, h * dh)
    qg = q.reshape(b, s, kvh, g, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    scores = scores * (dh ** -0.5)
    if mask is not None:
        scores = jnp.where(mask[:, None, None], scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    if precision is not None and precision.quantize_attention:
        p = qdq(p.astype(jnp.bfloat16)).astype(jnp.float32)
    out = jnp.einsum("bkgst,btkd->bskgd", p.astype(v.dtype), v)
    return out.reshape(b, s, h * dh)


def causal_mask(s: int, dtype=bool) -> jax.Array:
    return jnp.tril(jnp.ones((s, s), dtype))


def attention_forward(
    x: jax.Array,
    params: dict,
    cfg,
    precision: Optional[PrecisionConfig] = None,
    *,
    positions: Optional[jax.Array] = None,
    mask: Optional[jax.Array] = None,       # (B, S, S') or None => causal
    causal: bool = True,
    kv_src: Optional[jax.Array] = None,     # cross-attention source
    use_rope: bool = True,
    prefix_len: int = 0,
    lengths: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-sequence attention (training / scoring / encoder)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(x, params, cfg, precision, kv_src)
    if use_rope and kv_src is None:
        if positions is None:
            positions = jnp.arange(s)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if _impl() == "chunked" and causal and kv_src is None:
        out = _sdpa_chunked(q, k, v, precision, cfg,
                            prefix_len=prefix_len, lengths=lengths)
    else:
        if mask is None and causal and kv_src is None:
            mask = causal_mask(s)[None]
        out = _sdpa(q, k, v, mask, precision, cfg)
    out = constrain(out, "act_btd")
    return linear(out, params["wo"], precision=precision)


# ---------------------------------------------------------------------------
# Rollout path: prefill + decode against the (possibly fp8) cache
# ---------------------------------------------------------------------------

def _quantize_kv(k, v, cache: KVCache, precision: PrecisionConfig,
                 recalibrate: bool):
    """Quantize fresh K/V for cache insertion.

    recalibrate=True  -> inference-side calibration: scales from this
                         tensor's amax (per-step QKV scale recalibration).
    recalibrate=False -> reuse cache scales (decode steps / trainer-side).
    """
    if not cache.quantized:
        return k.astype(cache.k.dtype), v.astype(cache.v.dtype), cache
    if recalibrate and precision.calculate_kv_scales:
        k_scale = calibrate_scale(jnp.abs(k.astype(jnp.float32)).max(),
                                  margin=1.05)
        v_scale = calibrate_scale(jnp.abs(v.astype(jnp.float32)).max(),
                                  margin=1.05)
        cache = cache._replace(k_scale=k_scale, v_scale=v_scale)
    kq = quantize_per_tensor(k, cache.k_scale, cache.k.dtype)
    vq = quantize_per_tensor(v, cache.v_scale, cache.v.dtype)
    return kq, vq, cache


def attention_prefill(
    x: jax.Array,
    params: dict,
    cfg,
    cache: KVCache,
    precision: PrecisionConfig,
    *,
    lengths: Optional[jax.Array] = None,   # (B,) valid prompt lengths
    positions: Optional[jax.Array] = None,
    use_rope: bool = True,
    block_tables: Optional[jax.Array] = None,   # (B, W) paged cache only
):
    """Causal attention over the prompt; writes the cache at [0:S).

    With a `PagedKVCache` the write scatters through `block_tables`;
    positions past `lengths` (prompt padding) land in the trash block so a
    shared pool is never polluted by another sequence's padding.
    """
    b, s, _ = x.shape
    q, k, v = _project_qkv(x, params, cfg, precision)
    if positions is None:
        positions = jnp.arange(s)[None, :]
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    kq, vq, cache = _quantize_kv(k, v, cache, precision, recalibrate=True)
    if isinstance(cache, PagedKVCache):
        assert block_tables is not None, "paged prefill needs block_tables"
        pos = jnp.broadcast_to(positions, (b, s))
        valid = jnp.ones((b, s), bool) if lengths is None \
            else pos < lengths[:, None]
        cache = paged_write(cache, block_tables, pos, valid, kq, vq)
    else:
        cache = cache._replace(
            k=jax.lax.dynamic_update_slice(cache.k, kq, (0, 0, 0, 0)),
            v=jax.lax.dynamic_update_slice(cache.v, vq, (0, 0, 0, 0)),
        )

    # The model consumes what the cache holds: dequantize the quantized K/V
    # so prefill logits match decode-time numerics (train-inference mismatch
    # is then *only* due to quantization, as in the paper).
    if cache.quantized:
        k_use = dequantize_per_tensor(kq, cache.k_scale, x.dtype)
        v_use = dequantize_per_tensor(vq, cache.v_scale, x.dtype)
    else:
        k_use, v_use = k, v
    if _impl() == "chunked":
        out = _sdpa_chunked(q, k_use, v_use, precision, cfg, lengths=lengths)
    else:
        mask = causal_mask(s)[None]
        if lengths is not None:
            valid = jnp.arange(s)[None] < lengths[:, None]        # (B, S)
            mask = jnp.logical_and(mask, valid[:, None, :])
        out = _sdpa(q, k_use, v_use, mask, precision, cfg)
    return linear(out, params["wo"], precision=precision), cache


def attention_prefill_chunk(
    x: jax.Array,                # (B, C, D) hidden of this prompt chunk
    params: dict,
    cfg,
    cache: PagedKVCache,
    precision: PrecisionConfig,
    *,
    start: jax.Array,            # (B,) tokens already in the cache
    lengths: jax.Array,          # (B,) total valid tokens AFTER this chunk
    block_tables: jax.Array,     # (B, W)
    use_rope: bool = True,
    use_kernel: bool = False,
):
    """Chunked-prefill attention: write C prompt tokens at positions
    [start, start+C) through the block table, then attend each of them
    over everything reachable so far.  With `use_kernel` the Pallas
    `fp8_paged_prefill_attention` reads prior-context K/V directly from
    the pool via scalar-prefetched block tables (in-kernel dequant with
    the pool-global scales); the jnp fallback gathers a contiguous copy
    back from the pool (the same table-gather decode uses), sliced to
    the live leading blocks so neither path pays `max_seq_len` bytes.
    Either way a prompt of any length streams through a fixed-width
    chunk trace, and the pool bytes read are bit-identical to what a
    one-shot prefill would have written, so the logits agree.

    Positions at or past `lengths` (ragged final chunk) scatter to the
    trash block and their outputs are garbage the caller never reads.
    """
    assert isinstance(cache, PagedKVCache), "chunked prefill is paged-only"
    b, c, _ = x.shape
    q, k, v = _project_qkv(x, params, cfg, precision)
    positions = start[:, None] + jnp.arange(c)[None, :]         # (B, C)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    kq, vq, cache = _quantize_kv(k, v, cache, precision, recalibrate=True)
    valid = positions < lengths[:, None]
    cache = paged_write(cache, block_tables, positions, valid, kq, vq)

    w, bs = block_tables.shape[1], cache.block_size
    kvh, dh = cache.k.shape[2], cfg.d_head
    # the chunk's last query reaches at most min(start + C, lengths)
    # context tokens — table entries past that are never live
    w_live = _live_blocks(jnp.minimum(start + c, lengths), w, bs)
    phys = _paged_physical(cache, block_tables)[:, :w_live]
    if use_kernel:
        from repro.kernels import ops
        g = cfg.n_heads // kvh
        out = ops.fp8_paged_prefill_attention(
            q.reshape(b, c, kvh, g, dh),
            cache.k, cache.v, cache.k_scale, cache.v_scale,
            phys, start, lengths,
        ).reshape(b, c, cfg.n_heads * dh).astype(x.dtype)
    else:
        k_raw = cache.k[phys].reshape(b, w_live * bs, kvh, dh)
        v_raw = cache.v[phys].reshape(b, w_live * bs, kvh, dh)
        if cache.quantized:
            k_all = dequantize_per_tensor(k_raw, cache.k_scale, x.dtype)
            v_all = dequantize_per_tensor(v_raw, cache.v_scale, x.dtype)
        else:
            k_all, v_all = k_raw, v_raw
        k_pos = jnp.arange(w_live * bs)[None, None, :]          # (1, 1, S')
        mask = jnp.logical_and(k_pos <= positions[:, :, None],
                               k_pos < lengths[:, None, None])  # (B, C, S')
        out = _sdpa(q, k_all, v_all, mask, precision, cfg)
    return linear(out, params["wo"], precision=precision), cache


def attention_decode(
    x: jax.Array,                # (B, 1, D) current-token hidden
    params: dict,
    cfg,
    cache: KVCache,
    lengths: jax.Array,          # (B,) tokens already in cache
    precision: PrecisionConfig,
    *,
    use_rope: bool = True,
    use_kernel: bool = False,
    block_tables: Optional[jax.Array] = None,   # (B, W) paged cache only
):
    """One decode step: append K/V, attend over [0:lengths]+self."""
    b = x.shape[0]
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q, k, v = _project_qkv(x, params, cfg, precision)
    if use_rope:
        pos = lengths[:, None]
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)

    kq, vq, cache = _quantize_kv(k, v, cache, precision, recalibrate=False)
    if isinstance(cache, PagedKVCache):
        assert block_tables is not None, "paged decode needs block_tables"
        cache = paged_write(cache, block_tables, lengths[:, None],
                            jnp.ones((b, 1), bool), kq, vq)
        return _paged_attention_over_table(
            x, q, cache, block_tables, lengths + 1, params, precision, cfg,
            use_kernel=use_kernel)
    batch_idx = jnp.arange(b)
    cache = cache._replace(
        k=cache.k.at[batch_idx, lengths].set(kq[:, 0]),
        v=cache.v.at[batch_idx, lengths].set(vq[:, 0]),
    )
    new_lengths = lengths + 1

    if use_kernel:
        from repro.kernels import ops
        g = h // kvh
        out = ops.fp8_decode_attention(
            q.reshape(b, kvh, g, dh),
            cache.k, cache.v, cache.k_scale, cache.v_scale, new_lengths,
        ).reshape(b, 1, h * dh).astype(x.dtype)
    else:
        # reshard the *fp8 payload* (not the dequantized copy) when the
        # attention math needs the cache replicated over tp — 1 byte/elem on
        # the wire instead of 2-4 (§Perf decode iteration)
        k_raw = constrain(cache.k, "kv_gather")
        v_raw = constrain(cache.v, "kv_gather")
        k_all = dequantize_per_tensor(k_raw, cache.k_scale, x.dtype) \
            if cache.quantized else k_raw
        v_all = dequantize_per_tensor(v_raw, cache.v_scale, x.dtype) \
            if cache.quantized else v_raw
        s_max = cache.k.shape[1]
        mask = (jnp.arange(s_max)[None] < new_lengths[:, None])[:, None, :]
        out = _sdpa(q, k_all, v_all, mask, precision, cfg)
    return linear(out, params["wo"], precision=precision), cache


def _paged_attention_over_table(
    x: jax.Array,                # (B, 1, D) current-token hidden
    q: jax.Array,                # (B, 1, H, Dh) roped query
    cache: PagedKVCache,
    block_tables: jax.Array,     # (B, W)
    new_lengths: jax.Array,      # (B,) lengths AFTER the append
    params: dict,
    precision: PrecisionConfig,
    cfg,
    *,
    use_kernel: bool = False,
):
    """Attend one query token over the K/V reachable through `block_tables`.

    Only the leading `ceil(max(new_lengths) / BS)` table entries are ever
    dereferenced (`_live_blocks`) — both paths stop paying `max_seq_len`
    bytes per decode step, and stale table entries past the live region
    are provably unread.  The gathered view is (B, W_live*BS, KVH, D) in
    *logical* order — block j of a sequence covers positions
    [j*BS, (j+1)*BS) — so the standard length mask applies unchanged.
    Invalid table entries read the trash block and are masked by
    `new_lengths`.
    """
    b, _, h, dh = q.shape
    kvh = cache.k.shape[2]
    w, bs = block_tables.shape[1], cache.block_size
    w_live = _live_blocks(new_lengths, w, bs)
    phys = _paged_physical(cache, block_tables)[:, :w_live]      # (B, W_live)
    if use_kernel:
        from repro.kernels import ops
        g = h // kvh
        out = ops.fp8_paged_decode_attention(
            q.reshape(b, kvh, g, dh),
            cache.k, cache.v, cache.k_scale, cache.v_scale, phys,
            new_lengths,
        ).reshape(b, 1, h * dh).astype(x.dtype)
    else:
        k_raw = cache.k[phys].reshape(b, w_live * bs, kvh, dh)
        v_raw = cache.v[phys].reshape(b, w_live * bs, kvh, dh)
        k_all = dequantize_per_tensor(k_raw, cache.k_scale, x.dtype) \
            if cache.quantized else k_raw
        v_all = dequantize_per_tensor(v_raw, cache.v_scale, x.dtype) \
            if cache.quantized else v_raw
        mask = (jnp.arange(w_live * bs)[None] <
                new_lengths[:, None])[:, None, :]
        out = _sdpa(q, k_all, v_all, mask, precision, cfg)
    return linear(out, params["wo"], precision=precision), cache


# ---------------------------------------------------------------------------
# Cross-attention KV (enc-dec): static per request, quantized once at prefill
# ---------------------------------------------------------------------------

def cross_attention_cache(enc_out: jax.Array, params: dict, cfg,
                          precision: PrecisionConfig,
                          k_scale: Optional[jax.Array] = None,
                          v_scale: Optional[jax.Array] = None):
    """Precompute cross K/V from encoder output; quantize once (DESIGN §6).

    `k_scale`/`v_scale` seed the fresh cache's scales: the serving engine
    passes the pool's per-layer globals so a request prefilled after the
    calibration forward quantizes its cross K/V with the *calibrated*
    scales instead of the init value (with `calculate_kv_scales` still on,
    calibration from this tensor's amax overrides the seed).
    """
    b, s, _ = enc_out.shape
    kvh, dh = cfg.n_kv_heads, cfg.d_head
    k = linear(enc_out, params["wk"], precision=precision).reshape(b, s, kvh, dh)
    v = linear(enc_out, params["wv"], precision=precision).reshape(b, s, kvh, dh)
    cache = init_kv_cache(b, s, kvh, dh, precision, enc_out.dtype)
    if k_scale is not None:
        cache = cache._replace(k_scale=k_scale, v_scale=v_scale)
    kq, vq, cache = _quantize_kv(k, v, cache, precision, recalibrate=True)
    return cache._replace(k=kq, v=vq)


def cross_attention_decode(x, params, cfg, cross_cache: KVCache,
                           src_lengths: jax.Array, precision: PrecisionConfig):
    b, s, _ = x.shape
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = linear(x, params["wq"], precision=precision).reshape(b, s, h, dh)
    k = dequantize_per_tensor(cross_cache.k, cross_cache.k_scale, x.dtype) \
        if cross_cache.quantized else cross_cache.k
    v = dequantize_per_tensor(cross_cache.v, cross_cache.v_scale, x.dtype) \
        if cross_cache.quantized else cross_cache.v
    s_src = k.shape[1]
    mask = (jnp.arange(s_src)[None] < src_lengths[:, None])[:, None, :]
    out = _sdpa(q, k, v, mask, precision, cfg)
    return linear(out, params["wo"], precision=precision)
