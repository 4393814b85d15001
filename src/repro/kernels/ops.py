"""Public jit'd wrappers around the Pallas kernels.

Responsibilities:
  * backend dispatch — on CPU (this container) kernels run `interpret=True`;
    on TPU they compile natively.  Callers never pass `interpret`.
  * shape normalization — pad arbitrary (M, K, N) to tile multiples, slice
    the result back.
  * dtype plumbing between `QuantizedTensor` and the raw kernel signature.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.precision import E4M3, ScaleFormat
from repro.core.quant import QuantizedTensor
from repro.kernels import fp8_gemm as _gemm
from repro.kernels import fp8_kv_attention as _attn
from repro.kernels import fp8_qdq_attention as _qdq_attn
from repro.kernels import fp8_quant as _quant


@functools.cache
def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, mults: tuple) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def quantize_activation(x: jax.Array, fp8_dtype=E4M3,
                        scale_format: ScaleFormat = ScaleFormat.FP32
                        ) -> QuantizedTensor:
    """Fused dynamic activation quantization (1x128 tiles).

    Accepts any rank; leading dims are flattened into rows.  K is padded to
    a 128 multiple (padding contributes zeros and never wins the amax).
    """
    shape = x.shape
    k = shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    # row block: a multiple of the fp8 tile's 32 sublanes, capped at 256;
    # padded rows are zeros and are sliced off with their scales
    bm = min(256, -(-m // 32) * 32)
    x2 = _pad_to(x2, (bm, 128))
    q, s = _quant.quantize_activation_kernel(
        x2, fp8_dtype=fp8_dtype, scale_format=scale_format, bm=bm,
        interpret=_interpret())
    q = q[:m, :k].reshape(shape)
    s = s[:m].reshape(shape[:-1] + (-1,))
    return QuantizedTensor(q, s, (1,) * (len(shape) - 1) + (128,))


def quantize_weight(w: jax.Array, fp8_dtype=E4M3,
                    scale_format: ScaleFormat = ScaleFormat.FP32
                    ) -> QuantizedTensor:
    """Fused static weight quantization (128x128 blocks); 2D only here,
    stacked weights are vmapped by the caller."""
    k, n = w.shape
    wp = _pad_to(w, (128, 128))
    q, s = _quant.quantize_weight_kernel(
        wp, fp8_dtype=fp8_dtype, scale_format=scale_format,
        interpret=_interpret())
    return QuantizedTensor(q[:k, :n], s, (128, 128))


def fp8_matmul(x_q: QuantizedTensor, w_q: QuantizedTensor,
               out_dtype=jnp.bfloat16, bm: int = 256, bn: int = 256
               ) -> jax.Array:
    """y = dequant(x_q) @ dequant(w_q), computed by the blockwise kernel.

    x_q: activations, 1x128 tiles, any leading rank.
    w_q: weights, 128x128 blocks, (K, N).
    """
    xshape = x_q.data.shape
    k = xshape[-1]
    kw, n = w_q.data.shape
    assert k == kw, (xshape, w_q.data.shape)

    a = x_q.data.reshape(-1, k)
    a_s = x_q.scales.reshape(a.shape[0], -1)
    m = a.shape[0]

    # pad everything to tile multiples
    bm_eff = min(bm, _gemm.DEFAULT_BM)
    a = _pad_to(a, (bm_eff, 128))
    a_s = _pad_to(a_s, (bm_eff, 1))
    w = _pad_to(w_q.data, (128, bn))
    w_s = _pad_to(w_q.scales, (1, bn // 128))

    y = _gemm.fp8_gemm(a, w, a_s, w_s, bm=bm_eff, bn=bn, out_dtype=out_dtype,
                       interpret=_interpret())
    return y[:m, :n].reshape(xshape[:-1] + (n,))


def fp8_paged_decode_attention(q, k_pool, v_pool, k_scale, v_scale,
                               block_tables, lengths):
    """PagedAttention decode over an fp8 block pool, length-clamped.

    `block_tables` must already hold *physical* pool rows (the models layer
    maps unmapped -1 entries to the trash block before calling in); entries
    at or past each slot's `ceil(lengths / block_size)` live blocks are
    never dereferenced.  The pool's block size is the kernel's S tile, so
    no padding is needed — blocks are tile-sized by construction.
    """
    return _attn.fp8_paged_decode_attention(
        q, k_pool, v_pool, k_scale, v_scale, block_tables, lengths,
        interpret=_interpret())


def fp8_paged_prefill_attention(q, k_pool, v_pool, k_scale, v_scale,
                                block_tables, start, lengths):
    """Chunked-prefill attention over an fp8 block pool.

    q (B, C, KVH, G, D) are the chunk's roped queries at absolute
    positions [start, start+C); the chunk's own K/V must already be
    scattered into the pool (the kernel reads intra-chunk context from
    pool bytes, exactly like the jnp gather path).  Same physical-table
    contract as the paged decode kernel; entries past the reachable
    context `ceil(min(start+C, lengths) / block_size)` are never read.
    """
    return _attn.fp8_paged_prefill_attention(
        q, k_pool, v_pool, k_scale, v_scale, block_tables, start, lengths,
        interpret=_interpret())


def fp8_decode_attention(q, k_cache, v_cache, k_scale, v_scale, lengths,
                         bs: int = _attn.DEFAULT_BS):
    """FlashDecoding over fp8 KV.  Pads S to a block multiple; padded
    positions are masked by `lengths`."""
    s = k_cache.shape[1]
    bs = min(bs, max(128, 1 << (s - 1).bit_length()))
    while s % bs and bs > 128:
        bs //= 2
    if s % bs:  # small/odd S: pad to one block
        bs = min(bs, 1 << (s - 1).bit_length())
        k_cache = _pad_to(k_cache, (1, bs, 1, 1))
        v_cache = _pad_to(v_cache, (1, bs, 1, 1))
    return _attn.fp8_decode_attention(
        q, k_cache, v_cache, k_scale, v_scale, lengths, bs=bs,
        interpret=_interpret())


def qdq_attention_kernels_apply(q: jax.Array, kv: jax.Array) -> bool:
    """Whether full-FP8 attention of q (B, S, H, D) over the fp8 view kv
    (B, T, KVH, D) runs in `qdq_scores`/`qdq_pv`: compiled on a TPU, for
    one bf16 query token and heads of one 1x128 tile, in a process with one
    device.  The kernels have no partitioning rule, so under a mesh XLA
    would gather their operands to every device; there the caller keeps
    its XLA path."""
    return (not _interpret() and jax.device_count() == 1
            and q.shape[1] == 1 and q.dtype == jnp.bfloat16
            and kv.shape[-1] <= 128)


def qdq_scores(q, k, k_scale):
    """Scores of q (B, KVH, G, D) over the full-FP8 QDQ of the fp8 K view
    (B, T, KVH, D) dequantized with `k_scale`: (B, KVH, G, T)."""
    return _qdq_attn.qdq_scores(q, k, k_scale, interpret=_interpret())


def qdq_pv(p, v, v_scale):
    """p (B, KVH, G, T) times the full-FP8 QDQ of the fp8 V view
    (B, T, KVH, D) dequantized with `v_scale`: (B, KVH, G, D)."""
    return _qdq_attn.qdq_pv(p, v, v_scale, interpret=_interpret())
