"""Pallas TPU kernels: serving attention over an FP8 KV cache.

Paper §2.3: fp8 KV storage with per-step recalibrated scales removes the
long-context memory bottleneck.  On TPU the generation step is purely
HBM-bandwidth bound — each token must stream the reachable KV through
VMEM — so storing KV as fp8 halves the dominant traffic term, and the
kernels below make that traffic the *only* traffic: no gathered
contiguous copy, no dequantized bf16 intermediate ever lands in HBM.

Three entry points, one kernel body per attention kind:

`fp8_paged_decode_attention` — PagedAttention decode over a block *pool*
    (N+1, BS, KVH, D) addressed through per-slot tables (vLLM layout).
    The tables ride in as a scalar-prefetch operand together with the
    per-slot live-block counts `nb[i] = ceil(context_len[i] / BS)` and
    the lengths, so the K/V BlockSpec index_maps translate (slot,
    logical block w) -> physical pool row *clamped to the live region*:

        row = tbl[i, min(w, nb[i] - 1)]

    Grid (B, W) with W a static table-width bound — but iterations
    past a slot's live region map to the same pool row as the last live
    block, which the TPU pipeline recognizes (an unchanged block index
    issues no new DMA), and their compute is skipped with `pl.when`.
    Decode cost therefore scales with each slot's actual context, not
    `max_seq_len`; one kernel launch serves the whole fused
    continuous-batching decode step, ragged tails masked by `lengths`.
    Table entries at or past `nb[i]` are NEVER used as indices — stale
    or trash ids beyond the live region are provably unread.

`fp8_decode_attention` — FlashDecoding over a *contiguous* (B, S, KVH, D)
    cache (the identity-table RL rollout shape).  The cache is reshaped
    (free) into a pool of S/BS-token pages with the identity table and
    served by the paged decode kernel.

`fp8_paged_prefill_attention` — flash-style chunked-prefill attention:
    for a prefill chunk of width C at positions [start, start+C), the
    queries attend over everything reachable so far — the KV of earlier
    chunks is read *directly from the paged pool* through the same
    clamped scalar-prefetch translation (the chunk's own KV was
    scattered into the pool just before, so intra-chunk attention also
    reads pool bytes, exactly like the jnp gather path it replaces).
    Grid (B, W); the wrapper lays the chunk's queries out per KV head as
    (B, KVH, C*G, D) rows; causal masking is by absolute position
    (k_pos <= start + c), and rows past `lengths` (ragged final chunk)
    attend to nothing.

Block layout (what the TPU compiler accepts): the last two dims of
every block must divide by (8, 128) or equal the array's.  A K/V block
is one whole page (1, BS, KVH, D) — all KV heads of it, so (KVH, D) are
the array's own dims and any page size BS is legal; the kernel walks
the heads with static (strided) VMEM reads.  The q/out blocks carry all
heads of one slot the same way.  Block tables, live-block counts,
`start` and `lengths` are scalar-prefetch operands, and the two K/V
scales sit in SMEM.

Scale-handling contract: K/V payloads are E4M3 (or bf16, where dequant
degenerates to a multiply by 1) with ONE pool-global f32 scale per
layer for K and one for V — the serving engine calibrates them at the
first prefill and every block quantizes against the same globals, so
the kernels dequantize in VMEM with a single scalar each, never
materializing a bf16 copy in HBM.  The dequantized values are exactly
the jnp fallback's `dequantize_per_tensor(..., q.dtype)`: the scale and
the product are rounded to the queries' dtype (bf16 when serving), so
kernel and gather paths attend over identical K/V.

VMEM per grid step at BS=16, KVH=8, D=128: one fp8 page of K or V is
16*8*128 B = 16 KiB, q/out KVH*G*D*2 B, acc KVH*G*D*4 B — far below
budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BS = 512
_NEG_INF = -1e30


def _scales(k_scale, v_scale, dtype) -> jax.Array:
    """(2,) f32 [k_scale, v_scale], each rounded to `dtype` — the value
    the jnp fallback's `dequantize_per_tensor(q, scale, dtype)`
    multiplies by."""
    s = jnp.stack([jnp.asarray(k_scale, jnp.float32).reshape(()),
                   jnp.asarray(v_scale, jnp.float32).reshape(())])
    return s.astype(dtype).astype(jnp.float32)


def _deq(tile, scale, dtype):
    """Dequantize an fp8 K/V tile in VMEM at the queries' precision `dtype`
    (bf16 on the serving path: the MXU's input width).  The f32 product
    rounded once to `dtype` is the fallback's multiply in `dtype` (for
    bf16 the f32 product is exact); the result is returned as f32 for the
    f32-accumulating matmuls."""
    return (tile.astype(jnp.float32) * scale).astype(dtype) \
        .astype(jnp.float32)


def _clamped_kv_map(i, w, tbl, nb, *_):
    """Shared K/V index map of both paged kernels — THE clamping contract:
    grid steps past slot i's live region re-map to its last live pool row
    (an unchanged block index issues no new DMA on TPU), so table entries
    at or past nb[i] are never used as indices."""
    return (tbl[i, jnp.minimum(w, nb[i] - 1)], 0, 0, 0)


def _slot_map(i, w, *_):
    return (i, 0, 0, 0)


def _live_block_counts(lengths: jax.Array, bs: int, n_w: int) -> jax.Array:
    """nb[i] = clip(ceil(lengths[i] / bs), 1, n_w) — the number of leading
    table entries holding live context (>= 1 so the clamped index map
    `tbl[i, min(w, nb-1)]` is always in range, even for idle slots)."""
    nb = (lengths.astype(jnp.int32) + bs - 1) // bs
    return jnp.clip(nb, 1, n_w)


def _attend_page(q_ref, k_ref, v_ref, s_ref, valid, sm_scale,
                 m_ref, l_ref, acc_ref):
    """Online-softmax update of every KV head's accumulators over one
    pool page, shared by the decode and prefill kernels (they differ only
    in how the q rows and the validity mask are built).  q_ref block
    (1, KVH, rows, D); k/v page (1, BS, KVH, D); scratch (KVH, rows, .)."""
    for h in range(k_ref.shape[2]):
        q = q_ref[0, h].astype(jnp.float32)                     # (rows, D)
        k = _deq(k_ref[0, :, h, :], s_ref[0], q_ref.dtype)       # (BS, D)
        v = _deq(v_ref[0, :, h, :], s_ref[1], q_ref.dtype)       # (BS, D)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale      # (rows, BS)
        scores = jnp.where(valid, scores, _NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _init_acc(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _write_out(o_ref, l_ref, acc_ref):
    for h in range(acc_ref.shape[0]):
        o_ref[0, h] = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).astype(
            o_ref.dtype)


def _paged_attention(kernel, q_rows, k_pool, v_pool, k_scale, v_scale,
                     prefetch, *, interpret):
    """pallas_call over grid (B, W) with the shared block layout: q/out
    (1, KVH, rows, D) per slot, one whole K/V page per step, the scales
    in SMEM, `prefetch` = (tables, live counts, ...) as scalar prefetch."""
    b, kvh, rows, d = q_rows.shape
    bs = k_pool.shape[1]
    n_w = prefetch[0].shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, n_w),
        in_specs=[
            pl.BlockSpec((1, kvh, rows, d), _slot_map),
            pl.BlockSpec((1, bs, kvh, d), _clamped_kv_map),
            pl.BlockSpec((1, bs, kvh, d), _clamped_kv_map),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, kvh, rows, d), _slot_map),
        scratch_shapes=[
            pltpu.VMEM((kvh, rows, 1), jnp.float32),
            pltpu.VMEM((kvh, rows, 1), jnp.float32),
            pltpu.VMEM((kvh, rows, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_rows.shape, q_rows.dtype),
        interpret=interpret,
    )(*prefetch, q_rows, k_pool, v_pool,
      _scales(k_scale, v_scale, q_rows.dtype))


# ---------------------------------------------------------------------------
# Paged decode: KV lives in a block pool, indexed through per-sequence
# block tables (vLLM PagedAttention).
# ---------------------------------------------------------------------------


def _paged_decode_attn_kernel(
    tbl_ref,      # scalar-prefetch (B, W) int32 physical block ids
    nb_ref,       # scalar-prefetch (B,) int32 live block counts
    len_ref,      # scalar-prefetch (B,) int32 context lengths
    q_ref,        # (1, KVH, G, D)
    k_ref,        # (1, BS, KVH, D) fp8 — pool row tbl[b, min(w, nb-1)]
    v_ref,        # (1, BS, KVH, D) fp8
    s_ref,        # SMEM (2,) f32 [k_scale, v_scale]
    o_ref,        # (1, KVH, G, D)
    m_ref,        # scratch (KVH, G, 1) f32
    l_ref,        # scratch (KVH, G, 1) f32
    acc_ref,      # scratch (KVH, G, D) f32
    *,
    bs: int,
    n_w: int,
    sm_scale: float,
):
    i = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        _init_acc(m_ref, l_ref, acc_ref)

    # Grid steps past this slot's live region re-map to the last live pool
    # row (no fresh DMA) and contribute nothing: skip their compute.
    @pl.when(w < nb_ref[i])
    def _update():
        # logical position of this block's tokens = w * bs + offset; the
        # ragged tail of the last live block sits past `lengths` and
        # masks to -inf
        pos = w * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        valid = pos < len_ref[i]
        _attend_page(q_ref, k_ref, v_ref, s_ref, valid, sm_scale,
                     m_ref, l_ref, acc_ref)

    @pl.when(w == n_w - 1)
    def _done():
        _write_out(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def fp8_paged_decode_attention(
    q: jax.Array,             # (B, KVH, G, D) bf16 (or f32)
    k_pool: jax.Array,        # (N, BS, KVH, D) fp8 (or bf16)
    v_pool: jax.Array,        # (N, BS, KVH, D)
    k_scale: jax.Array,       # () or (1,) f32
    v_scale: jax.Array,       # () or (1,) f32
    block_tables: jax.Array,  # (B, W) int32 PHYSICAL pool rows; entries at
                              # or past ceil(lengths/BS) are never read
    lengths: jax.Array,       # (B,) int32
    *,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, kvh, g, d = q.shape
    n, bs, kvh2, d2 = k_pool.shape
    b2, n_w = block_tables.shape
    assert (kvh, d, b) == (kvh2, d2, b2), (q.shape, k_pool.shape,
                                           block_tables.shape)
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_paged_decode_attn_kernel, bs=bs, n_w=n_w,
                               sm_scale=sm_scale)
    lengths = lengths.astype(jnp.int32)
    prefetch = (block_tables.astype(jnp.int32),
                _live_block_counts(lengths, bs, n_w), lengths)
    return _paged_attention(kernel, q, k_pool, v_pool, k_scale, v_scale,
                            prefetch, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bs", "sm_scale", "interpret"))
def fp8_decode_attention(
    q: jax.Array,         # (B, KVH, G, D) bf16 (or f32)
    k_cache: jax.Array,   # (B, S, KVH, D) fp8 (or bf16 — dequant is a no-op)
    v_cache: jax.Array,   # (B, S, KVH, D) fp8
    k_scale: jax.Array,   # () or (1,) f32
    v_scale: jax.Array,   # () or (1,) f32
    lengths: jax.Array,   # (B,) int32
    *,
    bs: int = DEFAULT_BS,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Contiguous-cache decode: the cache viewed as a pool of S/BS-token
    pages per slot, served through the identity block table."""
    b, s_len, kvh, d = k_cache.shape
    bs = min(bs, s_len)
    assert s_len % bs == 0, (s_len, bs)
    n_s = s_len // bs
    pool_k = k_cache.reshape(b * n_s, bs, kvh, d)
    pool_v = v_cache.reshape(b * n_s, bs, kvh, d)
    tables = jnp.arange(b * n_s, dtype=jnp.int32).reshape(b, n_s)
    return fp8_paged_decode_attention(
        q, pool_k, pool_v, k_scale, v_scale, tables, lengths,
        sm_scale=sm_scale, interpret=interpret)


# ---------------------------------------------------------------------------
# Paged chunked-prefill: a C-token prompt chunk attends over everything
# reachable so far, reading prior-context (and its own, just-scattered)
# K/V straight from the pool through the clamped scalar-prefetch
# translation — the jnp path's gathered contiguous copy never exists.
# ---------------------------------------------------------------------------


def _paged_prefill_attn_kernel(
    tbl_ref,      # scalar-prefetch (B, W) int32 physical block ids
    nb_ref,       # scalar-prefetch (B,) int32 live block counts
    start_ref,    # scalar-prefetch (B,) int32 chunk start positions
    len_ref,      # scalar-prefetch (B,) int32 valid tokens after the chunk
    q_ref,        # (1, KVH, C*G, D) row r = chunk position r // G
    k_ref,        # (1, BS, KVH, D) fp8 — pool row tbl[b, min(w, nb-1)]
    v_ref,        # (1, BS, KVH, D) fp8
    s_ref,        # SMEM (2,) f32 [k_scale, v_scale]
    o_ref,        # (1, KVH, C*G, D)
    m_ref,        # scratch (KVH, C*G, 1) f32
    l_ref,        # scratch (KVH, C*G, 1) f32
    acc_ref,      # scratch (KVH, C*G, D) f32
    *,
    bs: int,
    n_w: int,
    g: int,
    sm_scale: float,
):
    i = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        _init_acc(m_ref, l_ref, acc_ref)

    @pl.when(w < nb_ref[i])
    def _update():
        rows = q_ref.shape[2]
        # row r of the (C*G) query block is chunk position r//G; causal
        # masking is by ABSOLUTE position (earlier chunks included), and
        # rows past `lengths` (ragged final chunk) attend to nothing
        q_pos = start_ref[i] + \
            jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) // g
        k_pos = w * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        valid = jnp.logical_and(k_pos <= q_pos, q_pos < len_ref[i])
        _attend_page(q_ref, k_ref, v_ref, s_ref, valid, sm_scale,
                     m_ref, l_ref, acc_ref)

    @pl.when(w == n_w - 1)
    def _done():
        _write_out(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def fp8_paged_prefill_attention(
    q: jax.Array,             # (B, C, KVH, G, D) roped chunk queries
    k_pool: jax.Array,        # (N, BS, KVH, D) fp8 (or bf16)
    v_pool: jax.Array,        # (N, BS, KVH, D)
    k_scale: jax.Array,       # () or (1,) f32
    v_scale: jax.Array,       # () or (1,) f32
    block_tables: jax.Array,  # (B, W) int32 PHYSICAL pool rows; entries at
                              # or past the live region are never read
    start: jax.Array,         # (B,) int32 chunk start positions
    lengths: jax.Array,       # (B,) int32 total valid tokens AFTER the chunk
    *,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, c, kvh, g, d = q.shape
    n, bs, kvh2, d2 = k_pool.shape
    b2, n_w = block_tables.shape
    assert (kvh, d, b) == (kvh2, d2, b2), (q.shape, k_pool.shape,
                                           block_tables.shape)
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_paged_prefill_attn_kernel, bs=bs, n_w=n_w,
                               g=g, sm_scale=sm_scale)
    start = start.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    # reachable context for the chunk: its last query row sits at position
    # min(start + C, lengths) - 1, so live blocks cover min(start+C, len)
    nb = _live_block_counts(jnp.minimum(start + c, lengths), bs, n_w)
    prefetch = (block_tables.astype(jnp.int32), nb, start, lengths)
    # per-KV-head query rows: (B, C, KVH, G, D) -> (B, KVH, C*G, D)
    q_rows = q.transpose(0, 2, 1, 3, 4).reshape(b, kvh, c * g, d)
    out = _paged_attention(kernel, q_rows, k_pool, v_pool, k_scale, v_scale,
                           prefetch, interpret=interpret)
    return out.reshape(b, kvh, c, g, d).transpose(0, 2, 1, 3, 4)
