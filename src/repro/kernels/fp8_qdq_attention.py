"""Pallas TPU kernels: the matmuls of full-FP8 decode attention over a
gathered fp8 K/V view.

Full-FP8 attention (paper §2.3.2) quantize-dequantizes K and V per 1x128
row before the score and PV matmuls.  Read from an fp8 cache, the view is
an fp8 payload times one per-layer scale, so each kernel runs that QDQ on
the payload in VMEM (`_qdq_rows`: the values `qdq` gives on the view
dequantized to bf16) and feeds the result straight to its matmul: the fp8
view is read once, and only the scores or the output are written.  The
softmax and the QDQ of Q and P stay with the caller.

Grid (B / bb, T / bt): `bb` sequences by `bt` keys a program.  The view
comes in token-major, (B, T*KVH, D), so a sequence's block is dense rows
of all heads: the QDQ runs over whole vregs into an f32 scratch, and each
head's keys are a sublane-strided read of it (row t*KVH + h).  Scores are
independent per key; PV accumulates over the key blocks in f32 and rounds
once.  A key block past T (T not a multiple of bt) holds garbage, which
the score kernel only writes out of bounds and the PV kernel masks.  A
row is one 1x128 tile: D <= 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import E4M3, FP8_MAX

_EPS = 1e-12
_BLOCK_BYTES = 2 << 20          # fp8 K/V bytes a program takes
_KEYS = 1024                    # whole sequences up to this many keys


def _round_to_spacing(x, bits: int, min_exp=None):
    """x to the nearest multiple (ties to even) of the spacing of a float
    with `bits` mantissa bits at x's exponent, that exponent held at least
    `min_exp`; kept in f32.  Adding 1.5 * 2^(e + 23 - bits) puts the sum in
    a binade whose f32 ulp is that spacing, so the add rounds and taking
    the constant away again is exact: two float ops and two or three
    integer ops, where a round trip through the narrow type packs and
    unpacks vregs."""
    e = jax.lax.bitcast_convert_type(x, jnp.int32) & 0x7F800000
    if min_exp is not None:
        e = jnp.maximum(e, (min_exp + 127) << 23)
    c = jax.lax.bitcast_convert_type(e + (((23 - bits) << 23) | 0x400000),
                                     jnp.float32)
    return (x + c) - c


def _round_bf16(x):
    """f32 to the nearest bf16 value (ties to even), kept in f32; x zero or
    an f32 normal (a payload times a per-layer scale is)."""
    return _round_to_spacing(x, 7)


def _round_e4m3(y):
    """y, |y| <= 448 (1 + 2^-22), to the nearest E4M3 value (ties to even),
    in f32: what `saturating_cast(y, E4M3)` gives, back in f32.  Below 2^-6
    E4M3 is subnormal, with the spacing of 2^-6."""
    return _round_to_spacing(y, 3, -6)


def _qdq_rows(payload, scale):
    """E4M3 QDQ of fp8 rows (..., D) dequantized to bf16 with `scale`
    (already rounded to bf16), one 1x128 tile a row, in f32: rounded to
    bf16 it is `qdq(dequantize_per_tensor(payload, scale, bf16))`.
    |x / row| <= 448 (1 + 2^-23), so the clip of `saturating_cast` never
    binds and is left out."""
    x = _round_bf16(payload.astype(jnp.float32) * scale)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    row = jnp.maximum(amax, _EPS) / FP8_MAX[E4M3]
    return _round_e4m3(x / row) * row


def _scores_kernel(s_ref, q_ref, k_ref, o_ref, view_ref):
    """q (bb, KVH, G, D); k (bb, bt*KVH, D) fp8; out (bb, KVH, G, bt)."""
    kvh, bt = q_ref.shape[1], o_ref.shape[3]

    def seq(i, carry):
        view_ref[...] = _qdq_rows(k_ref[i], s_ref[0])
        for h in range(kvh):
            k = view_ref[pl.ds(h, bt, stride=kvh), :].astype(q_ref.dtype)
            o_ref[i, h] = jax.lax.dot_general(
                q_ref[i, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(o_ref.dtype)
        return carry
    jax.lax.fori_loop(0, k_ref.shape[0], seq, 0)


def _pv_kernel(s_ref, p_ref, v_ref, o_ref, view_ref, acc_ref, *, keys: int):
    """p (bb, KVH, G, bt); v (bb, bt*KVH, D) fp8; out (bb, KVH, G, D),
    accumulated in `acc_ref` over the key blocks."""
    j = pl.program_id(1)
    kvh, bt = p_ref.shape[1], p_ref.shape[3]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def seq(i, carry):
        view_ref[...] = _qdq_rows(v_ref[i], s_ref[0])
        for h in range(kvh):
            v = view_ref[pl.ds(h, bt, stride=kvh), :]
            p = p_ref[i, h]
            if keys % bt:
                # the last block runs past the keys: zero what lies there
                t = j * bt + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
                v = jnp.where(t < keys, v, 0)
                t = j * bt + jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
                p = jnp.where(t < keys, p.astype(jnp.float32), 0) \
                    .astype(p.dtype)
            acc_ref[i, h] += jax.lax.dot_general(
                p, v.astype(p.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return carry
    jax.lax.fori_loop(0, v_ref.shape[0], seq, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _blocks(b: int, t: int, row_bytes: int):
    """(bb, bt): whole sequences up to `_KEYS` keys, else 512-key blocks;
    as many sequences (a divisor of b, at most 8) as fit `_BLOCK_BYTES`."""
    bt = t if t <= _KEYS else 512
    fit = max(1, min(8, _BLOCK_BYTES // (bt * row_bytes)))
    return max(d for d in range(1, fit + 1) if b % d == 0), bt


def _scale(scale):
    """The per-layer scale rounded to bf16, as `dequantize_per_tensor`
    multiplies by it for a bf16 view, for SMEM."""
    return jnp.asarray(scale, jnp.float32).reshape(1).astype(jnp.bfloat16) \
        .astype(jnp.float32)


def _call(kernel, rows, kv, scale, out_last, *, accumulate, interpret):
    """`kernel` over the fp8 view kv (B, T, KVH, D), passed token-major, and
    rows (B, KVH, G, R): q, or with `accumulate` p blocked like the keys,
    whose out (B, KVH, G, D) accumulates over the key blocks."""
    assert rows.dtype == jnp.bfloat16, rows.dtype   # the view QDQs as bf16
    b, kvh, g, n = rows.shape
    t, d = kv.shape[1], kv.shape[3]
    bb, bt = _blocks(b, t, kvh * d)
    by_key = lambda i, j: (i, 0, 0, j)                         # noqa: E731
    whole = lambda i, j: (i, 0, 0, 0)                          # noqa: E731
    scratch = [pltpu.VMEM((bt * kvh, d), jnp.float32)]
    if accumulate:
        rows_spec = pl.BlockSpec((bb, kvh, g, bt), by_key)
        out_spec = pl.BlockSpec((bb, kvh, g, out_last), whole)
        scratch.append(pltpu.VMEM((bb, kvh, g, out_last), jnp.float32))
    else:
        rows_spec = pl.BlockSpec((bb, kvh, g, n), whole)
        out_spec = pl.BlockSpec((bb, kvh, g, bt), by_key)
    return pl.pallas_call(
        kernel,
        grid=(b // bb, pl.cdiv(t, bt)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            rows_spec,
            pl.BlockSpec((bb, bt * kvh, d), lambda i, j: (i, j, 0)),
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, out_last), rows.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "arbitrary" if accumulate else "parallel")),
        interpret=interpret,
    )(_scale(scale), rows, kv.reshape(b, t * kvh, d))


@functools.partial(jax.jit, static_argnames=("interpret",))
def qdq_scores(q, k, k_scale, *, interpret: bool = False):
    """q (B, KVH, G, D) against the QDQ of fp8 K (B, T, KVH, D) dequantized
    with `k_scale`: (B, KVH, G, T) in q's dtype, f32-accumulated."""
    return _call(_scores_kernel, q, k, k_scale, k.shape[1],
                 accumulate=False, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qdq_pv(p, v, v_scale, *, interpret: bool = False):
    """p (B, KVH, G, T) times the QDQ of fp8 V (B, T, KVH, D) dequantized
    with `v_scale`: (B, KVH, G, D) in p's dtype, f32-accumulated."""
    return _call(functools.partial(_pv_kernel, keys=v.shape[1]), p, v,
                 v_scale, v.shape[3], accumulate=True, interpret=interpret)
