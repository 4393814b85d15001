"""Pallas TPU kernel: fused blockwise FP8 quantization.

Produces the fp8 payload and the per-block scales in one pass over the data
(single HBM read of the source tensor).  Two layouts, matching paper §2.1.1:

  * activation mode: 1x128 row tiles  -> scales (M, K/128)
  * weight mode:     128x128 blocks   -> scales (M/128, K/128)

The weight-sync phase (paper §2.1.2) runs this over every linear weight each
RL step, so it is a hot spot at step granularity; the activation mode runs in
every rollout forward pass.

Grid: one program per (BM, 128) slab; a program reduces its slab to scales
and writes the quantized payload.  VMEM at BM=256: in 256*128*2B = 64KiB,
out 32KiB — trivially resident.

Scale outputs obey the TPU tiling rule (the last two block dims divide by
(8, 128) or equal the array's): a program owns a whole scale row — (BM,
K/128) for activations, (1, N/128) of the (K/128, 1, N/128) weight-scale
array — which stays resident across the inner grid axis, and writes its
own column with an iota select.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.precision import E4M3, FP8_MAX, ScaleFormat

_EPS = 1e-12


def _quantize_tile(x, q_ref, amax, *, fp8_max: float, fp8_dtype,
                   pow2: bool):
    """Scale from `amax`, write the fp8 payload of `x`, return the scale."""
    scale = jnp.maximum(amax, _EPS) / fp8_max
    if pow2:
        scale = jnp.exp2(jnp.ceil(jnp.log2(scale)))
    q = jnp.clip(x / scale, -fp8_max, fp8_max)
    q_ref[...] = q.astype(fp8_dtype)
    return scale


def _put_column(s_ref, scale, j):
    """Write `scale` into column j of the resident scale row block."""
    col = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, s_ref.ndim - 1)
    s_ref[...] = jnp.where(col == j, scale, s_ref[...])


def _quant_act_kernel(x_ref, q_ref, s_ref, **kw):
    """1x128 tiles: one scale per (row, 128-col block)."""
    x = x_ref[...].astype(jnp.float32)               # (BM, 128)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)  # (BM, 1)
    _put_column(s_ref, _quantize_tile(x, q_ref, amax, **kw),
                pl.program_id(1))


def _quant_weight_kernel(x_ref, q_ref, s_ref, **kw):
    """128x128 blocks: one scale per program."""
    x = x_ref[...].astype(jnp.float32)               # (128, 128)
    amax = jnp.max(jnp.max(jnp.abs(x), axis=1, keepdims=True), axis=0,
                   keepdims=True)                    # (1, 1)
    _put_column(s_ref, _quantize_tile(x, q_ref, amax, **kw)[None],
                pl.program_id(1))


@functools.partial(jax.jit, static_argnames=("fp8_dtype", "scale_format", "bm", "interpret"))
def quantize_activation_kernel(
    x: jax.Array,                      # (M, K), K % 128 == 0
    *,
    fp8_dtype=E4M3,
    scale_format: ScaleFormat = ScaleFormat.FP32,
    bm: int = 256,
    interpret: bool = False,
):
    m, k = x.shape
    assert k % 128 == 0, k
    bm = min(bm, m)
    assert m % bm == 0, (m, bm)
    kernel = functools.partial(
        _quant_act_kernel,
        fp8_max=FP8_MAX[fp8_dtype],
        fp8_dtype=fp8_dtype,
        pow2=scale_format == ScaleFormat.UE8M0,
    )
    return pl.pallas_call(
        kernel,
        grid=(m // bm, k // 128),
        in_specs=[pl.BlockSpec((bm, 128), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bm, 128), lambda i, j: (i, j)),
            pl.BlockSpec((bm, k // 128), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), fp8_dtype),
            jax.ShapeDtypeStruct((m, k // 128), jnp.float32),
        ],
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("fp8_dtype", "scale_format", "interpret"))
def quantize_weight_kernel(
    w: jax.Array,                      # (K, N), both % 128 == 0
    *,
    fp8_dtype=E4M3,
    scale_format: ScaleFormat = ScaleFormat.FP32,
    interpret: bool = False,
):
    k, n = w.shape
    assert k % 128 == 0 and n % 128 == 0, (k, n)
    kernel = functools.partial(
        _quant_weight_kernel,
        fp8_max=FP8_MAX[fp8_dtype],
        fp8_dtype=fp8_dtype,
        pow2=scale_format == ScaleFormat.UE8M0,
    )
    q, s = pl.pallas_call(
        kernel,
        grid=(k // 128, n // 128),
        in_specs=[pl.BlockSpec((128, 128), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((128, 128), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1, n // 128), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, n), fp8_dtype),
            jax.ShapeDtypeStruct((k // 128, 1, n // 128), jnp.float32),
        ],
        interpret=interpret,
    )(w)
    return q, s.reshape(k // 128, n // 128)
