"""Pallas TPU kernel: blockwise-scaled FP8 GEMM (the DeepGEMM analogue).

Paper §2.1.1 uses DeepGEMM on H100: fp8 x fp8 tensor-core GEMM with 1x128
activation tiles and 128x128 weight blocks.  TPU adaptation (DESIGN.md §2):

  * fp8 operands + fp32 block scales live in HBM — this halves the weight
    memory traffic, which the paper identifies as the dominant win in the
    memory-bound long-context rollout regime;
  * tiles are streamed HBM->VMEM by `pallas_call` BlockSpecs;
  * dequantization happens in-VMEM (vector unit), the MXU consumes bf16.
    On fp8-MXU hardware (v6e+) the same BlockSpecs feed the MXU directly.

Layout / grid:

  A   (M, K)      fp8   1x128 row tiles      a_scales (M, K/128) f32
  W   (K, N)      fp8   128x128 blocks       w_scales (K/128, N/128) f32
  out (M, N)      bf16 (or f32)

  grid = (M/BM, N/BN, K/BK) with BK = 128 so one K-step spans exactly one
  scale block; K is the innermost (minor) grid dim so the f32 accumulator
  tile stays resident in VMEM across the K loop.

  Scale blocks follow the TPU tiling rule (the last two block dims divide
  by (8, 128) or equal the array's): a step loads its rows' whole scale
  row (BM, K/128) and the w-scales expanded to columns, (K/128, BN), and
  picks K-block `k` out of each with an iota mask.  Both blocks keep
  their index across the K loop, so each is fetched once per tile.

VMEM budget at the default BM=256, BN=256, BK=128:
  A tile 256*128*1B = 32KiB, W tile 128*256*1B = 32KiB,
  acc 256*256*4B = 256KiB, scales < 2KiB  ->  « 16MiB VMEM; the MXU sees
  (256x128)@(128x256) matmuls, all dims multiples of the 128 systolic tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BN = 256
BK = 128  # fixed: matches the scale-block granularity


def _fp8_gemm_kernel(a_ref, w_ref, a_s_ref, w_s_ref, out_ref, acc_ref, *,
                     n_k: int, out_dtype):
    """One (BM, BN) output tile; accumulates over the K grid dimension."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Dequantize is deferred: (a@w) is computed on the raw fp8 payloads
    # upcast to bf16, then the rank-1 scale product a_s (BM,1) * w_s (1,1)
    # is applied to the f32 partial product.  Exact because every element of
    # this K-slab shares one w-scale and each row shares one a-scale.
    a = a_ref[...].astype(jnp.bfloat16)
    w = w_ref[...].astype(jnp.bfloat16)
    partial = jax.lax.dot_general(
        a, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # select K-block k: one nonzero term per sum, so the pick is exact
    a_s_all = a_s_ref[...]                                  # (BM, K/128)
    w_s_all = w_s_ref[...]                                  # (K/128, BN)
    a_s = jnp.sum(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, a_s_all.shape, 1) == k,
        a_s_all, 0.0), axis=1, keepdims=True)               # (BM, 1)
    w_s = jnp.sum(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, w_s_all.shape, 0) == k,
        w_s_all, 0.0), axis=0, keepdims=True)               # (1, BN)
    acc_ref[...] += partial * (a_s * w_s)

    @pl.when(k == n_k - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "out_dtype", "interpret")
)
def fp8_gemm(
    a: jax.Array,          # (M, K) fp8
    w: jax.Array,          # (K, N) fp8
    a_scales: jax.Array,   # (M, K//128) f32
    w_scales: jax.Array,   # (K//128, N//128) f32
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """Blockwise-scaled FP8 GEMM.  Dims must be multiples of the tile sizes
    (the `ops.py` wrapper pads arbitrary shapes)."""
    m, k = a.shape
    k2, n = w.shape
    assert k == k2, (a.shape, w.shape)
    assert m % bm == 0 and n % bn == 0 and k % BK == 0, (m, n, k, bm, bn)
    assert a_scales.shape == (m, k // BK), a_scales.shape
    assert w_scales.shape == (k // BK, n // BK), w_scales.shape
    n_k = k // BK

    grid = (m // bm, n // bn, n_k)
    kernel = functools.partial(_fp8_gemm_kernel, n_k=n_k, out_dtype=out_dtype)
    # one w-scale per (K-block, 128-wide N-block), repeated per column
    w_scales_cols = jnp.repeat(w_scales.astype(jnp.float32), BK, axis=1)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, BK), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((BK, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, n_k), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((n_k, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, w, a_scales.astype(jnp.float32), w_scales_cols)
