"""FP8 linear layers: rollout (W8A8) and end-to-end training paths.

Rollout path (paper §2.1): weights statically quantized at weight-sync time
(128x128 E4M3 blocks), activations dynamically quantized per forward pass
(1x128 E4M3 tiles).  On TPU the matmul runs through the Pallas blockwise
kernel; the pure-jnp QDQ path computes bit-identical *values* (same scales,
same casts) and is the default on CPU where interpret-mode kernels are slow.

E2E training path (paper §2.4): `fp8_dot` is a custom_vjp dot whose forward
quantizes x/w to E4M3 and whose backward quantizes the incoming gradient to
the recipe's grad format — E5M2 for the hybrid recipe (recommended), E4M3
for the pure-E4M3 ablation that the paper shows collapsing at ~step 500.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.precision import (
    E4M3,
    E5M2,
    Fp8Recipe,
    PrecisionConfig,
    ScaleFormat,
)
from repro.core.quant import (
    QuantizedTensor,
    dequantize,
    qdq,
    quantize_activation,
    quantize_weight,
)
from repro.obs.scopes import scope


@scope("matmul")
def _dot(x: jax.Array, w: jax.Array) -> jax.Array:
    """bf16 x bf16 -> f32-accumulated matmul, output in x.dtype."""
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)


@scope("weight_dequant")
def _weight(w_q: QuantizedTensor, dtype) -> jax.Array:
    return dequantize(w_q, dtype)


# ---------------------------------------------------------------------------
# Rollout path (inference engine)
# ---------------------------------------------------------------------------

def fp8_linear_rollout(
    x: jax.Array,
    w_q: QuantizedTensor,
    *,
    scale_format: ScaleFormat = ScaleFormat.FP32,
    use_kernel: bool = False,
) -> jax.Array:
    """W8A8 blockwise FP8 linear, inference only.

    `use_kernel=True` routes through the Pallas blockwise GEMM (TPU target);
    the default QDQ path computes the same quantized values with a plain XLA
    matmul (exact on CPU, used by tests and the RL experiments).
    """
    if use_kernel:
        from repro.kernels import ops  # local import: kernels are optional

        with scope("act_quant"):
            x_q = ops.quantize_activation(x, scale_format=scale_format)
        with scope("matmul"):
            return ops.fp8_matmul(x_q, w_q, out_dtype=x.dtype)
    with scope("act_quant"):
        x_dq = dequantize(quantize_activation(x, scale_format=scale_format),
                          x.dtype)
    return _dot(x_dq, _weight(w_q, x.dtype))


def linear(x: jax.Array, w, *, precision: Optional[PrecisionConfig] = None,
           quantized: bool = True) -> jax.Array:
    """Precision-dispatching linear used throughout the model zoo.

    `w` is either a raw array (bf16 path / excluded layer) or a
    QuantizedTensor (rollout path after weight sync).
    """
    if isinstance(w, QuantizedTensor):
        if not quantized:  # excluded layer got a quantized weight: dequant
            return _dot(x, _weight(w, x.dtype))
        fmt = precision.scale_format if precision else ScaleFormat.FP32
        return fp8_linear_rollout(x, w_q=w, scale_format=fmt)
    if precision is not None and precision.fp8_training and quantized:
        return fp8_dot(x, w, recipe=precision.recipe,
                       scale_format=precision.scale_format)
    return _dot(x, w.astype(x.dtype))


# ---------------------------------------------------------------------------
# End-to-end FP8 training path
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fp8_dot(x: jax.Array, w: jax.Array, recipe: Fp8Recipe = Fp8Recipe.HYBRID,
            scale_format: ScaleFormat = ScaleFormat.FP32) -> jax.Array:
    """Quantized dot with recipe-controlled backward.

    forward : E4M3(x, 1x128) @ E4M3(w, 128x128)
    backward: grad quantized to E5M2 (hybrid) or E4M3 (pure-E4M3 ablation)
              before both dgrad (g @ w^T) and wgrad (x^T @ g).
    """
    x_f = qdq(x, fp8_dtype=E4M3, scale_format=scale_format)
    w_f = dequantize(quantize_weight(w, E4M3, scale_format), x.dtype)
    return _dot(x_f, w_f)


def _fp8_dot_fwd(x, w, recipe, scale_format):
    x_f = qdq(x, fp8_dtype=E4M3, scale_format=scale_format)
    w_f = dequantize(quantize_weight(w, E4M3, scale_format), x.dtype)
    return _dot(x_f, w_f), (x_f, w_f)


def _fp8_dot_bwd(recipe, scale_format, res, g):
    x_f, w_f = res
    grad_fmt = E5M2 if recipe == Fp8Recipe.HYBRID else E4M3
    # Quantize the grad-output once per contraction layout, like DeepGEMM's
    # dgrad/wgrad pair: 1x128 tiles along the contraction dim of each GEMM.
    g_for_dx = qdq(g, fp8_dtype=grad_fmt, scale_format=scale_format)  # over N
    dx = jax.lax.dot_general(
        g_for_dx, w_f, (((g.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x_f.dtype)
    # wgrad: contraction over all leading (batch/seq) dims
    lead = tuple(range(g.ndim - 1))
    g2 = g.reshape(-1, g.shape[-1])
    # tiles along the M (contraction) dim -> quantize the transpose rowwise
    g_for_dw = qdq(g2.T, fp8_dtype=grad_fmt, scale_format=scale_format).T
    x2 = x_f.reshape(-1, x_f.shape[-1])
    dw = jax.lax.dot_general(
        x2, g_for_dw, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(w_f.dtype)
    del lead
    return dx, dw


fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)
