"""Pallas TPU kernels for the FP8-RL hot spots (DESIGN.md §2).

fp8_gemm          — blockwise-scaled FP8 GEMM (DeepGEMM analogue)
fp8_quant         — fused blockwise quantization (weight-sync / activations)
fp8_kv_attention  — FlashDecoding over an fp8 KV cache
fp8_qdq_attention — full-FP8 decode attention's matmuls over a gathered
                    fp8 K/V view, its QDQ done in VMEM

`ops` is the public API (backend dispatch + padding); `ref` holds the
pure-jnp oracles the kernels are validated against.

The paged-prefill kernel doubles as the speculative-decoding scorer:
a `Verify` action runs the [pending, draft_1..draft_k] chunk through
`fp8_paged_prefill_attention` exactly like any chunked-prefill chunk
(same block-table scatter, same causal mask over prior context), and the
engine truncates the slot's length back to the accepted prefix afterwards
— KV rows past the truncated length are never read (per-slot length
masking plus the kernel's live-block clamp), so rejection costs nothing
but the already-paid trace.  See `serving/spec_decode.py` for the full
rewind contract.
"""
from repro.kernels import ops, ref
from repro.kernels.config import KernelConfig
from repro.kernels.ops import (
    fp8_decode_attention,
    fp8_matmul,
    fp8_paged_decode_attention,
    fp8_paged_prefill_attention,
    quantize_activation,
    quantize_weight,
)

__all__ = [
    "ops", "ref", "KernelConfig", "fp8_decode_attention", "fp8_matmul",
    "fp8_paged_decode_attention", "fp8_paged_prefill_attention",
    "quantize_activation", "quantize_weight",
]
