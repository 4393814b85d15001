"""Operations and bytes of the work, counted from shapes.

These are the yardstick of the roofline and MFU metrics.  They count what
the work needs, not what an implementation happens to do: a later change
that computes the same tokens with fewer bytes moved reads a higher share,
and one that wastes work reads a lower one.

A dense decoder layer here is GQA attention (q, k, v, o projections) and a
gated MLP (gate, up, down).  Norms, RoPE, softmax and sampling are
elementwise and left out of the operation count; they are a few percent of
a layer at these widths.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the counts need, read from a configuration file."""

    layers: int
    d_model: int
    d_ff: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   d_ff=c["intermediate_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
                   vocab=c["vocab_size"])

    @property
    def layer_linear_params(self) -> int:
        """Weights of one layer's seven linears."""
        d, q, kv = self.d_model, self.n_heads * self.d_head, \
            self.n_kv_heads * self.d_head
        return d * q + 2 * d * kv + q * d + 3 * d * self.d_ff

    @property
    def kv_elems_per_token(self) -> int:
        """K and V entries one token adds to the cache, over all layers."""
        return self.layers * 2 * self.n_kv_heads * self.d_head


# A weight-stationary linear at W8A8 stores one f32 scale per 128 x 128
# block beside its 1-byte payload.
FP8_BLOCK = 128 * 128


def weight_bytes(m: Dims, linear_bytes: int) -> int:
    """Bytes of the weights one forward pass reads: the layer linears at
    their storage type (fp8 payload plus block scales, or bf16), the bf16
    output head and the norm scales.  Embedding rows are counted by the
    caller per token."""
    lin = m.layers * m.layer_linear_params
    lin_bytes = lin * linear_bytes + (4 * lin // FP8_BLOCK if linear_bytes == 1
                                      else 0)
    head = m.d_model * m.vocab * 2
    norms = (2 * m.layers + 1) * m.d_model * 2
    return lin_bytes + head + norms


def forward_flops(m: Dims, tokens: int, attended: int,
                  head_tokens: int) -> float:
    """Multiply-adds x 2 of a forward pass over `tokens` positions.

    attended: sum over those positions of the keys each attends to
    (itself included).  head_tokens: positions whose logits are needed.
    """
    linears = 2.0 * tokens * m.layers * m.layer_linear_params
    attention = 4.0 * m.layers * m.n_heads * m.d_head * attended
    head = 2.0 * head_tokens * m.d_model * m.vocab
    return linears + attention + head


def causal_attended(lengths: Sequence[int]) -> int:
    """Keys attended over a causal pass of sequences of these lengths."""
    return sum(n * (n + 1) // 2 for n in lengths)


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def least_seconds(self, peak_flops: float, peak_bytes: float) -> float:
        return max(self.flops / peak_flops, self.bytes / peak_bytes)


def generate_work(m: Dims, prompt_lengths: Sequence[int], group: int,
                  new_tokens: int, page: int, shared_blocks: int,
                  linear_bytes: int, kv_bytes: int) -> list:
    """Work of one GRPO rollout call, one `Work` per forward pass.

    The prompts are prefilled once, and each of their `group` samples then
    decodes `new_tokens` tokens: the first comes from the prefill's
    logits, each later one from a decode pass, so `new_tokens - 1` decode
    passes are work.  A decode pass reads the weights once, reads every
    sequence's context from the cache (the `shared_blocks * page` prompt
    tokens a group shares are read once for the group) and writes one
    token's K and V per sequence.
    """
    wb = weight_bytes(m, linear_bytes)
    kv_tok = m.kv_elems_per_token * kv_bytes
    emb_row = m.d_model * 2
    n_prompt = sum(prompt_lengths)
    prefill = Work(
        flops=forward_flops(m, n_prompt, causal_attended(prompt_lengths),
                            len(prompt_lengths)),
        bytes=wb + n_prompt * (kv_tok + emb_row))
    shared = shared_blocks * page
    n = len(prompt_lengths) * group
    passes = [prefill]
    for i in range(1, new_tokens):
        # the token decoded at step i sits at position L + i - 1 and
        # attends to the L + i positions up to and including itself
        ctx = [ln + i for ln in prompt_lengths]
        attended = group * sum(ctx)
        read = sum(shared + group * (c - 1 - shared) for c in ctx)
        passes.append(Work(
            flops=forward_flops(m, n, attended, n),
            bytes=wb + read * kv_tok + n * (kv_tok + emb_row)))
    return passes


def update_flops(m: Dims, seq_lengths: Sequence[int]) -> float:
    """Forward and backward of the policy update over sequences of these
    lengths (prompt plus response): the backward pass costs twice the
    forward.  Recomputation under remat is not work and is not counted."""
    fwd = forward_flops(m, sum(seq_lengths), causal_attended(seq_lengths),
                        sum(seq_lengths))
    return 3.0 * fwd
