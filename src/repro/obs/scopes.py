"""The names the program gives its own work, for the profiler.

Two kinds of name, one registry, so that a reader of a trace (the chip
benchmark, the tests) imports them from here and a renamed scope cannot
silently empty what it reads:

- device scopes (`scope`): `jax.named_scope`, which only writes the
  compiled HLO's `op_name` metadata (``jit(generate)/while/body/decode/
  .../kv_read/...``), so it costs nothing at run time.  Every fusion
  carries its root instruction's path; the innermost registered name on
  a path is the op's scope.  `PHASES` are the outer scopes of
  `rl.rollout.generate`, which split its device time by phase.
- program host spans (`span`): `jax.profiler.TraceAnnotation`, which
  lands on the profiler's host plane and so shares the device trace's
  clock.  With no profiler running it is one check.

Both stay on always; there is no switch.
"""
from __future__ import annotations

import jax

# outer scopes of `generate`: its phases
PHASES = ("prefill", "fork", "decode")

DEVICE_SCOPES = PHASES + (
    "sample",           # core.sampling.sample, after prefill and per pass
    "commit",           # the decode loop stores the last pass's token
    "embed",            # the token embedding lookup (`_embed`)
    "head",             # the final norm and the lm head (`_unembed`)
    "residual",         # each sublayer's pre-norm and its residual add
    "mlp",              # the MLP's activation (its linears: below)
    "kv_write",         # quantize fresh K/V, scatter it, step the lengths
    "kv_read",          # table gather and dequantize of K/V (kernels too)
    "attention",        # rope, qk-norm, scores, softmax, PV
    "weight_dequant",   # fp8 weight blocks to the activation dtype
    "act_quant",        # 1x128 activation quantize and its dequantize
    "matmul",           # the linears' dots (bf16, QDQ or the fp8 kernel)
    "quantize",         # weight sync: bf16 policy to blockwise fp8
)

PROGRAM_SPANS = (
    "weight_sync",      # the whole of rl.weight_sync.sync_policy_weights
    "rl.prompts", "rl.sync", "rl.rollout", "rl.rewards", "rl.update",
    "rl.calibrate", "rl.checkpoint",    # the phases of RLTrainer.train_step
    "engine.step",      # ServingEngine.execute
)


def scope(name: str):
    """A device scope: every op traced inside it carries `name` in its
    HLO `op_name` path."""
    assert name in DEVICE_SCOPES, name
    return jax.named_scope(name)


def span(name: str):
    """A program host span on the profiler's clock."""
    assert name in PROGRAM_SPANS, name
    return jax.profiler.TraceAnnotation(name)
