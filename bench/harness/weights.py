"""Weights made from the seed, on the device, in one jitted call.

The program's parameter tree gives only the structure (leaf paths and
shapes, through `jax.eval_shape`); every value is drawn here, so the
reference never reads weights that the program made.  Scales follow the
usual initialisation: embeddings N(0, 0.02), each matrix N(0, 1/fan_in),
norm scales 1.

A traffic mix may ask for a bias channel: hidden unit 0 of the residual
stream holds a constant, no layer reads or writes it, and the output
head's row 0 turns it into a fixed logit offset for chosen token ids.  It
stands in for what a trained policy has and random weights lack: a
preference for some tokens, such as the answer format of a task, or
against one, such as end-of-sequence.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16
# leaves that read the residual stream (row 0 cut off from the channel)
_READERS = ("wq", "wk", "wv", "wg", "wu")
# leaves that write it (column 0 cut off)
_WRITERS = ("wo", "wd")


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _leaf(key, name: str, shape):
    last = name.rsplit("/", 1)[-1]
    if "norm" in last:
        return jnp.ones(shape, BF16)
    if last == "emb":
        return (jax.random.normal(key, shape, BF16) * 0.02).astype(BF16)
    return (jax.random.normal(key, shape, BF16)
            * (shape[-2] ** -0.5)).astype(BF16)


def _bias_channel(name: str, leaf, channel: dict):
    last = name.rsplit("/", 1)[-1]
    if last == "emb":
        return leaf.at[:, 0].set(channel["value"])
    if last in _READERS:
        return leaf.at[..., 0, :].set(0)
    if last in _WRITERS:
        return leaf.at[..., :, 0].set(0)
    if last == "lm_head":
        # after the final norm the channel reads value / rms_final
        unit = channel["value"] / channel["rms_final"]
        for ids, offset in channel["logits"]:
            leaf = leaf.at[0, jnp.asarray(ids)].set(offset / unit)
        return leaf
    return leaf


def make(structure, seed: int, spec=None):
    """The policy's bf16 weights for a 32-bit `seed`, on the default
    device.  structure: the abstract tree from `structure_of`.  spec: the
    traffic's `weights` entry, or None: {"head_scale": s} multiplies the
    output head (logits of standard deviation s rather than 1, so that the
    next-token distribution is a few nats wide, as a trained model's, and
    not all but flat), and {"channel": {"value": v, "rms_final": r,
    "logits": [[ids, offset], ...]}} adds the bias channel.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(structure)
    build = _builder(treedef, tuple(_path(p) for p, _ in flat),
                     tuple(s.shape for _, s in flat),
                     json.dumps(spec or {}, sort_keys=True))
    return build(jax.random.key(seed))


@functools.lru_cache(maxsize=None)
def _builder(treedef, names, shapes, spec_json: str):
    """One jitted maker per tree and spec, built once per process."""
    spec = json.loads(spec_json)
    head_scale = spec.get("head_scale", 1.0)
    channel = spec.get("channel")

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(names))
        leaves = [_leaf(k, n, s) for k, n, s in zip(keys, names, shapes)]
        leaves = [(x * head_scale).astype(BF16) if n == "lm_head" else x
                  for n, x in zip(names, leaves)]
        if channel is not None:
            leaves = [_bias_channel(n, x, channel)
                      for n, x in zip(names, leaves)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build


def structure_of(init_params, cfg):
    """The abstract parameter tree of `init_params(cfg, key)`."""
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
