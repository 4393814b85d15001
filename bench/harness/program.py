"""The benchmark's only bridge to the program under test: the program's
model configuration built from a configuration file, its precision
recipes by name, and the structure of its parameter tree."""
from __future__ import annotations

import sys

from .common import SRC

if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))


def arch(c: dict):
    """The program's `ArchConfig` for a configuration file, with every
    size taken from the file."""
    from repro.configs.base import ArchConfig

    if c.get("tie_word_embeddings") or c.get("hidden_act") != "silu":
        raise ValueError(f"{c['name']}: only untied SwiGLU decoders run here")
    return ArchConfig(
        name=c["name"], family="dense", source=c["source"],
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        qk_norm=bool(c.get("qk_norm")), tie_embeddings=False)


def precision(recipe: str):
    """A `launch.train` precision recipe by its name."""
    from repro.launch.train import PRECISIONS

    return PRECISIONS[recipe]


def structure(cfg):
    """The abstract parameter tree the program's `init_params` builds."""
    from repro.models import init_params

    from .weights import structure_of
    return structure_of(init_params, cfg)
