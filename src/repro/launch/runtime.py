"""Process set-up shared by the launchers and `chip_smoke.py`.

`enable_compile_cache()` turns on JAX's persistent compilation cache once
per process: where `JAX_COMPILATION_CACHE_DIR` is set JAX already reads it
and nothing else is configured; otherwise the cache lives at the fixed
`<checkout>/.jax_cache`, so every run from one checkout shares it (the
path is part of the cache key — a directory that moves never hits).

`describe_cut()` is the one line each launcher prints about the model it
actually built: which widths it kept and what depth/vocabulary it cut.
"""
from __future__ import annotations

import json
import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def describe_cut(full, cfg) -> str:
    """JSON line naming the built config against its published one."""
    return json.dumps({
        "cut": cfg.name,
        "n_layers": [cfg.n_layers, full.n_layers],
        "vocab_size": [cfg.vocab_size, full.vocab_size],
        "d_model": [cfg.d_model, full.d_model],
        "d_ff": [cfg.d_ff, full.d_ff],
        "heads": [cfg.n_heads, cfg.n_kv_heads, cfg.d_head],
        "params": cfg.param_count(),
    })
