"""Gated MLP (SwiGLU / GeGLU) with FP8-aware linears."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.fp8_linear import linear
from repro.core.precision import PrecisionConfig
from repro.models.common import constrain, dense_init
from repro.obs.scopes import scope

_ACT = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
}


def init_mlp_params(keygen, cfg, dtype=jnp.bfloat16) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    p = {
        "wg": dense_init(keygen(), (d, f), d, dtype),
        "wd": dense_init(keygen(), (f, d), f, dtype),
        "norm_scale": jnp.ones((d,), dtype),
    }
    if cfg.mlp_gated:
        p["wu"] = dense_init(keygen(), (d, f), d, dtype)
    return p


@scope("mlp")
def mlp_forward(x: jax.Array, params: dict, cfg,
                precision: Optional[PrecisionConfig] = None) -> jax.Array:
    act = _ACT[cfg.act]
    g = linear(x, params["wg"], precision=precision)
    if cfg.mlp_gated:
        u = linear(x, params["wu"], precision=precision)
        h = act(g) * u
    else:
        h = act(g)
    h = constrain(h, "act_btf")
    return linear(h, params["wd"], precision=precision)
