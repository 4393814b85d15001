"""GPipe-style pipeline parallelism via shard_map + ppermute.

The production dry-run meshes use DP x TP (DESIGN.md §3); this module
provides the PP capability for depth-dominated deployments and is validated
in tests on a small stage mesh (equivalence with the sequential stack).

Schedule: classic GPipe fill-drain.  With S stages and M microbatches the
loop runs M + S - 1 ticks; at tick t, stage s processes microbatch (t - s)
if it exists.  Activations hop stages through `ppermute` (maps onto ICI
neighbour links on a real pod), outputs accumulate at the last stage and
are returned to all stages with a final psum (cheap: one output tensor).

Bubble fraction = (S-1)/(M+S-1) — reported by `bubble_fraction` so the
launcher can pick M.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from jax import shard_map
from jax.sharding import PartitionSpec as P


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def pipeline_apply(
    stage_fn: Callable,        # (stage_params, x) -> y   (same shape)
    mesh: Mesh,
    stage_axis: str = "stage",
):
    """Returns pipelined(params_stacked, x_microbatched).

    params_stacked : (S, ...) pytree — stage s uses slice s.
    x_microbatched : (M, mb, ...) — M microbatches.
    Result         : (M, mb, ...) = stack of stage_{S-1}(...stage_0(x_m)).
    """
    n_stages = mesh.shape[stage_axis]

    def _inner(stage_params, xs):
        # stage_params: (1, ...) local slice; xs: full (M, mb, ...) replicated
        sp = jax.tree.map(lambda a: a[0], stage_params)
        s = jax.lax.axis_index(stage_axis)
        m = xs.shape[0]
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(t, carry):
            buf, out = carry
            mb_idx = t - s
            active = jnp.logical_and(mb_idx >= 0, mb_idx < m)
            # stage 0 ingests a fresh microbatch; others take the ppermuted buf
            fresh = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, m - 1), axis=0, keepdims=False)
            x_in = jnp.where(s == 0, fresh, buf)
            y = stage_fn(sp, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage writes its finished microbatch into the output slab
            out_idx = jnp.clip(mb_idx, 0, m - 1)
            write = jnp.logical_and(active, s == n_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(out, out_idx, 0, keepdims=False)
            upd = jnp.where(write, y, cur)
            out = jax.lax.dynamic_update_index_in_dim(out, upd, out_idx, 0)
            buf = jax.lax.ppermute(y, stage_axis, perm)
            return buf, out

        buf0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)
        _, out = jax.lax.fori_loop(0, m + n_stages - 1, tick, (buf0, out0))
        # outputs live on the last stage only; share them with everyone
        mine = jnp.where(s == n_stages - 1, out, jnp.zeros_like(out))
        return jax.lax.psum(mine, stage_axis)

    def pipelined(params_stacked, x_microbatched):
        in_specs = (
            jax.tree.map(lambda _: P(stage_axis), params_stacked),
            P(),
        )
        fn = shard_map(_inner, mesh=mesh, in_specs=in_specs, out_specs=P(),
                       check_vma=False)
        return fn(params_stacked, x_microbatched)

    return pipelined
