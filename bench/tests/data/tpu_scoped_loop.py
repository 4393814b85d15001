"""Writes `tpu_scoped_loop.xplane.pb`, the TPU trace the scope tests
read: a jitted 5-pass loop whose body is scoped `decode/kv_read` and
`decode/matmul`, traced once after a warm-up call.  Run it on a TPU:

    python3 bench/tests/data/tpu_scoped_loop.py <output directory>
"""
import pathlib
import sys

import jax
import jax.numpy as jnp


@jax.jit
def f(x, w):
    def body(i, x):
        with jax.named_scope("decode"):
            with jax.named_scope("kv_read"):
                y = jnp.sin(x.astype(jnp.float32)) * 2
            with jax.named_scope("matmul"):
                return (y.astype(jnp.bfloat16) @ w).astype(x.dtype)
    return jax.lax.fori_loop(0, 5, body, x)


if __name__ == "__main__":
    x = jnp.ones((512, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 1e-3
    f(x, w).block_until_ready()
    with jax.profiler.trace(str(pathlib.Path(sys.argv[1]))):
        with jax.profiler.TraceAnnotation("outer"):
            f(x, w).block_until_ready()
