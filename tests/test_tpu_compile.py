"""The Pallas kernels compile for a TPU v5e at qwen3-8b widths.

Interpret mode (every other kernel test) cannot see the TPU compiler's
rules — block shapes whose last two dims must divide by (8, 128) or equal
the array's, Mosaic's layouts, VMEM limits.  These tests compile each
kernel with `interpret=False` for a *described* v5e (no chip attached:
the TPU compiler is installed, nothing runs) and check the program holds
the Mosaic kernel.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import fp8_gemm, fp8_quant
from repro.kernels import fp8_kv_attention as attn

CFG = get_config("qwen3-8b")
KVH, D = CFG.n_kv_heads, CFG.d_head
G = CFG.n_heads // CFG.n_kv_heads
K, N = CFG.d_model, CFG.d_ff
M, B, PAGES, PAGE, TABLE, CHUNK = 256, 8, 64, 16, 8, 16
F8 = jnp.float8_e4m3fn


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    # the TPU compiler is part of this repo's test installation: a
    # topology that cannot be described is a failure, not a skip
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a described chip's programs can be written to the persistent cache
    # but never read back: keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


KERNELS = {
    "quantize_activation": (
        lambda x: fp8_quant.quantize_activation_kernel(x, interpret=False),
        [((M, K), jnp.bfloat16)]),
    "quantize_weight": (
        lambda w: fp8_quant.quantize_weight_kernel(w, interpret=False),
        [((K, N), jnp.bfloat16)]),
    "fp8_gemm": (
        lambda a, w, a_s, w_s: fp8_gemm.fp8_gemm(a, w, a_s, w_s,
                                                 interpret=False),
        [((M, K), F8), ((K, N), F8), ((M, K // 128), jnp.float32),
         ((K // 128, N // 128), jnp.float32)]),
    "paged_decode": (
        lambda *a: attn.fp8_paged_decode_attention(*a, interpret=False),
        [((B, KVH, G, D), jnp.bfloat16), ((PAGES, PAGE, KVH, D), F8),
         ((PAGES, PAGE, KVH, D), F8), ((), jnp.float32), ((), jnp.float32),
         ((B, TABLE), jnp.int32), ((B,), jnp.int32)]),
    "paged_prefill": (
        lambda *a: attn.fp8_paged_prefill_attention(*a, interpret=False),
        [((B, CHUNK, KVH, G, D), jnp.bfloat16), ((PAGES, PAGE, KVH, D), F8),
         ((PAGES, PAGE, KVH, D), F8), ((), jnp.float32), ((), jnp.float32),
         ((B, TABLE), jnp.int32), ((B,), jnp.int32), ((B,), jnp.int32)]),
    "contiguous_decode": (
        lambda *a: attn.fp8_decode_attention(*a, interpret=False),
        [((B, KVH, G, D), jnp.bfloat16), ((B, 1024, KVH, D), F8),
         ((B, 1024, KVH, D), F8), ((), jnp.float32), ((), jnp.float32),
         ((B,), jnp.int32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("page", [4, 8])
def test_paged_kernels_compile_at_small_pages(one_chip, page):
    """Whole pages carry all KV heads, so (KVH, D) are the array's own
    dims and the engine's small default page sizes are legal too."""
    pool = ((PAGES, page, KVH, D), F8)
    scalar = ((), jnp.float32)
    ids = ((B,), jnp.int32)
    _compile(lambda *a: attn.fp8_paged_decode_attention(*a, interpret=False),
             one_chip, ((B, KVH, G, D), jnp.bfloat16), pool, pool, scalar,
             scalar, ((B, TABLE), jnp.int32), ids)
    _compile(lambda *a: attn.fp8_paged_prefill_attention(*a,
                                                         interpret=False),
             one_chip, ((B, 5, KVH, G, D), jnp.bfloat16), pool, pool,
             scalar, scalar, ((B, TABLE), jnp.int32), ids, ids)
