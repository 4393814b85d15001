"""Full-FP8 decode attention over an fp8 cache: the K/V QDQ from the payload.

The QDQ kernels' row QDQ must be `qdq(dequantize_per_tensor(q, s, bf16))`
bit for bit; decode attention through them must give what `_sdpa` over the
dequantized view gives; recipes without `quantize_attention` or an fp8
cache must not reach them at all.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_serving_config
from repro.core import (
    BF16_ROLLOUT,
    E4M3,
    FP8_KV_ONLY_ROLLOUT,
    FULL_FP8_ROLLOUT,
    ScaleFormat,
    qdq,
)
from repro.core.quant import (
    calibrate_scale,
    dequantize_per_tensor,
    quantize_per_tensor,
)
from repro.kernels import fp8_qdq_attention as kern
from repro.kernels import ops
from repro.models import attention as attn
from repro.models.common import KeyGen

jax.config.update("jax_platform_name", "cpu")


def _reference_qdq(q, scale):
    """What `_sdpa` applies to the K/V view of an fp8 cache: `qdq` (at its
    FP32 scale format, whatever the recipe's) of the bf16 dequantized view."""
    return qdq(dequantize_per_tensor(q, scale, jnp.bfloat16))


def _payload(case: str, d: int):
    """An fp8 payload (B, T, KVH, d) and its per-tensor scale."""
    ks = jax.random.split(jax.random.key(d), 2)
    shape = (3, 40, 2, d)
    # row magnitudes spread over e^-3 .. e^3
    x = jax.random.normal(ks[0], shape) * jnp.exp(
        jax.random.uniform(ks[1], shape[:-1] + (1,), minval=-3, maxval=3))
    if case == "zero_rows":
        # the trash page holds zeros
        x = x.at[:, :8].set(0.0)
    scale = calibrate_scale(jnp.abs(x).max(), margin=1.05)
    q = quantize_per_tensor(x, scale, E4M3)
    if case == "saturated":
        q = q.at[0, :4].set(jnp.float8_e4m3fn(448.0))
        q = q.at[1, :4, :, ::2].set(jnp.float8_e4m3fn(-448.0))
    if case == "random_bytes":
        # any code but NaN, in 512 rows: rows whose QDQ meets its ties
        b = jax.random.randint(jax.random.key(7), (16, 16, 2, d), 0, 256)
        b = jnp.where((b & 0x7F) == 0x7F, b - 1, b).astype(jnp.uint8)
        q = jax.lax.bitcast_convert_type(b, jnp.float8_e4m3fn)
    if case == "every_byte":
        # row r: every magnitude code up to its cap 126 - 7r (the row amax),
        # both signs, so every payload byte meets 16 different row scales
        k = jnp.arange(d)
        cap = 126 - 7 * jnp.arange(16)[:, None]
        code = (k % (cap + 1)) | jnp.where(k % 2 == 1, 128, 0)
        code = code.at[:, 0].set(cap[:, 0])
        rows = jax.lax.bitcast_convert_type(code.astype(jnp.uint8),
                                            jnp.float8_e4m3fn)
        q = q.at[0, :8].set(rows.reshape(8, 2, d))
    if case == "tensor_amax":
        # margin 1 puts the tensor's amax on the fp8 max of one row
        scale = calibrate_scale(jnp.abs(x).max())
        q = quantize_per_tensor(x, scale, E4M3)
        assert float(jnp.abs(q.astype(jnp.float32)).max()) == 448.0
    return q, scale


@pytest.mark.parametrize("d", [16, 128])
@pytest.mark.parametrize("case", ["mixed", "zero_rows", "saturated",
                                  "tensor_amax", "every_byte", "random_bytes"])
def test_payload_qdq_is_qdq_of_dequantized_view(case, d):
    """The kernels' row QDQ from the payload, rounded to bf16, against the
    QDQ of the dequantized view, bit for bit."""
    q, scale = _payload(case, d)
    # both op by op: under jit XLA's CPU backend may keep the dequantized
    # product in f32 (excess precision) and turns `amax / 448` into a
    # product with 1/448, which are not the recipe's math
    got = kern._qdq_rows(q, kern._scale(scale)[0])
    want = _reference_qdq(q, scale)
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.bfloat16), np.float32),
        np.asarray(want, np.float32))


def _grid_and_midpoints(dtype, top: float):
    """Every non-negative finite value of `dtype` up to `top`, the midpoints
    between neighbours (the ties) and the points just either side of them."""
    bits = {jnp.bfloat16: jnp.uint16, jnp.float8_e4m3fn: jnp.uint8}[dtype]
    n = 1 << (8 * jnp.dtype(bits).itemsize)
    grid = np.asarray(jax.lax.bitcast_convert_type(
        jnp.arange(n, dtype=jnp.uint32).astype(bits), dtype), np.float32)
    grid = np.unique(grid[np.isfinite(grid) & (grid >= 0) & (grid <= top)])
    mid = ((grid[:-1].astype(np.float64) + grid[1:]) / 2).astype(np.float32)
    pts = np.concatenate([grid, mid, np.nextafter(mid, 0), np.nextafter(mid, 1)])
    return np.concatenate([pts, -pts])


@pytest.mark.parametrize("fmt", ["bf16", "e4m3"])
def test_kernel_rounding_is_the_cast(fmt):
    """The kernels' roundings against XLA's casts, on every tie."""
    dtype, fn, top = {
        "bf16": (jnp.bfloat16, kern._round_bf16, 1e4),
        "e4m3": (jnp.float8_e4m3fn, kern._round_e4m3, 448.0),
    }[fmt]
    pts = _grid_and_midpoints(dtype, top)
    if fmt == "bf16":
        # the kernels round products of a payload and a scale: f32 normals
        pts = pts[np.abs(pts) >= np.finfo(np.float32).tiny]
    rand = np.asarray(jax.random.normal(jax.random.key(0), (20000,)) * jnp.exp(
        jax.random.uniform(jax.random.key(1), (20000,), minval=-14,
                           maxval=5)), np.float32)
    x = jnp.asarray(np.concatenate([pts, np.clip(rand, -top, top)]))
    want = np.asarray(x.astype(dtype).astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(jax.jit(fn)(x)), want)


def _cfg(group: int):
    return dataclasses.replace(tiny_serving_config(), n_heads=2 * group,
                               n_kv_heads=2, d_head=128)


def _decode(layout, cfg, precision, seed=0):
    """attention_decode of one token over a cache of random K/V."""
    b, s_max, bs = 3, 24, 4
    kg = KeyGen(jax.random.key(seed))
    params = attn.init_attn_params(kg, cfg)
    kvh, dh = cfg.n_kv_heads, cfg.d_head
    x = jax.random.normal(kg(), (b, 1, cfg.d_model)).astype(jnp.bfloat16)
    lengths = jnp.array([5, 17, 23], jnp.int32)
    if layout == "paged":
        n = b * s_max // bs
        cache = attn.init_paged_kv_cache(n, bs, kvh, dh, precision)
        # every sequence's pages scattered over the pool
        tables = jax.random.permutation(kg(), n).reshape(b, s_max // bs)
    else:
        cache = attn.init_kv_cache(b, s_max, kvh, dh, precision)
        tables = None
    k = jax.random.normal(kg(), cache.k.shape) * 2.0
    v = jax.random.normal(kg(), cache.v.shape)
    if cache.quantized:
        k_s = calibrate_scale(jnp.abs(k).max(), margin=1.05)
        v_s = calibrate_scale(jnp.abs(v).max(), margin=1.05)
        cache = cache._replace(k=quantize_per_tensor(k, k_s),
                               v=quantize_per_tensor(v, v_s),
                               k_scale=k_s, v_scale=v_s)
    else:
        cache = cache._replace(k=k.astype(cache.k.dtype),
                               v=v.astype(cache.v.dtype))
    out, _ = attn.attention_decode(x, params, cfg, cache, lengths, precision,
                                   block_tables=tables)
    return np.asarray(out, np.float32)


@pytest.mark.parametrize("recipe", ["full_fp8", "full_fp8_ue8m0", "bf16",
                                    "fp8_kv_only"])
@pytest.mark.parametrize("group", [4, 12])
@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_decode_matches_sdpa_over_dequantized_view(layout, group, recipe,
                                                   monkeypatch):
    """Decode attention with the QDQ kernels available (interpreted here)
    against `_sdpa` over the dequantized view, the path without them."""
    # `_sdpa`'s K/V QDQ keeps FP32 row scales whatever the recipe's format
    precision = {"full_fp8": FULL_FP8_ROLLOUT,
                 "full_fp8_ue8m0": dataclasses.replace(
                     FULL_FP8_ROLLOUT, scale_format=ScaleFormat.UE8M0),
                 "bf16": BF16_ROLLOUT,
                 "fp8_kv_only": FP8_KV_ONLY_ROLLOUT}[recipe]
    cfg = _cfg(group)
    want = _decode(layout, cfg, precision)
    calls = []

    def spy(*a):
        calls.append(1)
        return kern.qdq_scores(*a, interpret=True)

    monkeypatch.setattr(ops, "qdq_attention_kernels_apply", lambda q, kv: True)
    monkeypatch.setattr(ops, "qdq_scores", spy)
    got = _decode(layout, cfg, precision)
    assert bool(calls) == recipe.startswith("full_fp8")
    if recipe.startswith("full_fp8"):
        # the same K/V values; the kernels accumulate each matmul in f32
        # and round once, the einsums in their own order: outputs of about
        # 2 move by two bf16 steps (skipping the K/V QDQ moves them 0.08)
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-2)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("case,applies", [
    ("decode", True),
    ("cpu", False),
    ("two_devices", False),
    ("two_tokens", False),
    ("f32_queries", False),
    ("wide_heads", False),
])
def test_qdq_attention_kernels_apply(case, applies, monkeypatch):
    """The kernels run compiled on one TPU for one bf16 query token over
    heads of one tile; everything else keeps the XLA path."""
    monkeypatch.setattr(ops, "_interpret", lambda: case == "cpu")
    monkeypatch.setattr(jax, "device_count",
                        lambda: 2 if case == "two_devices" else 1)
    s = 2 if case == "two_tokens" else 1
    dtype = jnp.float32 if case == "f32_queries" else jnp.bfloat16
    d = 256 if case == "wide_heads" else 128
    q = jax.ShapeDtypeStruct((512, s, 32, d), dtype)
    kv = jax.ShapeDtypeStruct((512, 392, 8, d), jnp.float8_e4m3fn)
    assert ops.qdq_attention_kernels_apply(q, kv) == applies


@pytest.mark.parametrize("b,t,group,d", [
    (4, 24, 4, 128),
    (2, 40, 12, 64),
    # past 1024 keys the kernels take 512-key blocks; the last runs past T
    (2, 1032, 4, 128),
])
def test_qdq_kernels_match_payload_qdq(b, t, group, d):
    kvh = 2
    ks = jax.random.split(jax.random.key(t), 4)
    x = jax.random.normal(ks[0], (b, t, kvh, d)) * jnp.exp(
        jax.random.uniform(ks[1], (b, t, kvh, 1), minval=-3, maxval=3))
    scale = calibrate_scale(jnp.abs(x).max(), margin=1.05)
    payload = quantize_per_tensor(x, scale)
    kv = _reference_qdq(payload, scale).astype(jnp.float32)
    q = jax.random.normal(ks[2], (b, kvh, group, d)).astype(jnp.bfloat16)
    p = jax.nn.softmax(jax.random.normal(ks[3], (b, kvh, group, t)), -1) \
        .astype(jnp.bfloat16)
    hi = jax.lax.Precision.HIGHEST
    want = jnp.einsum("bkgd,btkd->bkgt", q.astype(jnp.float32), kv,
                      precision=hi)
    got = kern.qdq_scores(q, payload, scale, interpret=True)
    # f32 accumulation, rounded once to bf16 (2^-8 relative)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2 ** -8, atol=1e-3)
    want = jnp.einsum("bkgt,btkd->bkgd", p.astype(jnp.float32), kv,
                      precision=hi)
    got = kern.qdq_pv(p, payload, scale, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2 ** -8, atol=1e-3)
