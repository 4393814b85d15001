"""The operation and byte counts against hand counts at qwen3-8b's
published widths (4 layers, 18,992 vocabulary rows)."""
from __future__ import annotations

import bench_tiny  # noqa: F401  (puts the harness on the path)
from harness import common, counts

QWEN = counts.Dims.from_config(
    common.load_json(common.BENCH / "configs" / "qwen3-8b.json"))
# one layer's linears: wq 4096x4096, wk and wv 4096x1024, wo 4096x4096,
# gate, up and down 4096x12288
LAYER = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 12288
LIN = 4 * LAYER
HEAD = 4096 * 18992
KV_TOKEN = 4 * 2 * 8 * 128            # K and V entries a token adds


def test_layer_params():
    assert QWEN.layer_linear_params == LAYER == 192_937_984
    assert QWEN.kv_elems_per_token == KV_TOKEN


def test_one_decode_pass_of_qwen3_8b():
    prefill, decode = counts.generate_work(
        QWEN, [64], group=1, new_tokens=2, page=8, shared_blocks=0,
        linear_bytes=1, kv_bytes=1)
    # the second token: one position at 64 attending to 65 keys
    flops = 2 * LIN + 4 * 4 * 32 * 128 * 65 + 2 * HEAD
    assert decode.flops == flops
    weights = LIN + 4 * LIN // (128 * 128) + 2 * HEAD + 9 * 4096 * 2
    # reads the 64 cached positions, writes one K/V row and reads one
    # embedding row
    assert decode.bytes == weights + 64 * KV_TOKEN + KV_TOKEN + 4096 * 2
    assert prefill.flops == 64 * 2 * LIN + 4 * 4 * 32 * 128 * (64 * 65 // 2) \
        + 2 * HEAD


def test_a_group_reads_its_shared_prefix_once():
    _, one = counts.generate_work(QWEN, [64], 1, 2, 8, 0, 2, 2)
    _, shared = counts.generate_work(QWEN, [64], 8, 2, 8, 8, 2, 2)
    # 8 samples of a 64-token prompt whose 8 pages of 8 are shared read
    # those 64 positions once, and each writes its own K/V row
    assert shared.bytes - one.bytes == 7 * (KV_TOKEN * 2 + 4096 * 2)
    assert shared.flops == 8 * one.flops


def test_one_update_of_qwen3_8b():
    fwd = 2 * 28 * LIN + 4 * 4 * 32 * 128 * (28 * 29 // 2) + 2 * 28 * HEAD
    assert counts.update_flops(QWEN, [28]) == 3 * fwd


def test_least_time_is_the_larger_bound():
    w = counts.Work(flops=197e12, bytes=819e9 / 2)
    assert w.least_seconds(197e12, 819e9) == 1.0
    w = counts.Work(flops=1.0, bytes=819e9 * 2)
    assert w.least_seconds(197e12, 819e9) == 2.0
