"""Roofline / dry-run infrastructure tests (no 512-device mesh needed)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import REGISTRY, get_config
from repro.configs.base import DECODE_32K, PREFILL_32K, TRAIN_4K
from repro.core.precision import FULL_FP8_ROLLOUT
from repro.launch import steps as steps_mod
from repro.roofline.analysis import (
    RooflineTerms,
    collective_bytes,
    model_flops_for_cell,
)

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# collective-bytes HLO parser
# ---------------------------------------------------------------------------

HLO_SAMPLE = """
HloModule jit_step
  %x.1 = bf16[8,128]{1,0} all-gather(%p0), replica_groups={}
  %y = f32[256]{0} all-reduce(%z), to_apply=%add
  ROOT %t = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-to-all(%a, %b)
  %ignored = bf16[8,128]{1,0} add(%x.1, %x.1)
  %ag2 = f32[16]{0} all-gather-start(%q)
  %ag3 = f32[16]{0} all-gather-done(%ag2)
  %cp = u8[1024]{0} collective-permute(%w)
"""


def test_collective_bytes_parser():
    out = collective_bytes(HLO_SAMPLE)
    counts = out.pop("_counts")
    assert out["all-gather"] == 8 * 128 * 2 + 16 * 4   # start counted, done not
    assert out["all-reduce"] == 256 * 4
    assert out["all-to-all"] == 2 * 16 * 4             # tuple result
    assert out["collective-permute"] == 1024
    assert counts["all-gather"] == 2
    assert out["reduce-scatter"] == 0


def test_collective_bytes_on_real_compile():
    """Parser agrees with a known collective: psum of f32[1024] -> 4KB."""
    def f(x):
        return jax.lax.psum(x, "i")

    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = jax.make_mesh((1,), ("i",))
    g = shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    txt = jax.jit(g).lower(jnp.zeros((1024,), jnp.float32)).compile().as_text()
    out = collective_bytes(txt)
    out.pop("_counts")
    # single-device psum may be optimized away entirely; parser must not crash
    assert all(v >= 0 for v in out.values())


# ---------------------------------------------------------------------------
# roofline terms
# ---------------------------------------------------------------------------

def test_roofline_terms_math():
    t = RooflineTerms(
        flops_per_device=197e12,       # exactly 1s of compute
        bytes_per_device=819e9 * 2,    # 2s of memory
        coll_bytes_per_device=50e9 * 3,  # 3s of collectives
        coll_breakdown={}, model_flops=197e12 * 256, n_devices=256)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(2.0)
    assert t.collective_s == pytest.approx(3.0)
    assert t.dominant == "collective"
    assert t.step_time_s == pytest.approx(3.0)
    assert t.useful_flops_fraction == pytest.approx(1.0)
    assert t.mfu == pytest.approx(1 / 3)


def test_model_flops_conventions():
    cfg = get_config("llama3.2-3b")
    n = cfg.active_param_count()
    assert model_flops_for_cell(cfg, TRAIN_4K, "train") == \
        pytest.approx(6.0 * n * 256 * 4096)
    assert model_flops_for_cell(cfg, PREFILL_32K, "prefill") == \
        pytest.approx(2.0 * n * 32 * 32768)
    assert model_flops_for_cell(cfg, DECODE_32K, "decode") == \
        pytest.approx(2.0 * n * 128)


def test_moe_active_params_less_than_total():
    cfg = get_config("grok-1-314b")
    assert cfg.active_param_count() < 0.5 * cfg.param_count()


# ---------------------------------------------------------------------------
# input/cache/param specs: every assigned cell has well-formed stand-ins
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", sorted(REGISTRY))
def test_input_specs_every_cell(arch):
    cfg = get_config(arch)
    for shape in cfg.shapes():
        specs = steps_mod.input_specs(cfg, shape)
        assert "tokens" in specs
        for leaf in jax.tree.leaves(specs):
            assert isinstance(leaf, jax.ShapeDtypeStruct)
        if shape.kind == "train":
            total = specs["tokens"].shape[1] + (
                specs["patches"].shape[1] if "patches" in specs else 0)
            assert total == shape.seq_len
            assert specs["tokens"].shape[0] == shape.global_batch
        elif shape.kind == "decode":
            assert specs["tokens"].shape == (shape.global_batch,)
            cache = steps_mod.cache_specs(cfg, shape, FULL_FP8_ROLLOUT)
            # at least one slot holds state; kv caches sized seq_len
            for name, slot in cache["slots"].items():
                if "kv" in slot:
                    assert slot["kv"].k.shape[2] == shape.seq_len
                    assert slot["kv"].k.dtype == jnp.float8_e4m3fn


def test_param_specs_quantized_tree():
    cfg = get_config("granite-moe-3b-a800m").reduced()
    specs = steps_mod.param_specs(cfg, FULL_FP8_ROLLOUT)
    from repro.core.quant import QuantizedTensor
    leaves = [l for l in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, QuantizedTensor))
        if isinstance(l, QuantizedTensor)]
    assert leaves, "rollout param specs must contain QuantizedTensors"


def test_dryrun_cell_list_counts():
    from repro.launch.dryrun import cell_list
    cells = cell_list()
    assert len(cells) == 64                       # 32 per mesh
    assert sum(1 for c in cells if c[2] == "multi") == 32
    long_cells = {c[0] for c in cells if c[1] == "long_500k"}
    assert long_cells == {"mamba2-780m", "jamba-1.5-large-398b"}
