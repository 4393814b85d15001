"""A tiny cell of each traffic kind, for CPU tests of the harness: every
width cut to a few hundred, the mixes cut to a few sequences."""
from __future__ import annotations

import importlib.util
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import common, program  # noqa: E402,F401

CONFIG = {"name": "tiny", "source": "tests", "hidden_size": 256,
          "intermediate_size": 512, "num_attention_heads": 2,
          "num_key_value_heads": 1, "head_dim": 128, "num_hidden_layers": 2,
          "vocab_size": 512, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
          "hidden_act": "silu", "tie_word_embeddings": False,
          "qk_norm": True}
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
# between the tiny cells' sound readings and their controls' and faults'
LIMITS = {
    "rollout": {"logp_gap_max": 5.0, "missing_tokens": 0.0},
    "rl_step": {"loss_gap": 0.02, "reward_gap": 0.0, "grad_gap": 0.5,
                "change_gap": 0.5, "logp_gap_max": 3.0},
}


def run_module():
    spec = importlib.util.spec_from_file_location("bench_run_main",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the benchmark's own traffic file of each kind, and the metrics a cell of
# that kind reports
TRAFFIC = {"rollout": "grpo_r256.fp8", "rl_step": "rl_short.fp8"}
END_TO_END = {"rollout": ["rollout_tokens_per_s", "setup_s"],
              "rl_step": ["rl_step_s", "setup_s"]}


def cell(kind: str) -> common.Cell:
    """A tiny cell of `kind` ("rollout" or "rl_step") on the benchmark's
    traffic file of that kind, cut to a few sequences."""
    t = common.load_json(BENCH / "traffic" / f"{TRAFFIC[kind]}.json")
    if kind == "rollout":
        t.update(prompts=2, samples_per_prompt=2, prompt_pad=16,
                 prompt_lengths=[8, 12], new_tokens=8, page=4)
        t["check"]["sequences"] = 2
    else:
        t.update(prompts=4, samples_per_prompt=4)
        t["weights"]["channel"]["rms_final"] = 1.1
    limits = {"numbers": {k: {"limit": v} for k, v in LIMITS[kind].items()}}
    e2e = [{"name": n, "unit": "x"} for n in END_TO_END[kind]]
    return common.Cell(name=f"tiny.{kind}", chips=1, config=dict(CONFIG),
                       traffic=t, limits=limits, end_to_end=e2e,
                       per_layer=[])


def no_compile_cache(monkeypatch):
    """Keep a test off the checkout's persistent compile cache, and give
    back the cache setting a run changes."""
    import jax
    import repro.launch.runtime as runtime

    monkeypatch.setattr(runtime, "enable_compile_cache", lambda: "off")
    prev = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev)
