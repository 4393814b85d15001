"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--full]

--full runs the long training-curve configurations; the default is a quick
pass suitable for CI.
"""
from __future__ import annotations

import sys
import time
import traceback

MODULES = [
    ("rollout_perf", "Fig 3/5/9/14 rollout ms/token (roofline-modeled)"),
    ("kv_capacity", "§2.3.2 fp8-KV capacity/preemption (serving engine)"),
    ("prefix_sharing", "GRPO prefix-block sharing (refcount + CoW)"),
    ("continuous_batching", "Scheduler: chunked-prefill TTFT + eviction"),
    ("kernel_hotpath", "Pallas hot path: trace parity + bytes-moved gate"),
    ("spec_decode", "Speculative decoding: acceptance + bit-exact + bytes"),
    ("hybrid_serving", "SSM/enc-dec swap-resume + fp8 hybrid capacity"),
    ("weight_sync", "§2.1.2 weight-sync cost + quant error"),
    ("live_update", "Live fleet: hot-swap attribution + replica scaling"),
    ("observability", "Step-trace telemetry: zero-perturbation + reconcile"),
    ("tiered_kv", "Two-tier KV: host-tier prefix revival vs recompute"),
    ("fault_tolerance", "Fleet chaos: failover exactly-once + atomic push"),
    ("router_precision", "Fig 6 router precision mismatch-KL"),
    ("scale_format", "Fig 12 FP32 vs UE8M0 scales mismatch-KL"),
    ("recipe_ablation", "Fig 11 hybrid vs pure-E4M3 grad profiling"),
    ("training_curves", "Fig 2/8 dense RL curves"),
    ("moe_curves", "Fig 4 MoE RL curves"),
    ("roofline_table", "§Roofline dry-run summary"),
]


def main() -> None:
    quick = "--full" not in sys.argv
    print("name,us_per_call,derived")
    failed = []
    for mod_name, desc in MODULES:
        t0 = time.time()
        print(f"# {mod_name}: {desc}", flush=True)
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
            mod.main(quick=quick)
        except Exception:
            failed.append(mod_name)
            print(f"{mod_name}/ERROR,0.0,{traceback.format_exc(limit=3)!r}")
        print(f"# {mod_name} done in {time.time()-t0:.1f}s", flush=True)
    if failed:
        sys.exit(f"failed modules: {', '.join(failed)}")


if __name__ == '__main__':
    main()
