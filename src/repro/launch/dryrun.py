import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
# the 512 devices are virtual host devices: this process and the children
# it starts (which inherit the environment) never take an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run driver (assignment: MULTI-POD DRY-RUN).

For every (architecture x input-shape x mesh) cell:
  * build the production mesh (16x16 single pod / 2x16x16 multi-pod),
  * lower + compile the cell's step (train_step / prefill_step / serve_step)
    from ShapeDtypeStruct inputs — no allocation anywhere,
  * print `memory_analysis()` (fits-per-device proof) and
    `cost_analysis()` (FLOPs/bytes for §Roofline),
  * parse post-SPMD HLO for collective bytes,
  * write one JSON per cell into benchmarks/dryrun_results/.

Cost accounting: XLA counts a `while` (layer-scan) body ONCE, so raw
cost_analysis undercounts by ~n_layers.  Each cell therefore also compiles
two tiny *unrolled* accounting variants (R=1 and R=2 pattern repeats; for
enc-dec a third) and fits  total = outside + R * per_layer  exactly.  The
full scanned artifact remains the source of truth for memory and for the
"compiles on the production mesh" proof.

Usage:
  python -m repro.launch.dryrun --all                # every cell, both meshes
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k --mesh single
  python -m repro.launch.dryrun --list
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback

RESULTS_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "benchmarks",
    "dryrun_results")

# precision of the paper-faithful baseline: FP8 rollout (linears+KV), BF16 train
BASE_PRECISION = "fp8"


def cell_list():
    """All cells, multi-pod (cheap compile proofs) first, small archs first —
    so a budget-limited sequential grind banks the broadest coverage early."""
    from repro.configs import ASSIGNED
    by_size = sorted(ASSIGNED, key=lambda n: ASSIGNED[n].param_count())
    cells = []
    for mesh in ("multi", "single"):
        for name in by_size:
            for shape in ASSIGNED[name].shapes():
                cells.append((name, shape.name, mesh))
    return cells


def result_path(arch, shape, mesh, precision=BASE_PRECISION, tag=""):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    return os.path.join(
        RESULTS_DIR, f"{arch}__{shape}__{mesh}__{precision}{suffix}.json")


# ---------------------------------------------------------------------------
# single-cell execution (in-process)
# ---------------------------------------------------------------------------

def _lower_and_compile(cfg, shape, mesh, rules, precision, opt_cfg,
                       attn_impl: str = "naive"):
    """Build + lower + compile one step for one cfg variant."""
    import jax

    from repro.launch import steps as steps_mod
    from repro.models.attention import attention_impl
    from repro.models.common import activation_sharding
    from repro.optim import init as opt_init

    with mesh, activation_sharding(rules), attention_impl(attn_impl):
        if shape.kind == "train":
            step = steps_mod.make_train_step(cfg, None, opt_cfg)
            p_specs = steps_mod.param_specs(cfg)
            o_specs = jax.eval_shape(lambda p: opt_init(p, opt_cfg), p_specs)
            b_specs = steps_mod.input_specs(cfg, shape)
            in_sh = (rules.params(p_specs), rules.params(o_specs),
                     rules.batch_spec(b_specs))
            lowered = jax.jit(
                step, in_shardings=in_sh,
                out_shardings=(in_sh[0], in_sh[1], None),
                donate_argnums=(0, 1),
            ).lower(p_specs, o_specs, b_specs)
        elif shape.kind == "prefill":
            step = steps_mod.make_prefill_step(cfg, shape, precision)
            p_specs = steps_mod.param_specs(cfg, precision)
            b_specs = steps_mod.input_specs(cfg, shape)
            cache_out = jax.eval_shape(step, p_specs, b_specs)[1]
            in_sh = (rules.params(p_specs), rules.batch_spec(b_specs))
            lowered = jax.jit(
                step, in_shardings=in_sh,
                out_shardings=(None, rules.cache_spec(cache_out)),
            ).lower(p_specs, b_specs)
        else:  # decode
            step = steps_mod.make_serve_step(cfg, precision)
            p_specs = steps_mod.param_specs(cfg, precision)
            b_specs = steps_mod.input_specs(cfg, shape)
            c_specs = steps_mod.cache_specs(cfg, shape, precision)
            c_sh = rules.cache_spec(c_specs)
            in_sh = (rules.params(p_specs),
                     rules.batch_spec(b_specs)["tokens"], c_sh)
            lowered = jax.jit(
                step, in_shardings=in_sh, out_shardings=(None, c_sh),
                donate_argnums=(2,),
            ).lower(p_specs, b_specs["tokens"], c_specs)
        return lowered, lowered.compile()


def _raw_costs(compiled):
    from repro.roofline.analysis import collective_bytes
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    coll = collective_bytes(compiled.as_text())
    counts = coll.pop("_counts")
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": {k: float(v) for k, v in coll.items()},
        "coll_counts": counts,
    }


def _lin(base, plus_one, r_full):
    """Fit total = outside + R*body from c(R=1) and c(R=2) samples."""
    body = max(plus_one - base, 0.0)
    outside = max(base - body, 0.0)
    return outside + r_full * body


def _extrapolate(c11, c21, c12, r_dec, r_enc):
    """Linear-in-depth extrapolation of every numeric cost field."""
    def fit(get):
        b_dec = max(get(c21) - get(c11), 0.0)
        b_enc = max(get(c12) - get(c11), 0.0) if c12 is not None else 0.0
        outside = max(get(c11) - b_dec - b_enc, 0.0)
        return outside + r_dec * b_dec + r_enc * b_enc

    out = {
        "flops": fit(lambda c: c["flops"]),
        "bytes": fit(lambda c: c["bytes"]),
        "coll": {k: fit(lambda c, k=k: c["coll"][k]) for k in c11["coll"]},
    }
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             precision_name: str = BASE_PRECISION, tag: str = "",
             overrides: dict | None = None) -> dict:
    from repro.configs import get_config
    from repro.core.precision import (
        BF16_ROLLOUT, FULL_FP8_ROLLOUT, FP8_LINEAR_ROLLOUT)
    from repro.distributed import ShardingRules
    from repro.launch.mesh import make_production_mesh
    from repro.models import blocks as blocks_mod
    from repro.models.transformer import scan_unroll
    from repro.optim import AdamWConfig
    from repro.roofline.analysis import RooflineTerms, model_flops_for_cell

    cfg = get_config(arch)
    shape = next(s for s in cfg.shapes() if s.name == shape_name)
    precision = {"bf16": BF16_ROLLOUT, "fp8": FULL_FP8_ROLLOUT,
                 "fp8lin": FP8_LINEAR_ROLLOUT}[precision_name]
    overrides = overrides or {}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.size
    if overrides.get("full_tp"):
        # beyond-paper decode sharding: every mesh axis is TP — weights stay
        # resident (no per-step ZeRO gathers), activations all-reduce instead
        rules = ShardingRules(
            mesh, tp_axis=tuple(mesh.axis_names), dp_axes=(),
            vocab_parallel_ce=overrides.get("vocab_parallel_ce", False))
    else:
        rules = ShardingRules(
            mesh, zero3=overrides.get("zero3", True),
            sequence_parallel=overrides.get("sequence_parallel", False),
            vocab_parallel_ce=overrides.get("vocab_parallel_ce", False))
    # big models need fp8 optimizer moments to fit HBM (DESIGN §3)
    opt_cfg = AdamWConfig(fp8_moments=cfg.param_count() > 50e9)

    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "precision": precision_name, "n_devices": n_dev,
        "status": "running", "tag": tag, "overrides": overrides,
    }

    # ---- the real artifact: scanned, production mesh --------------------
    t0 = time.time()
    attn_impl = overrides.get("attn_impl", "naive")
    lowered, compiled = _lower_and_compile(cfg, shape, mesh, rules,
                                           precision, opt_cfg, attn_impl)
    record["compile_s"] = time.time() - t0

    ma = compiled.memory_analysis()
    print("memory_analysis:", ma)
    record["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes_est": int(ma.argument_size_in_bytes
                              + ma.temp_size_in_bytes
                              + ma.output_size_in_bytes
                              - ma.alias_size_in_bytes),
    }
    record["raw_costs_scanned"] = _raw_costs(compiled)
    del lowered, compiled

    # ---- accounting variants: unrolled R=1 / R=2 ------------------------
    # (single-pod only: the roofline table is single-pod by assignment; the
    # multi-pod pass is the sharding/compile proof)
    if mesh_kind == "multi" and not overrides.get("force_accounting"):
        record["roofline"] = None
        record["status"] = "ok"
        return record

    period = len(blocks_mod.layer_pattern(cfg))
    r_dec = cfg.n_layers // period
    enc_period = len(blocks_mod.layer_pattern(cfg, decoder=False)) \
        if cfg.is_encdec else 0
    r_enc = cfg.n_enc_layers // enc_period if cfg.is_encdec else 0

    def variant(n_dec_rep, n_enc_rep):
        changes = {"n_layers": period * n_dec_rep}
        if cfg.is_encdec:
            changes["n_enc_layers"] = enc_period * n_enc_rep
        vcfg = dataclasses.replace(cfg, **changes)
        with scan_unroll(True):
            _, c = _lower_and_compile(vcfg, shape, mesh, rules, precision,
                                      opt_cfg, attn_impl)
        return _raw_costs(c)

    t1 = time.time()
    c11 = variant(1, 1)
    c21 = variant(2, 1)
    c12 = variant(1, 2) if cfg.is_encdec else None
    record["accounting_s"] = time.time() - t1

    ext = _extrapolate(c11, c21, c12, r_dec, r_enc)
    terms = RooflineTerms(
        flops_per_device=ext["flops"],
        bytes_per_device=ext["bytes"],
        coll_bytes_per_device=float(sum(ext["coll"].values())),
        coll_breakdown={"bytes": ext["coll"],
                        "counts": record["raw_costs_scanned"]["coll_counts"]},
        model_flops=model_flops_for_cell(cfg, shape, shape.kind),
        n_devices=n_dev,
    )
    record["roofline"] = terms.to_dict()
    record["status"] = "ok"
    print(f"roofline(extrapolated): compute={terms.compute_s:.4e}s "
          f"memory={terms.memory_s:.4e}s collective={terms.collective_s:.4e}s "
          f"dominant={terms.dominant} useful_flops={terms.useful_flops_fraction:.2f} "
          f"mfu={terms.mfu:.3f}")
    return record


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------

def run_cell_subprocess(arch, shape, mesh, precision=BASE_PRECISION, tag="",
                        overrides=None, timeout=5400):
    out_path = result_path(arch, shape, mesh, precision, tag)
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--mesh", mesh, "--precision", precision]
    if tag:
        cmd += ["--tag", tag]
    if overrides:
        cmd += ["--overrides", json.dumps(overrides)]
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(RESULTS_DIR))
    env.setdefault("PYTHONPATH", os.path.join(repo_root, "src"))
    t0 = time.time()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                              timeout=timeout)
        err = proc.stderr[-4000:]
        failed = proc.returncode != 0
    except subprocess.TimeoutExpired:
        err, failed = f"timeout after {timeout}s", True
    if failed and not os.path.exists(out_path):
        record = {"arch": arch, "shape": shape, "mesh": mesh,
                  "precision": precision, "status": "error", "tag": tag,
                  "wall_s": time.time() - t0, "error": err}
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
    return out_path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--precision", default=BASE_PRECISION)
    ap.add_argument("--tag", default="")
    ap.add_argument("--overrides", default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.list:
        for c in cell_list():
            print(*c)
        return

    if args.all:
        cells = cell_list()
        for i, (arch, shape, mesh) in enumerate(cells):
            out_path = result_path(arch, shape, mesh)
            if os.path.exists(out_path) and not args.force:
                print(f"[{i+1}/{len(cells)}] cached {arch} {shape} {mesh}")
                continue
            print(f"[{i+1}/{len(cells)}] {arch} {shape} {mesh} ...",
                  flush=True)
            t0 = time.time()
            run_cell_subprocess(arch, shape, mesh)
            with open(out_path) as f:
                status = json.load(f).get("status")
            print(f"    -> {status} ({time.time()-t0:.0f}s)", flush=True)
        return

    # single-cell (in-process) mode
    overrides = json.loads(args.overrides) if args.overrides else None
    out_path = result_path(args.arch, args.shape, args.mesh, args.precision,
                           args.tag)
    try:
        record = run_cell(args.arch, args.shape, args.mesh, args.precision,
                          args.tag, overrides)
    except Exception:
        record = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
                  "precision": args.precision, "tag": args.tag,
                  "status": "error", "error": traceback.format_exc()[-6000:]}
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
        print(record["error"], file=sys.stderr)
        sys.exit(1)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    print("wrote", out_path)


if __name__ == "__main__":
    main()
