"""Distributed-layer tests.

Multi-device behaviour needs `--xla_force_host_platform_device_count`,
which must be set before jax initializes — so each test runs a small
program in a subprocess.  Pure-logic pieces (safe_spec) run in-process.
"""
import os
import subprocess
import sys
import textwrap

import jax
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import safe_spec

jax.config.update("jax_platform_name", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_prog(src: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(src)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr}\nstdout:\n{out.stdout}"
    return out.stdout


# ---------------------------------------------------------------------------
# safe_spec (pure logic, single device OK)
# ---------------------------------------------------------------------------

def test_safe_spec_drops_nondividing():
    mesh = jax.make_mesh((1,), ("model",))

    class FakeMesh:
        shape = {"data": 4, "model": 8}
        axis_names = ("data", "model")

    m = FakeMesh()
    assert safe_spec(m, (24, 32), P("data", "model")) == P("data", "model")
    assert safe_spec(m, (25, 32), P("data", "model")) == P(None, "model")
    assert safe_spec(m, (24, 30), P("data", "model")) == P("data", None)
    assert safe_spec(m, (24,), P(("data", "model"))) == P(None)
    assert safe_spec(m, (32,), P(("data", "model"))) == P(("data", "model"))
    del mesh


# ---------------------------------------------------------------------------
# sharded train-step compile with ShardingRules (8 devices: 2 dp x 4 tp)
# ---------------------------------------------------------------------------

def test_sharded_train_step_compiles_and_reduces():
    out = run_prog("""
        import jax, jax.numpy as jnp, re
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import init_params, forward_train
        from repro.distributed import ShardingRules
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(dp=2, tp=4)
        cfg = get_config("granite-moe-3b-a800m").reduced(
            d_model=64, d_ff=64, vocab_size=256, n_layers=2)
        params = init_params(cfg, jax.random.key(0))
        rules = ShardingRules(mesh, zero3=True)
        pspec = rules.params(params)

        def loss_fn(p, tokens):
            logits, aux = forward_train(p, {"tokens": tokens}, cfg)
            lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
            return -jnp.mean(jnp.take_along_axis(lp, tokens[:, 1:, None], -1))

        def train_step(p, tokens):
            l, g = jax.value_and_grad(loss_fn)(p, tokens)
            return jax.tree.map(lambda a, b: a - 1e-3 * b.astype(a.dtype), p, g), l

        tokens = jax.ShapeDtypeStruct((8, 16), jnp.int32)
        tok_sh = NamedSharding(mesh, P("data", None))
        with mesh:
            lowered = jax.jit(train_step,
                              in_shardings=(pspec, tok_sh),
                              out_shardings=(pspec, None)).lower(
                jax.eval_shape(lambda: params), tokens)
            compiled = lowered.compile()
        txt = compiled.as_text()
        colls = sorted(set(re.findall(
            r'(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)', txt)))
        print("COLLECTIVES:", ",".join(colls))
        # run it for real to confirm numerics
        params_sharded = jax.device_put(params, pspec)
        tok = jax.device_put(
            jax.random.randint(jax.random.key(1), (8, 16), 0, 256), tok_sh)
        with mesh:
            new_p, loss = jax.jit(train_step, in_shardings=(pspec, tok_sh),
                                  out_shardings=(pspec, None))(params_sharded, tok)
        import numpy as np
        assert np.isfinite(float(loss)), loss
        print("LOSS_OK", float(loss))
    """)
    assert "all-reduce" in out or "reduce-scatter" in out
    assert "all-gather" in out  # ZeRO-3 gathers inside the scan
    assert "LOSS_OK" in out


def test_sharded_matches_single_device():
    """DP+TP sharded loss == single-device loss (same params, same batch)."""
    out = run_prog("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import init_params, forward_train
        from repro.distributed import ShardingRules
        from repro.launch.mesh import make_test_mesh

        cfg = get_config("llama3.2-3b").reduced(
            d_model=64, d_ff=128, vocab_size=256, n_layers=2, n_heads=4,
            n_kv_heads=2, d_head=16)
        params = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, 256)

        def loss_fn(p, t):
            logits, _ = forward_train(p, {"tokens": t}, cfg)
            lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
            return -jnp.mean(jnp.take_along_axis(lp, t[:, 1:, None], -1))

        ref = float(jax.jit(loss_fn)(params, tokens))

        mesh = make_test_mesh(dp=2, tp=4)
        rules = ShardingRules(mesh, zero3=True)
        pspec = rules.params(params)
        tok_sh = NamedSharding(mesh, P("data", None))
        with mesh:
            got = float(jax.jit(loss_fn, in_shardings=(pspec, tok_sh))(
                jax.device_put(params, pspec), jax.device_put(tokens, tok_sh)))
        print("REF", ref, "GOT", got)
        assert abs(ref - got) < 1e-5 * max(1.0, abs(ref)), (ref, got)
        print("MATCH_OK")
    """)
    assert "MATCH_OK" in out


# ---------------------------------------------------------------------------
# pipeline parallelism (4 stages)
# ---------------------------------------------------------------------------

def test_pipeline_matches_sequential():
    out = run_prog("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed import pipeline_apply, bubble_fraction

        mesh = jax.make_mesh((4,), ("stage",))
        S, M, MB, D = 4, 8, 2, 16
        key = jax.random.key(0)
        params = {"w": jax.random.normal(key, (S, D, D)) * 0.3,
                  "b": jax.random.normal(jax.random.key(1), (S, D)) * 0.1}
        x = jax.random.normal(jax.random.key(2), (M, MB, D))

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        # sequential reference
        ref = x
        for s in range(S):
            ref = stage_fn({"w": params["w"][s], "b": params["b"][s]}, ref)

        piped = pipeline_apply(stage_fn, mesh)
        got = piped(params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        assert abs(bubble_fraction(4, 8) - 3/11) < 1e-9
        print("PIPE_OK")
    """, devices=4)
    assert "PIPE_OK" in out


# ---------------------------------------------------------------------------
# fp8-compressed gradient all-reduce
# ---------------------------------------------------------------------------

def test_compressed_psum_close_to_exact():
    out = run_prog("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import compressed_psum
        from jax import shard_map
        from repro.distributed.compression import comm_bytes

        mesh = jax.make_mesh((8,), ("data",))
        x = jax.random.normal(jax.random.key(0), (8, 4, 333))

        f_exact = shard_map(lambda a: jax.lax.psum(a[0], "data"),
                            mesh=mesh, in_specs=P("data"), out_specs=P(),
                            check_vma=False)
        f_comp = shard_map(lambda a: compressed_psum(a[0], "data"),
                           mesh=mesh, in_specs=P("data"), out_specs=P(),
                           check_vma=False)
        exact = np.asarray(f_exact(x))
        comp = np.asarray(f_comp(x))
        rel = np.abs(comp - exact).mean() / (np.abs(exact).mean() + 1e-9)
        assert rel < 0.03, rel
        assert comm_bytes(10**6, 8, True) < 0.6 * comm_bytes(10**6, 8, False)
        print("COMP_OK", rel)
    """)
    assert "COMP_OK" in out
