"""Multi-device distribution tests, run in SUBPROCESSES with a small
forced device count (the main pytest process must keep 1 device)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return r.stdout


@pytest.mark.slow
def test_small_mesh_train_step_shards_and_matches_single_device():
    """pjit'd train step on a 2x4 mesh == single-device step (same math)."""
    out = _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_axis_env, make_mesh
        from repro.launch.shardings import ShardingRules
        from repro.models import make_train_step
        from repro.models.lm import init_train_state
        from repro.models.pjit_utils import use_axis_env

        cfg = get_smoke_config("internlm2_1_8b")
        mesh = make_mesh((2, 4), ("data", "model"))
        env = make_axis_env(mesh)
        rules = ShardingRules(env, cfg)
        params, opt = init_train_state(jax.random.PRNGKey(0), cfg)
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size),
        }
        step = make_train_step(cfg, lr=1e-3)
        # single device reference
        _, _, loss_ref = jax.jit(step)(params, opt, batch, jnp.int32(0))
        # sharded
        with use_axis_env(env):
            psh = rules.tree_shardings(params)
            osh = rules.tree_shardings(opt)
            bsh = rules.batch_spec(batch, 4)
            f = jax.jit(step, in_shardings=(psh, osh, bsh, NamedSharding(mesh, P())))
            p2, o2, loss = f(params, opt, batch, jnp.int32(0))
        err = abs(float(loss) - float(loss_ref))
        assert err < 5e-2, (float(loss), float(loss_ref))
        # params actually sharded
        some = p2["stages"][0]["slot0"]["ffn"]["w_in"]["w"]
        assert len(some.sharding.device_set) > 1
        print("OK", float(loss), float(loss_ref))
    """))
    assert "OK" in out


@pytest.mark.slow
def test_small_mesh_moe_shardmap():
    """Expert-parallel MoE under shard_map == local-loop MoE semantics."""
    out = _run(textwrap.dedent("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_axis_env, make_mesh
        from repro.models.moe import apply_moe, init_moe
        from repro.models.pjit_utils import use_axis_env

        cfg = get_smoke_config("qwen3_moe_235b_a22b")
        cfg = dataclasses.replace(cfg, moe_capacity_factor=16.0)
        mesh = make_mesh((2, 4), ("data", "model"))
        env = make_axis_env(mesh)
        p = init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                              dtype=jnp.float32).astype(cfg.jnp_dtype)
        y_local = apply_moe(p, x, cfg)             # no env: local path
        with use_axis_env(env):
            y_dist = jax.jit(lambda p, x: apply_moe(p, x, cfg))(p, x)
        a = np.asarray(y_local, np.float32); b = np.asarray(y_dist, np.float32)
        rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-6)
        assert rel < 0.05, rel
        print("OK", rel)
    """))
    assert "OK" in out


@pytest.mark.slow
def test_sharded_dispatch_parity_subprocess():
    """Kernel-vs-jnp parity with a mesh installed: the shard_map dispatch
    class (single-device lanes get this via subprocess; the full matrix
    lives in test_sharded_dispatch.py under forced host devices)."""
    out = _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import SparsityConfig, apply_linear, init_linear
        from repro.kernels import dispatch
        from repro.launch.mesh import make_axis_env, make_mesh
        from repro.models.pjit_utils import use_axis_env

        mesh = make_mesh((2, 4), ("data", "model"))
        env = make_axis_env(mesh)
        for mode, n, hint in [("dense", 4, "col"), ("compressed", 2, "row"),
                              ("compressed", 1, "col"), ("gather", 2, "row")]:
            cfg = SparsityConfig(n=n, m=4, mode=mode)
            p = init_linear(jax.random.PRNGKey(0), 256, 128, cfg, jnp.float32)
            x = jax.random.normal(jax.random.PRNGKey(1), (32, 256))
            with use_axis_env(env):
                with dispatch.use_dispatch(backend="jnp"):
                    y_ref = apply_linear(p, x, cfg, gather=hint)
                with dispatch.use_dispatch(backend="interpret"):
                    y_k = apply_linear(p, x, cfg, gather=hint)
                shard = dispatch.shard_spec_from_env(hint)
                d = dispatch.plan_for(p, (32, 256), cfg, dtype=jnp.float32,
                    dispatch=dispatch.DispatchConfig(backend="interpret"),
                    shard=shard)
            assert d.placement == "shard_map", (mode, n, hint, d)
            a, b = np.asarray(y_k, np.float32), np.asarray(y_ref, np.float32)
            err = np.abs(a - b).max() / (np.abs(b).max() + 1e-6)
            assert err < 1e-5, (mode, n, hint, err)
        print("OK")
    """))
    assert "OK" in out


@pytest.mark.slow
def test_hlo_cost_flops_vs_analytic():
    """While-aware HLO cost ~ 6*N*D for a dense train step (<= 60% over)."""
    out = _run(textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_axis_env, make_mesh
        from repro.launch.shardings import ShardingRules
        from repro.launch.hlo_cost import analyze
        from repro.models import make_train_step
        from repro.models.lm import init_train_state
        from repro.models.pjit_utils import use_axis_env
        import dataclasses

        cfg = get_smoke_config("internlm2_1_8b")
        cfg = dataclasses.replace(cfg, num_layers=4, d_model=128, d_ff=512,
                                  num_heads=4, num_kv_heads=4, head_dim=32,
                                  vocab_size=512)
        mesh = make_mesh((2, 4), ("data", "model"))
        env = make_axis_env(mesh)
        rules = ShardingRules(env, cfg)
        params, opt = init_train_state(jax.random.PRNGKey(0), cfg)
        b, t = 8, 256
        batch = {"tokens": jax.ShapeDtypeStruct((b, t), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((b, t), jnp.int32)}
        step = make_train_step(cfg)
        with use_axis_env(env):
            f = jax.jit(step, in_shardings=(
                rules.tree_shardings(params), rules.tree_shardings(opt),
                rules.batch_spec(batch, b), NamedSharding(mesh, P())))
            lowered = f.lower(
                jax.eval_shape(lambda: params), jax.eval_shape(lambda: opt),
                batch, jax.ShapeDtypeStruct((), jnp.int32))
        cost = analyze(lowered.compile().as_text(), 8)
        n_params = cfg.param_count()
        analytic = 6 * n_params * b * t / 8
        ratio = cost["flops"] / analytic
        assert 0.9 < ratio < 2.5, ratio
        print("OK ratio", ratio)
    """))
    assert "OK" in out
