"""The paged KV pool on a mesh: its placement rule
(``repro.models.paged.paged_cache_shardings``, which
``ShardingRules.paged_cache_shardings`` hands on) and an ``Engine`` over a
``(1, 4)`` mesh of four CPU devices.  The engine runs in a child process
with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (this
process keeps the one CPU device the other tests see)."""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.launch.shardings import ShardingRules
from repro.models.paged import heads_spec, init_paged_caches
from repro.models.pjit_utils import AxisEnv

ROOT = Path(__file__).resolve().parents[1]


def _rules(kv_heads: int, model: int = 4) -> tuple:
    cfg = dataclasses.replace(get_smoke_config("mistral_large_123b"),
                              num_heads=8, num_kv_heads=kv_heads)
    mesh = AbstractMesh((1, model), ("data", "model"))
    return ShardingRules(AxisEnv(mesh=mesh, batch_axes=("data",)), cfg), cfg


@pytest.mark.parametrize("kv_qdtype", [None, "int8", "fp8"])
def test_pool_rule_puts_heads_on_model_and_scales_with_their_pool(
        kv_qdtype):
    rules, cfg = _rules(kv_heads=4)
    caches = jax.eval_shape(lambda: init_paged_caches(
        cfg, 9, 8, 4, kv_qdtype=kv_qdtype))
    specs = {k: s.spec for k, s in
             rules.paged_cache_shardings(caches)[0]["slot0"].items()}
    # (count, repeat, blocks, positions, Hkv, D): blocks and positions whole
    heads = P(None, None, None, None, "model")
    want = {"k": heads, "v": heads}
    if kv_qdtype is not None:
        want.update(k_scale=heads, v_scale=heads)
    assert specs == want


def test_pool_rule_replicates_heads_that_do_not_divide_model():
    rules, cfg = _rules(kv_heads=2)
    caches = jax.eval_shape(lambda: init_paged_caches(
        cfg, 9, 8, 4, kv_qdtype="int8"))
    specs = rules.paged_cache_shardings(caches)[0]["slot0"]
    assert all(s.spec == P() for s in specs.values())


@pytest.mark.parametrize("shape,heads,batch,want", [
    ((4, 3, 8, 16), 2, 0, P("data", None, "model")),
    ((3, 3, 8, 16), 2, 0, P(None, None, "model")),     # 3 slots over 2
    ((4, 3, 6, 16), 2, 0, P("data")),                   # 6 heads over 4
    ((4, 3, 8, 16), None, None, P()),
])
def test_heads_spec_places_whole_heads_and_slots(shape, heads, batch, want):
    env = AxisEnv(mesh=AbstractMesh((2, 4), ("data", "model")),
                  batch_axes=("data",))
    assert heads_spec(env, shape, heads, batch) == want


CHILD = r"""
import dataclasses, json

import jax
import jax.monitoring as mon
import numpy as np

from repro import serving
from repro.configs import get_smoke_config
from repro.models import init_params, paged_decode_step, paged_prefill_chunk

# Mistral-Large's head layout at a tiny width: 8 query heads on 4 KV heads
cfg0 = dataclasses.replace(get_smoke_config("mistral_large_123b"),
                           num_heads=8, num_kv_heads=4)


def engine(mesh):
    spec = serving.ServingSpec(layout="compressed", sparsity=(2, 4),
                               mesh=mesh, backend="jnp", slots=4, max_len=64,
                               block_len=8, prefill_chunk=16)
    cfg = spec.apply_to(cfg0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return serving.Engine(serving.prepare(params, spec, cfg=cfg))


def logits(eng):
    # one prefill chunk, then one decode step over every slot
    s = eng.spec
    rng = np.random.default_rng(0)
    table = np.arange(1, 1 + 4 * s.table_width, dtype=np.int32).reshape(
        4, s.table_width)
    prompt = rng.integers(0, cfg0.vocab_size, (1, 16), dtype=np.int32)
    with eng.prepared.activate():
        tok, tab = eng._feed(prompt, table[:1])
        pre, caches = paged_prefill_chunk(
            eng.prepared.params, eng._fresh_caches(), tok, np.int32(0), tab,
            np.int32(16), np.int32(0), eng.cfg, s.block_len, None)
        pos = np.array([16, 0, 0, 0], np.int32)
        dec, _ = paged_decode_step(
            eng.prepared.params, caches, *eng._feed(
                prompt[:, -4:].T, pos, table,
                np.array([True, False, False, False])),
            eng.cfg, s.block_len, None)
    return np.asarray(pre, np.float32), np.asarray(dec[:1], np.float32)


compiles = [0]


def on(event, duration, **_):
    if event in ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/backend_compile_duration"):
        compiles[0] += 1


mon.register_event_duration_secs_listener(on)
reqs = serving.make_poisson_trace(seed=0, num_requests=8, rate=1.0,
                                  vocab_size=cfg0.vocab_size)
placed, one = engine((1, 4)), engine(None)
placed.run(reqs)
compiles[0] = 0
again = placed.run(reqs)
n = compiles[0]
pool = jax.tree.leaves(placed._fresh_caches())[0]
(p_pre, p_dec), (o_pre, o_dec) = logits(placed), logits(one)
print(json.dumps({
    "pool_spec": [str(a) for a in pool.sharding.spec],
    "pool_devices": len(pool.sharding.device_set),
    "compiles_second_run": n,
    "tokens_match": [s.tokens for s in again.stats]
                    == [s.tokens for s in one.run(reqs).stats],
    "prefill_gap": float(np.abs(p_pre - o_pre).max()),
    "decode_gap": float(np.abs(p_dec - o_dec).max()),
    "scale": float(np.abs(o_pre).max())}))
"""


@pytest.fixture(scope="module")
def on_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", CHILD], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_engine_pool_is_born_with_kv_heads_on_model(on_mesh):
    # (count, repeat, blocks, positions, Hkv, D)
    assert on_mesh["pool_spec"] == ["None"] * 4 + ["model"]
    assert on_mesh["pool_devices"] == 4


def test_second_run_compiles_nothing(on_mesh):
    assert on_mesh["compiles_second_run"] == 0


def test_served_logits_match_the_engine_off_mesh(on_mesh):
    # the same programs but for the order of the all-reduced partial sums
    tol = 2e-2 * on_mesh["scale"]
    assert on_mesh["prefill_gap"] <= tol, on_mesh
    assert on_mesh["decode_gap"] <= tol, on_mesh
    assert on_mesh["tokens_match"]
