"""Mesh-aware dispatch: kernel-vs-jnp parity with an installed mesh env.

These tests need a multi-device CPU; run them with

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_sharded_dispatch.py

(the CI fast lane has a dedicated step).  Under a single-device pytest
process everything here skips — the subprocess test in
``test_dryrun_small.py``-style covers the default slow lane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SparsityConfig, apply_linear, init_linear
from repro.kernels import dispatch, registry

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


@pytest.fixture(scope="module")
def env():
    from repro.launch.mesh import make_axis_env, make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    return make_axis_env(mesh)


def _allclose(got, want, atol=1e-5):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = np.abs(want).max() + 1e-6
    np.testing.assert_allclose(got / scale, want / scale, atol=atol)


def _parity(env, cfg, gather, k=256, o=128, b=32, atol=1e-5):
    from repro.models.pjit_utils import use_axis_env

    p = init_linear(jax.random.PRNGKey(0), k, o, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, k))
    with use_axis_env(env):
        with dispatch.use_dispatch(backend="jnp"):
            y_ref = apply_linear(p, x, cfg, gather=gather)
        with dispatch.use_dispatch(backend="interpret"):
            y_k = apply_linear(p, x, cfg, gather=gather)
    _allclose(y_k, y_ref, atol=atol)
    return p


# ---------------------------------------------------------------------------
# plan(): the shard_map-vs-jnp decision matrix
# ---------------------------------------------------------------------------

def test_plan_shard_map_decisions(env):
    """Dense 4:4, Tier-1 2:4, and Tier-2 1:4 whose local shapes fit must
    plan shard_map (the acceptance criterion), with the right collective."""
    from repro.models.pjit_utils import use_axis_env

    dcfg = dispatch.DispatchConfig(backend="interpret")
    cases = [("dense", 4, "tile_gemm"), ("compressed", 2, "nm_spmm"),
             ("compressed", 1, "nm_spmm"), ("gather", 1, "nm_spmm_gather")]
    with use_axis_env(env):
        for mode, n, kernel in cases:
            for hint, coll in [("col", "none"), ("row", "psum")]:
                shard = dispatch.shard_spec_from_env(hint)
                d = dispatch.plan(
                    dispatch.GemmProblem(mode, b=32, ke=256, o=128, n=n, m=4,
                                         dtype=jnp.float32, sharded=True,
                                         shard=shard),
                    dispatch=dcfg)
                assert d.uses_shard_map and d.kernel == kernel, (mode, n, d)
                assert d.collective == coll
                assert d.shards == ((2, 1, 4) if hint == "col" else (2, 4, 1))
                assert d.local_dims == ((16, 256, 32) if hint == "col"
                                        else (16, 64, 128))
                assert "shard_map" in dispatch.describe(d)


def test_plan_jnp_reasons_under_mesh(env):
    from repro.models.pjit_utils import use_axis_env

    dcfg = dispatch.DispatchConfig(backend="interpret")
    with use_axis_env(env):
        # mesh active, no use-site spec -> jnp (the pre-refactor behavior)
        d = dispatch.plan(
            dispatch.GemmProblem("compressed", b=32, ke=256, o=128, n=2, m=4,
                                 dtype=jnp.float32, sharded=True),
            dispatch=dcfg)
        assert not d.uses_kernel and "no use-site shard spec" in d.reason
        # non-divisible out dim -> jnp with the shard-divide reason
        shard = dispatch.shard_spec_from_env("col")
        d = dispatch.plan(
            dispatch.GemmProblem("compressed", b=32, ke=256, o=129, n=2, m=4,
                                 dtype=jnp.float32, shard=shard),
            dispatch=dcfg)
        assert not d.uses_kernel and "does not divide" in d.reason
        # ke slice that splits packed N:M metadata -> dedicated reason:
        # ke=16, n=1: values rows 4, meta rows 1 — not splittable 4-ways
        shard = dispatch.shard_spec_from_env("row")
        d = dispatch.plan(
            dispatch.GemmProblem("compressed", b=32, ke=16, o=128, n=1, m=4,
                                 dtype=jnp.float32, shard=shard),
            dispatch=dcfg)
        assert not d.uses_kernel and "metadata axis" in d.reason
        # batch not divisible by the data axis -> jnp
        shard = dispatch.shard_spec_from_env("col")
        d = dispatch.plan(
            dispatch.GemmProblem("compressed", b=3, ke=256, o=128, n=2, m=4,
                                 dtype=jnp.float32, shard=shard),
            dispatch=dcfg)
        assert not d.uses_kernel and "does not divide" in d.reason
        # masked and autodiff guards outrank the shard path
        d = dispatch.plan(
            dispatch.GemmProblem("masked", b=32, ke=256, o=128, n=2, m=4,
                                 dtype=jnp.float32, shard=shard),
            dispatch=dcfg)
        assert not d.uses_kernel
        d = dispatch.plan(
            dispatch.GemmProblem("compressed", b=32, ke=256, o=128, n=2, m=4,
                                 dtype=jnp.float32, shard=shard,
                                 differentiating=True),
            dispatch=dcfg)
        assert not d.uses_kernel and "autodiff" in d.reason


def test_registry_select_fits_local_shards():
    sel = registry.select("compressed", b=32, ke=256, o=128, n=2, m=4,
                          dtype=jnp.float32, backend="interpret",
                          shards=(2, 4, 1))
    assert sel is not None
    _, blocks = sel
    assert blocks[1] <= 64  # fitted against ke_local = 256/4
    assert registry.select("compressed", b=32, ke=256, o=128, n=2, m=4,
                           dtype=jnp.float32, backend="interpret",
                           shards=(2, 3, 1)) is None
    assert registry.local_dims((32, 256, 128), (2, 4, 1)) == (16, 64, 128)
    assert registry.local_dims((32, 250, 128), (2, 4, 1)) is None


# ---------------------------------------------------------------------------
# kernel-vs-jnp parity with the mesh installed (TP / FSDP / mixed)
# ---------------------------------------------------------------------------

def test_parity_tp_col_fast(env):
    _parity(env, SparsityConfig(n=2, m=4, mode="compressed"), "col")


def test_parity_tp_row_fast(env):
    _parity(env, SparsityConfig(n=2, m=4, mode="compressed"), "row")


def test_parity_dense_and_gather_fast(env):
    _parity(env, SparsityConfig(mode="dense"), "col")
    _parity(env, SparsityConfig(n=1, m=4, mode="gather"), "row")


def test_parity_masked_stays_reference_under_mesh(env):
    # masked (SR-STE train path) must stay on the jnp reference but still
    # produce identical results whichever backend is requested
    _parity(env, SparsityConfig(n=2, m=4, mode="masked"), "col")


@pytest.mark.slow
@pytest.mark.parametrize("mode,n", [
    ("dense", 4),
    ("compressed", 1), ("compressed", 2), ("compressed", 4),
    ("gather", 1), ("gather", 2), ("gather", 4),
    ("masked", 1), ("masked", 2), ("masked", 4),
])
@pytest.mark.parametrize("gather", ["col", "row", None])
def test_parity_full_matrix(env, mode, n, gather):
    """TP- (col/row) and FSDP-style (hint None -> jnp fallback) sharded
    linears, all modes, n in {1, 2, 4}."""
    cfg = SparsityConfig(n=n, m=4, mode=mode)
    _parity(env, cfg, gather)


def test_shard_map_actually_runs_kernel(env, monkeypatch):
    """The mesh path must invoke the Pallas kernel body, not just plan it."""
    import repro.kernels.nm_spmm.kernel as nm_kernel
    from repro.models.pjit_utils import use_axis_env

    calls = []
    real = nm_kernel.nm_spmm

    def spy(*args, **kwargs):
        calls.append(kwargs.get("interpret"))
        return real(*args, **kwargs)

    monkeypatch.setattr(nm_kernel, "nm_spmm", spy)
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p = init_linear(jax.random.PRNGKey(0), 256, 128, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 256))
    with use_axis_env(env):
        with dispatch.use_dispatch(backend="interpret"):
            apply_linear(p, x, cfg, gather="col")
    assert calls == [True]


def test_sharded_parity_under_jit(env):
    """The decode/serving path traces sparse_matmul under jit with the
    mesh env installed — shard_map must compose with tracing."""
    from repro.models.pjit_utils import use_axis_env

    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p = init_linear(jax.random.PRNGKey(0), 256, 128, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 256))
    with use_axis_env(env):
        with dispatch.use_dispatch(backend="jnp"):
            y_ref = apply_linear(p, x, cfg, gather="row")
        with dispatch.use_dispatch(backend="interpret"):
            y_k = jax.jit(
                lambda p, x: apply_linear(p, x, cfg, gather="row"))(p, x)
    assert y_k.shape == (4, 8, 128)
    _allclose(y_k, y_ref)


# ---------------------------------------------------------------------------
# rowwise serving mode end-to-end (per-tier dispatch under the mesh)
# ---------------------------------------------------------------------------

def test_rowwise_apply_linear_parity_under_mesh(env):
    from repro.models.pjit_utils import use_axis_env

    rng = np.random.default_rng(0)
    k, o = 256, 96
    w = rng.normal(size=(k, o)) * (rng.random((k, o)) < 0.2)
    w = jnp.asarray(w, jnp.float32)
    from repro.core.sparse_linear import convert_layout

    cfg = SparsityConfig(n=2, m=4, mode="rowwise")
    p = convert_layout({"w": w}, cfg, "rowwise")
    x = jax.random.normal(jax.random.PRNGKey(1), (32, k))
    want = x @ w
    with use_axis_env(env):
        with dispatch.use_dispatch(backend="interpret"):
            got = apply_linear(p, x, cfg, gather="row")
    _allclose(got, want, atol=1e-5)


def test_pretune_tunes_local_shard_problems(env, tmp_path, monkeypatch):
    from repro.kernels import autotune
    from repro.models.pjit_utils import use_axis_env

    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p = init_linear(jax.random.PRNGKey(0), 256, 128, cfg, dtype=jnp.float32)
    tree = {"attn": {"wq": p}}
    with use_axis_env(env):
        with dispatch.use_dispatch(backend="interpret"):
            n_tuned = dispatch.pretune(tree, 32, cfg)
    assert n_tuned == 1
    # cache key is the per-shard local problem (col: o 128/4, b 32/2)
    key = autotune.cache_key("nm_spmm", 16, 256, 32, 2, 4, jnp.float32)
    assert autotune.lookup("interpret", key) is not None
    autotune.clear_memory_cache()


