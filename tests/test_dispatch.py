"""Dispatch-engine tests: kernel-vs-jnp parity through the public API,
registry fallback selection, autodiff/sharding guards, and the autotune
cache round-trip (memory -> JSON -> memory)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SparsityConfig, apply_linear, convert_layout, init_linear
from repro.kernels import autotune, dispatch, registry


def _allclose(got, want, atol=1e-5):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = np.abs(want).max() + 1e-6
    np.testing.assert_allclose(got / scale, want / scale, atol=atol)


# ---------------------------------------------------------------------------
# kernel-vs-jnp parity through apply_linear (the acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 4])
def test_compressed_parity_kernel_vs_jnp(n):
    cfg = SparsityConfig(n=n, m=4, mode="compressed")
    p = init_linear(jax.random.PRNGKey(0), 128, 64, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 128))
    with dispatch.use_dispatch(backend="jnp"):
        y_ref = apply_linear(p, x, cfg)
    with dispatch.use_dispatch(backend="interpret"):
        y_k = apply_linear(p, x, cfg)
    _allclose(y_k, y_ref)


@pytest.mark.parametrize("n", [1, 2, 4])
def test_gather_parity_kernel_vs_jnp(n):
    cfg = SparsityConfig(n=n, m=4, mode="gather")
    p = init_linear(jax.random.PRNGKey(0), 128, 64, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 128))
    with dispatch.use_dispatch(backend="jnp"):
        y_ref = apply_linear(p, x, cfg)
    with dispatch.use_dispatch(backend="interpret"):
        y_k = apply_linear(p, x, cfg)
    _allclose(y_k, y_ref)


def test_dense_parity_kernel_vs_jnp():
    cfg = SparsityConfig(mode="dense")
    p = init_linear(jax.random.PRNGKey(0), 128, 64, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 128))
    with dispatch.use_dispatch(backend="interpret"):
        y_k = apply_linear(p, x, cfg)
    _allclose(y_k, x @ p["w"])


def test_converted_serving_parity_3d_batch():
    """masked-trained -> compressed serving layout, 3-D activations, jit."""
    cfg_m = SparsityConfig(n=2, m=4, mode="masked")
    p = init_linear(jax.random.PRNGKey(0), 64, 32, cfg_m, dtype=jnp.float32)
    cfg_c = SparsityConfig(n=2, m=4, mode="compressed")
    pc = convert_layout(p, cfg_c, "compressed")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 64))
    with dispatch.use_dispatch(backend="jnp"):
        y_ref = apply_linear(pc, x, cfg_c)
    with dispatch.use_dispatch(backend="interpret"):
        y_k = jax.jit(lambda p, x: apply_linear(p, x, cfg_c))(pc, x)
    assert y_k.shape == (2, 3, 32)
    _allclose(y_k, y_ref)


def test_compressed_routes_through_pallas_kernel(monkeypatch):
    """The engine must actually invoke nm_spmm, not just plan to."""
    import repro.kernels.nm_spmm.kernel as nm_kernel

    calls = []
    real = nm_kernel.nm_spmm

    def spy(*args, **kwargs):
        calls.append(kwargs.get("interpret"))
        return real(*args, **kwargs)

    monkeypatch.setattr(nm_kernel, "nm_spmm", spy)
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p = init_linear(jax.random.PRNGKey(0), 64, 32, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    with dispatch.use_dispatch(backend="interpret"):
        apply_linear(p, x, cfg)
    assert calls == [True]
    calls.clear()
    with dispatch.use_dispatch(backend="jnp"):
        apply_linear(p, x, cfg)
    assert calls == []


# ---------------------------------------------------------------------------
# registry selection + fallback tiers
# ---------------------------------------------------------------------------

def test_registry_selects_expected_kernels():
    for mode, name in [("dense", "tile_gemm"), ("compressed", "nm_spmm"),
                       ("gather", "nm_spmm_gather")]:
        sel = registry.select(mode, b=16, ke=128, o=64, n=2, m=4,
                              dtype=jnp.float32, backend="interpret")
        assert sel is not None and sel[0].name == name


def test_registry_fallback_on_unfittable_shape():
    # ke=100 has no divisor that is a multiple of 16 (required for 1:4
    # meta packing) -> no kernel fits -> engine plans the jnp reference
    assert registry.select("compressed", b=4, ke=100, o=32, n=1, m=4,
                           dtype=jnp.float32, backend="interpret") is None
    d = dispatch.plan(
        dispatch.GemmProblem("compressed", b=4, ke=100, o=32, n=1, m=4,
                             dtype=jnp.float32),
        dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert not d.uses_kernel and "no registered kernel" in d.reason


def test_masked_and_jnp_backend_always_reference():
    d = dispatch.plan(
        dispatch.GemmProblem("masked", b=16, ke=128, o=64, n=2, m=4,
                             dtype=jnp.float32),
        dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert not d.uses_kernel
    d = dispatch.plan(
        dispatch.GemmProblem("compressed", b=16, ke=128, o=64, n=2, m=4,
                             dtype=jnp.float32),
        dispatch=dispatch.DispatchConfig(backend="jnp"))
    assert not d.uses_kernel


def test_autodiff_falls_back_to_jnp():
    """grad w.r.t. compressed values works even with kernels forced on."""
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p = init_linear(jax.random.PRNGKey(0), 64, 32, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))

    def loss(v):
        params = {"values": v, "meta_packed": p["meta_packed"]}
        return jnp.sum(apply_linear(params, x, cfg) ** 2)

    with dispatch.use_dispatch(backend="interpret"):
        g = jax.grad(loss)(p["values"])
    assert g.shape == p["values"].shape
    assert bool(jnp.any(g != 0))


@pytest.mark.parametrize("wrap", ["grad_of_jit", "jit_of_grad"])
def test_kernel_backward_is_the_reference_vjp(wrap):
    """The kernel call's backward pass is the jnp reference's VJP however
    grad and jit nest — tracer inspection cannot see a grad taken
    outside a jit, the custom_vjp at the kernel boundary can."""
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p = init_linear(jax.random.PRNGKey(0), 64, 32, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))

    def loss(v, xx):
        params = {"values": v, "meta_packed": p["meta_packed"]}
        return jnp.sum(apply_linear(params, xx, cfg) ** 2)

    grad = jax.grad(loss, argnums=(0, 1))
    fn = jax.grad(jax.jit(loss), argnums=(0, 1)) if wrap == "grad_of_jit" \
        else jax.jit(grad)
    with dispatch.use_dispatch(backend="jnp"):
        want = grad(p["values"], x)
    with dispatch.use_dispatch(backend="interpret"):
        got = fn(p["values"], x)
    for g, w in zip(got, want):
        _allclose(g, w)


def test_env_var_backend_override(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert registry.detect_backend() == "interpret"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "jnp")
    assert registry.detect_backend() == "jnp"


def test_block_fitting_helper():
    assert registry.largest_fitting_block(512, 128) == 128
    assert registry.largest_fitting_block(192, 128) == 96
    assert registry.largest_fitting_block(100, 512, 16) is None
    assert registry.largest_fitting_block(64, 512, 16) == 64


# ---------------------------------------------------------------------------
# autotune cache round-trip
# ---------------------------------------------------------------------------

def test_autotune_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    key = autotune.cache_key("nm_spmm", 16, 128, 64, 2, 4, jnp.float32)
    calls = []

    def runner(blocks):
        calls.append(blocks)
        return jnp.zeros(())

    cands = [(16, 128, 64), (8, 64, 64)]
    best = autotune.tune(runner, cands, backend="interpret", key=key)
    assert best in [tuple(c) for c in cands]
    assert len(calls) >= len(cands)          # every candidate timed

    # second tune: served from the in-process cache, runner untouched
    calls.clear()
    assert autotune.tune(runner, cands, backend="interpret", key=key) == best
    assert calls == []

    # drop the memory layer: must reload from the JSON store.  The store
    # file is keyed by device kind so interpret entries tuned under CPU
    # emulation can never be served to a Mosaic run.
    autotune.clear_memory_cache()
    assert autotune.lookup("interpret", key) == best
    assert autotune.store_path("interpret") == str(
        tmp_path / "cpu-interpret.json")
    assert (tmp_path / "cpu-interpret.json").exists()
    assert not (tmp_path / "interpret.json").exists()
    autotune.clear_memory_cache()


def test_autotune_stats_counts_hits_and_misses(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    autotune.reset_stats()
    key = autotune.cache_key("nm_spmm", 4, 64, 32, 2, 4, jnp.float32)
    assert autotune.lookup("interpret", key) is None
    autotune.record("interpret", key, (4, 64, 32), persist=False)
    assert autotune.lookup("interpret", key) == (4, 64, 32)
    s = autotune.stats()
    assert s["misses"] == 1 and s["hits"] == 1
    autotune.reset_stats()
    autotune.clear_memory_cache()


# ---------------------------------------------------------------------------
# flash attention folded into the registry/dispatch engine
# ---------------------------------------------------------------------------

def test_attention_registry_entry_and_plan():
    sel = registry.select("attention", b=256, ke=256, o=64, n=4, m=4,
                          dtype=jnp.bfloat16, backend="interpret")
    assert sel is not None and sel[0].name == "flash_attention"
    d = dispatch.plan(
        dispatch.GemmProblem("attention", b=256, ke=256, o=64, n=4, m=4,
                             dtype=jnp.bfloat16),
        dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert d.uses_kernel and d.kernel == "flash_attention"
    # odd head_dim fails the lane constraint -> jnp reason in plan
    d = dispatch.plan(
        dispatch.GemmProblem("attention", b=256, ke=256, o=63, n=4, m=4,
                             dtype=jnp.bfloat16),
        dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert not d.uses_kernel and "no registered kernel" in d.reason


@pytest.mark.parametrize("causal", [True, False])
def test_attention_dispatch_parity_kernel_vs_chunked(causal):
    from repro.models.attention import chunked_attention

    b, hkv, g, t, d = 1, 2, 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    qg = jax.random.normal(ks[0], (b, hkv, g, t, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
    want = chunked_attention(qg, k, v, causal, 64, 0, False, False)
    with dispatch.use_dispatch(backend="interpret"):
        got = dispatch.attention(qg, k, v, causal=causal, chunk=64)
    _allclose(got, want, atol=2e-5)


def test_attention_dispatch_falls_back_under_autodiff():
    """grad through the engine's attention uses the chunked custom VJP."""
    b, hkv, g, t, d = 1, 1, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    qg = jax.random.normal(ks[0], (b, hkv, g, t, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)

    def loss(qg):
        with dispatch.use_dispatch(backend="interpret"):
            return jnp.sum(dispatch.attention(qg, k, v, causal=True,
                                              chunk=32) ** 2)

    grad = jax.grad(loss)(qg)
    assert grad.shape == qg.shape and bool(jnp.any(grad != 0))


def test_attention_block_routes_through_flash_kernel(monkeypatch):
    """Model code no longer calls the flash kernel directly — the engine
    invokes it when a kernel backend is forced."""
    import repro.kernels.flash_attention.ops as fops
    from repro.models.attention import attention_block, init_attention
    from repro.models.config import ModelConfig

    calls = []
    real = fops.flash_attention_op

    def spy(*args, **kwargs):
        calls.append(kwargs.get("interpret"))
        return real(*args, **kwargs)

    monkeypatch.setattr(fops, "flash_attention_op", spy)
    cfg = ModelConfig(name="t", family="dense", vocab_size=64, d_model=64,
                      num_layers=1, num_heads=2, num_kv_heads=2, head_dim=32,
                      d_ff=128)
    p = init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 64),
                          jnp.float32).astype(cfg.jnp_dtype)
    with dispatch.use_dispatch(backend="interpret"):
        y = attention_block(p, x, cfg)
    assert y.shape == x.shape
    assert calls == [True]
    calls.clear()
    with dispatch.use_dispatch(backend="jnp"):
        attention_block(p, x, cfg)
    assert calls == []


def test_gather_hint_and_moe_expert_marker():
    """Expert stacks (router siblings) must plan hint-less — their real
    call sites sit inside the MoE's own shard_map body."""
    from repro.core.sparse_linear import gather_hint

    assert gather_hint(("attn", "wq")) == "col"
    assert gather_hint(("ffn", "w_out")) == "row"
    assert gather_hint(("moe", "experts", "w_in")) is None

    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p = init_linear(jax.random.PRNGKey(0), 64, 32, cfg, dtype=jnp.float32)
    stacked = jax.tree.map(lambda a: jnp.stack([a, a]), p)  # (E, ...) experts
    tree = {"moe": {"router": jnp.zeros((64, 2)), "w_in": stacked},
            "ffn": {"w_in": p}}
    hints = {names: gather_hint(names)
             for names, _ in dispatch.iter_linear_items(tree)}
    assert hints[("moe", "experts", "w_in")] is None
    assert hints[("ffn", "w_in")] == "col"


def test_mesh_probe_narrow_exception(monkeypatch):
    """_mesh_active must not swallow arbitrary errors from pjit_utils."""
    import builtins

    real_import = builtins.__import__

    def broken(name, *args, **kwargs):
        if name == "repro.models.pjit_utils":
            raise RuntimeError("real bug, must propagate")
        return real_import(name, *args, **kwargs)

    monkeypatch.setattr(builtins, "__import__", broken)
    monkeypatch.delitem(__import__("sys").modules, "repro.models.pjit_utils",
                        raising=False)
    with pytest.raises(RuntimeError):
        dispatch._mesh_active()


def test_pretune_walks_stacked_params(tmp_path, monkeypatch):
    """pretune must tune layer-stacked (scan-style) linears eagerly."""
    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p = init_linear(jax.random.PRNGKey(0), 64, 32, cfg, dtype=jnp.float32)
    stacked = {"layers": [{"proj": jax.tree.map(
        lambda a: jnp.stack([a, a]), p)}]}   # (2, ...) leading layer dim
    with dispatch.use_dispatch(backend="interpret"):
        n_tuned = dispatch.pretune(stacked, 4, cfg)
    assert n_tuned == 1
    key = autotune.cache_key("nm_spmm", 4, 64, 32, 2, 4, jnp.float32)
    assert autotune.lookup("interpret", key) is not None
    autotune.clear_memory_cache()


def test_autotuned_blocks_feed_dispatch(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p = init_linear(jax.random.PRNGKey(0), 64, 32, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    with dispatch.use_dispatch(backend="interpret"):
        y_ref = apply_linear(p, x, cfg)
    with dispatch.use_dispatch(backend="interpret", autotune=True):
        y_tuned = apply_linear(p, x, cfg)
    _allclose(y_tuned, y_ref)
    key = autotune.cache_key("nm_spmm", 8, 64, 32, 2, 4, jnp.float32)
    tuned = autotune.lookup("interpret", key)
    assert tuned is not None
    d = dispatch.plan(
        dispatch.GemmProblem("compressed", b=8, ke=64, o=32, n=2, m=4,
                             dtype=jnp.float32),
        dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert d.blocks == tuned and "autotuned" in d.reason
    autotune.clear_memory_cache()
