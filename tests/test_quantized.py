"""Int8 quantized execution class: storage round-trip bounds, kernel
parity vs fp32 within quantization tolerance for every family and N,
dtype-aware registry selection, dtype-distinct autotune keys, and the
dequantize-reference fallbacks (autodiff, shard specs, unfittable tiles).
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SparsityConfig, apply_linear, convert_layout, nm
from repro.core import quantize as q
from repro.kernels import autotune, dispatch, registry


def _norm_close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = np.abs(want).max() + 1e-6
    np.testing.assert_allclose(got / scale, want / scale, atol=tol)


def _w(k=128, o=64, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (k, o), jnp.float32)


def _family_params(family, w, n):
    """Serving-layout params for one kernel family at sparsity n:4.

    Built by hand (not via convert_layout) so n=4 genuinely
    exercises the compressed and gather layouts instead of degenerating
    to dense.
    """
    if family == "dense":
        return {"w": w}
    if family == "compressed":
        pruned, _ = nm.prune_nm(w, n, 4)
        c = nm.compress_nm(pruned, n, 4)
        return {"values": c.values, "meta_packed": nm.pack_meta(c.meta)}
    if family == "gather":
        k = w.shape[0]
        kc = k * n // 4
        base = jnp.arange(kc, dtype=jnp.int32) % 4
        idx = jnp.sort(base.reshape(-1, n), axis=1).reshape(kc)
        blk = (jnp.arange(kc, dtype=jnp.int32) // n) * 4
        return {"values": w[blk + idx, :], "gather_idx": idx}
    raise ValueError(family)


# ---------------------------------------------------------------------------
# storage: quantize -> dequantize round-trip bounds
# ---------------------------------------------------------------------------

def test_roundtrip_error_bound_per_channel():
    """Per-channel absolute error <= 1/127 of the channel absmax."""
    w = _w(256, 96)
    qv, scale = q.quantize_per_channel(w)
    assert qv.dtype == jnp.int8 and scale.shape == (96,)
    err = np.abs(np.asarray(q.dequantize(qv, scale)) - np.asarray(w))
    bound = np.abs(np.asarray(w)).max(axis=0) / 127.0
    assert (err.max(axis=0) <= bound + 1e-7).all()


def test_quantize_rows_bound_and_zero_rows():
    x = jnp.concatenate([jax.random.normal(jax.random.PRNGKey(1), (7, 64)),
                         jnp.zeros((1, 64))])
    xq, xs = q.quantize_rows(x)
    assert xq.dtype == jnp.int8 and xs.shape == (8, 1)
    err = np.abs(np.asarray(xq, np.float32) * np.asarray(xs)
                 - np.asarray(x, np.float32))
    bound = np.abs(np.asarray(x)).max(axis=1) / 127.0
    assert (err.max(axis=1) <= bound + 1e-7).all()
    assert not np.isnan(np.asarray(xs)).any()


def test_convert_layout_quantizes_every_mode():
    w = _w()
    dense = convert_layout({"w": w}, SparsityConfig(mode="dense"),
                               "dense", quantize="int8")
    assert dense["w"].dtype == jnp.int8 and dense["scale"].shape == (64,)
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    comp = convert_layout({"w": w}, cfg, "compressed", quantize="int8")
    assert comp["values"].dtype == jnp.int8 and "meta_packed" in comp
    gath = convert_layout({"w": w}, SparsityConfig(n=2, m=4, mode="gather"),
                              "gather", quantize="int8")
    assert gath["values"].dtype == jnp.int8 and "gather_idx" in gath
    rw = convert_layout({"w": w}, cfg, "rowwise", quantize="int8")
    for seg in rw["rowwise"].values():
        assert seg["values"].dtype == jnp.int8 and "scale" in seg
    with pytest.raises(ValueError):
        convert_layout({"w": w}, cfg, "compressed", quantize="fp4")


def test_quantize_tree_touches_only_linear_leaves():
    w = _w(64, 32)
    tree = {
        "embed": jnp.zeros((100, 64)),
        "moe": {"router": jnp.zeros((64, 2)),
                "w_in": {"w": jnp.stack([w, w])}},   # stacked experts
        "norm": {"gamma": jnp.ones((64,))},
    }
    qt = q._quantize_tree(tree)
    assert qt["embed"].dtype == tree["embed"].dtype
    assert qt["moe"]["router"].dtype == tree["moe"]["router"].dtype
    assert qt["norm"]["gamma"].dtype == jnp.float32
    assert qt["moe"]["w_in"]["w"].dtype == jnp.int8
    assert qt["moe"]["w_in"]["scale"].shape == (2, 32)   # per-layer scales


def test_iter_linear_items_strips_stacked_scale():
    w = _w(64, 32)
    leaf = q.quantize_linear({"w": jnp.stack([w, w])})
    items = dict(dispatch.iter_linear_items({"ffn": {"w_in": leaf}}))
    got = items[("ffn", "w_in")]
    assert got["w"].shape == (64, 32) and got["scale"].shape == (32,)


# ---------------------------------------------------------------------------
# kernel parity: int8 registry entries vs fp32 reference, all families x N
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["dense", "compressed", "gather"])
@pytest.mark.parametrize("n", [1, 2, 4])
def test_int8_kernel_parity_vs_fp32(family, n):
    if family == "dense" and n != 4:
        pytest.skip("dense has no sparsity axis")
    cfg = SparsityConfig(n=n, m=4, mode=family)
    p_fp = _family_params(family, _w(), n)
    p_q = q.quantize_linear(p_fp)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 128))
    with dispatch.use_dispatch(backend="jnp"):
        y_fp = apply_linear(p_fp, x, cfg)
        y_qref = apply_linear(p_q, x, cfg)       # dequantize reference
    with dispatch.use_dispatch(backend="interpret"):
        y_qk = apply_linear(p_q, x, cfg)         # int8 registry kernel
    d = dispatch.plan_for(p_q, (32, 128), cfg, dtype=jnp.int8,
                          dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert d.uses_kernel and d.kernel.endswith("_int8"), dispatch.describe(d)
    # vs fp32: weight + activation quantization noise
    _norm_close(y_qk, y_fp, 5e-2)
    # vs the dequantize reference: only activation quantization differs
    _norm_close(y_qk, y_qref, 3e-2)


def test_int8_kernel_invoked_not_planned(monkeypatch):
    import repro.kernels.nm_spmm.kernel as nm_kernel

    calls = []
    real = nm_kernel.nm_spmm_int8

    def spy(*args, **kwargs):
        calls.append(kwargs.get("interpret"))
        return real(*args, **kwargs)

    monkeypatch.setattr(nm_kernel, "nm_spmm_int8", spy)
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_q = q.quantize_linear(_family_params("compressed", _w(64, 32), 2))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    with dispatch.use_dispatch(backend="interpret"):
        apply_linear(p_q, x, cfg)
    assert calls == [True]
    calls.clear()
    with dispatch.use_dispatch(backend="jnp"):
        apply_linear(p_q, x, cfg)
    assert calls == []


# ---------------------------------------------------------------------------
# registry: dtype is a selection axis with int8-specific tiling
# ---------------------------------------------------------------------------

def test_registry_dtype_axis_selection():
    for mode, name in [("dense", "tile_gemm_int8"),
                       ("compressed", "nm_spmm_int8"),
                       ("gather", "nm_spmm_gather_int8")]:
        sel = registry.select(mode, b=32, ke=128, o=64, n=2, m=4,
                              dtype=jnp.int8, backend="interpret")
        assert sel is not None and sel[0].name == name
        # float problems must never land on the int8 entries
        sel = registry.select(mode, b=32, ke=128, o=64, n=2, m=4,
                              dtype=jnp.float32, backend="interpret")
        assert sel is not None and not sel[0].name.endswith("_int8")


def test_int8_tiling_stricter_than_fp32():
    # ke=40: fp32 nm_spmm fits (block_ke=40 is a multiple of 8 for n=2)
    # but no divisor of 40 hits the int8 32-row sublane quantum
    assert registry.select("compressed", b=32, ke=40, o=64, n=2, m=4,
                           dtype=jnp.float32, backend="interpret") is not None
    assert registry.select("compressed", b=32, ke=40, o=64, n=2, m=4,
                           dtype=jnp.int8, backend="interpret") is None
    d = dispatch.plan(
        dispatch.GemmProblem("compressed", b=32, ke=40, o=64, n=2, m=4,
                             dtype=jnp.int8),
        dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert not d.uses_kernel and "no registered kernel" in d.reason


def test_plan_reason_uses_canonical_dtype_name():
    """The no-entry-fits reason prints 'float32'/'int8', never the raw
    ``<class 'jax.numpy.float32'>`` repr (stable reports + asserts)."""
    for dt, name in [(jnp.float32, "float32"), (jnp.int8, "int8")]:
        d = dispatch.plan(
            dispatch.GemmProblem("compressed", b=4, ke=100, o=32, n=1, m=4,
                                 dtype=dt),
            dispatch=dispatch.DispatchConfig(backend="interpret"))
        assert not d.uses_kernel
        assert name in d.reason and "<class" not in d.reason
    assert registry.dtype_name(jnp.float32) == "float32"
    assert registry.dtype_name(jnp.int8) == "int8"
    assert registry.dtype_name("bfloat16") == "bfloat16"


# ---------------------------------------------------------------------------
# fallbacks: autodiff, shard specs
# ---------------------------------------------------------------------------

def test_quantized_autodiff_falls_back_to_dequant_reference():
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_q = q.quantize_linear(_family_params("compressed", _w(64, 32), 2))

    def loss(x):
        return jnp.sum(apply_linear(p_q, x, cfg) ** 2)

    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    with dispatch.use_dispatch(backend="interpret"):
        g = jax.grad(loss)(x)
    assert g.shape == x.shape and bool(jnp.any(g != 0))


def test_quantized_shard_spec_plans_shard_map():
    """int8 is a first-class citizen of the shard_map execution class:
    a use-site shard spec routes the quantized problem through the int8
    registry kernel per-shard (psum of int32 partials on a sharded
    contraction), no longer the dequantize reference."""
    spec = dispatch.ShardSpec(
        mesh=types.SimpleNamespace(shape={"model": 2}), ke="model")
    d = dispatch.plan(
        dispatch.GemmProblem("compressed", b=32, ke=128, o=64, n=2, m=4,
                             dtype=jnp.int8, shard=spec),
        dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert d.uses_kernel and d.uses_shard_map, dispatch.describe(d)
    assert d.kernel == "nm_spmm_int8" and d.collective == "psum"
    assert d.act_scales == "dynamic"
    assert "act-scales=dynamic" in dispatch.describe(d)
    # the fp32 twin of the same problem keeps the shard_map class too
    d = dispatch.plan(
        dispatch.GemmProblem("compressed", b=32, ke=128, o=64, n=2, m=4,
                             dtype=jnp.float32, shard=spec),
        dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert d.uses_kernel and d.uses_shard_map and d.act_scales is None
    # a local contraction slice that misses the int8 sublane quantum
    # still declines to the reference: ke=48 slices the 2:4 metadata
    # cleanly (48 % 16 == 0) but the local ke=24 has no block hitting
    # the 64-multiple int8 quantum for n=2
    d = dispatch.plan(
        dispatch.GemmProblem("compressed", b=32, ke=48, o=64, n=2, m=4,
                             dtype=jnp.int8, shard=spec),
        dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert not d.uses_kernel and "no registered kernel" in d.reason


# ---------------------------------------------------------------------------
# odd row counts: final row block pads to the 32-row int8 sublane quantum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family,n", [("dense", 4), ("compressed", 2),
                                      ("gather", 1)])
@pytest.mark.parametrize("b", [1, 3, 33])
def test_int8_odd_batch_pads_onto_kernel_path(family, n, b):
    """Decode batches off the 32-row quantum (b=1, 3, 33) must stay on
    the int8 kernel path — the run adapters zero-pad the final row block
    and slice the output — with blocks honoring the quantum."""
    cfg = SparsityConfig(n=n, m=4, mode=family)
    p_q = q.quantize_linear(_family_params(family, _w(), n))
    x = jax.random.normal(jax.random.PRNGKey(2), (b, 128))
    d = dispatch.plan_for(p_q, (b, 128), cfg, dtype=jnp.int8,
                          dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert d.uses_kernel and d.kernel.endswith("_int8"), dispatch.describe(d)
    assert d.blocks[0] % 32 == 0, d.blocks   # fitted against the padded rows
    with dispatch.use_dispatch(backend="jnp"):
        y_ref = apply_linear(p_q, x, cfg)
    with dispatch.use_dispatch(backend="interpret"):
        y_k = apply_linear(p_q, x, cfg)
    assert y_k.shape == (b, 64)
    _norm_close(y_k, y_ref, 3e-2)


# ---------------------------------------------------------------------------
# static activation scales: calibration + decode skips the absmax pass
# ---------------------------------------------------------------------------

def test_quantize_rows_static_saturates_and_shapes():
    x = jnp.asarray([[0.5, -1.0], [4.0, 0.25]], jnp.float32)
    xq, xs = q.quantize_rows_static(x, jnp.float32(1.0 / 127.0))
    assert xq.dtype == jnp.int8 and xs.shape == (2, 1)
    assert int(xq[0, 1]) == -127               # exactly representable
    assert int(xq[1, 0]) == 127                # out of range: saturates
    assert np.allclose(np.asarray(xs), 1.0 / 127.0)


def test_calibrate_activation_scales_stacked_tree():
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_fp = _family_params("compressed", _w(64, 32), 2)
    stacked = jax.tree.map(lambda a: jnp.stack([a, a]), p_fp)
    tree = {"blk": {"w_in": q.quantize_linear(stacked)},
            "norm": {"gamma": jnp.ones((64,))}}
    x0 = jax.random.normal(jax.random.PRNGKey(3), (4, 64))

    def batch_fn(p):
        def layer(x, lp):
            y = apply_linear(lp, x, cfg)
            return x + 0.0 * y[:, :1], y   # shape-stable carry, keeps y live
        _, ys = jax.lax.scan(layer, x0, p["blk"]["w_in"])
        return ys

    with dispatch.use_dispatch(backend="jnp"):
        calibrated, n_sites = q._calibrate_activation_scales(tree, batch_fn)
    assert n_sites == 1
    leaf = calibrated["blk"]["w_in"]
    # the scale broadcasts over the stacked layer dim (scan-sliceable)
    assert q.ACT_SCALE_KEY in leaf and leaf[q.ACT_SCALE_KEY].shape == (2,)
    # the calibration tag must NOT survive into the returned tree
    assert q._CALIB_KEY not in leaf
    # scale = absmax over every activation the stacked site saw / 127
    assert float(leaf[q.ACT_SCALE_KEY][0]) > 0
    # untouched leaves pass through
    assert calibrated["norm"]["gamma"].shape == (64,)
    # planning on the calibrated leaf reports the static class
    item = dict(dispatch.iter_linear_items(calibrated))[("blk", "w_in")]
    d = dispatch.plan_for(item, (4, 64), cfg, dtype=jnp.int8,
                          dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert d.act_scales == "static"
    assert "act-scales=static" in dispatch.describe(d)


def test_recalibration_through_cached_jit_records_fresh_store():
    """Calibrating twice through the SAME jitted batch_fn must record
    into the second store too: the io_callback resolves the active store
    at run time, so the jit cache hit on the second call (identical
    shapes/tags) cannot bake in the first, discarded store."""
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_q = q.quantize_linear(_family_params("compressed", _w(64, 32), 2))
    tree = {"blk": {"w_in": p_q}}

    @jax.jit
    def fwd(p, x):
        with dispatch.use_dispatch(backend="jnp"):
            return apply_linear(p["blk"]["w_in"], x, cfg)

    x1 = jax.random.normal(jax.random.PRNGKey(5), (4, 64))
    x2 = 3.0 * x1      # same shapes -> jit cache hit on the second call
    c1, n1 = q._calibrate_activation_scales(tree, lambda p: fwd(p, x1))
    c2, n2 = q._calibrate_activation_scales(tree, lambda p: fwd(p, x2))
    assert n1 == 1 and n2 == 1
    s1 = float(c1["blk"]["w_in"][q.ACT_SCALE_KEY])
    s2 = float(c2["blk"]["w_in"][q.ACT_SCALE_KEY])
    assert np.isclose(s2, 3.0 * s1, rtol=1e-5)


def test_static_vs_dynamic_scale_accuracy_bound():
    """Static (calibrated, tensor-wise) activation scales cost accuracy
    vs the per-row dynamic pass, but both stay within int8 round-trip
    bounds of the fp32 result on a representative batch."""
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_fp = _family_params("compressed", _w(), 2)
    p_q = q.quantize_linear(p_fp)
    x = jax.random.normal(jax.random.PRNGKey(4), (32, 128))
    p_static = dict(p_q)
    p_static[q.ACT_SCALE_KEY] = (
        jnp.max(jnp.abs(x)) / 127.0).astype(jnp.float32)
    with dispatch.use_dispatch(backend="jnp"):
        y_fp = apply_linear(p_fp, x, cfg)
    with dispatch.use_dispatch(backend="interpret"):
        y_dyn = apply_linear(p_q, x, cfg)
        y_static = apply_linear(p_static, x, cfg)
    _norm_close(y_dyn, y_fp, 5e-2)
    _norm_close(y_static, y_fp, 5e-2)       # same bound class
    _norm_close(y_static, y_dyn, 5e-2)      # scales differ, result doesn't


# ---------------------------------------------------------------------------
# autotune: dtype-distinct cache keys via pretune
# ---------------------------------------------------------------------------

def test_pretune_dtype_distinct_cache_keys(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_fp = _family_params("compressed", _w(64, 32), 2)
    tree = {"a": {"w_in": p_fp}, "b": {"w_in": q.quantize_linear(p_fp)}}
    with dispatch.use_dispatch(backend="interpret"):
        n_tuned = dispatch.pretune(tree, 4, cfg)
    assert n_tuned == 2    # the int8 twin is a distinct problem
    k_fp = autotune.cache_key("nm_spmm", 4, 64, 32, 2, 4, jnp.float32)
    k_q = autotune.cache_key("nm_spmm_int8", 4, 64, 32, 2, 4, jnp.int8)
    assert k_fp.endswith("float32") and k_q.endswith("int8")
    assert autotune.lookup("interpret", k_fp) is not None
    assert autotune.lookup("interpret", k_q) is not None
    autotune.clear_memory_cache()


# ---------------------------------------------------------------------------
# int8 under shard_map: plan matrix, per-shard parity, int32-psum ordering
# (needs XLA_FLAGS=--xla_force_host_platform_device_count=8 — the CI fast
# lane runs this file a second time under the forced device count; on a
# single-device pytest process everything below skips)
# ---------------------------------------------------------------------------

def sharded(fn):
    """Marker + skip guard: ``-m sharded`` selects exactly these tests
    (the dedicated CI step), and they skip on a single-device process."""
    fn = pytest.mark.sharded(fn)
    return pytest.mark.skipif(
        jax.device_count() < 8,
        reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
    )(fn)


@pytest.fixture(scope="module")
def env():
    from repro.launch.mesh import make_axis_env, make_mesh

    if jax.device_count() < 8:
        pytest.skip("needs 8 forced host devices")
    mesh = make_mesh((2, 4), ("data", "model"))
    return make_axis_env(mesh)


def _sharded_family_params(family, n, k=512, o=256, seed=0):
    return _family_params(family, _w(k, o, seed), n)


@sharded
def test_plan_int8_shard_map_matrix(env):
    """Acceptance: with a mesh active, int8 dense/2:4/1:4 sites plan the
    shard_map execution class on *_int8 kernels, not the dequantize
    reference — both TP orientations, with the right collective."""
    from repro.models.pjit_utils import use_axis_env

    dcfg = dispatch.DispatchConfig(backend="interpret")
    cases = [("dense", 4, "tile_gemm_int8"),
             ("compressed", 2, "nm_spmm_int8"),
             ("compressed", 1, "nm_spmm_int8"),
             ("gather", 1, "nm_spmm_gather_int8")]
    with use_axis_env(env):
        for mode, n, kernel in cases:
            for hint, coll in [("col", "none"), ("row", "psum")]:
                shard = dispatch.shard_spec_from_env(hint)
                d = dispatch.plan(
                    dispatch.GemmProblem(mode, b=32, ke=512, o=256, n=n, m=4,
                                         dtype=jnp.int8, sharded=True,
                                         shard=shard),
                    dispatch=dcfg)
                assert d.uses_shard_map and d.kernel == kernel, (
                    mode, n, hint, dispatch.describe(d))
                assert d.collective == coll
                assert d.act_scales == "dynamic"


@sharded
@pytest.mark.parametrize("family,n", [
    ("dense", 4), ("compressed", 1), ("compressed", 2), ("compressed", 4),
    ("gather", 1), ("gather", 2), ("gather", 4),
])
@pytest.mark.parametrize("hint", ["col", "row"])
@pytest.mark.parametrize("b", [4, 32])
def test_sharded_int8_parity(env, family, n, hint, b):
    """TP parity matrix: the per-shard int8 kernels vs the jnp dequantize
    reference, within int8 round-trip bounds (activation quantization is
    the only difference)."""
    from repro.models.pjit_utils import use_axis_env

    cfg = SparsityConfig(n=n, m=4, mode=family)
    p_q = q.quantize_linear(_sharded_family_params(family, n))
    x = jax.random.normal(jax.random.PRNGKey(1), (b, 512))
    with use_axis_env(env):
        with dispatch.use_dispatch(backend="jnp"):
            y_ref = apply_linear(p_q, x, cfg, gather=hint)
        with dispatch.use_dispatch(backend="interpret"):
            y_k = apply_linear(p_q, x, cfg, gather=hint)
    _norm_close(y_k, y_ref, 3e-2)


@sharded
def test_sharded_int8_fsdp_batch_only_spec(env):
    """FSDP-style batch-only sharding (no model-axis slicing) keeps the
    int8 kernel path: shards=(2,1,1), no collective."""
    from repro.models.pjit_utils import use_axis_env

    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_q = q.quantize_linear(_sharded_family_params("compressed", 2))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 512))
    with use_axis_env(env):
        shard = dispatch.shard_spec_from_env(None)   # batch-only
        d = dispatch.plan_for(p_q, (32, 512), cfg, dtype=jnp.int8,
                              shard=shard,
                              dispatch=dispatch.DispatchConfig(
                                  backend="interpret"))
        assert d.uses_shard_map and d.shards == (2, 1, 1)
        assert d.collective == "none"
        y_k = dispatch.sparse_matmul(
            x, p_q, cfg, shard=shard,
            dispatch=dispatch.DispatchConfig(backend="interpret"))
        y_ref = dispatch.sparse_matmul(
            x, p_q, cfg, dispatch=dispatch.DispatchConfig(backend="jnp"))
    _norm_close(y_k, y_ref, 3e-2)


@sharded
def test_sharded_int8_psum_matches_single_device_exactly(env):
    """The sharded-contraction ordering contract: shards quantize against
    the pmax-lifted global row scale, contract to raw int32 partials,
    psum exactly in int32, and dequantize once — so the row-sharded
    result matches the single-device int8 kernel bit-for-bit."""
    from repro.models.pjit_utils import use_axis_env

    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_q = q.quantize_linear(_sharded_family_params("compressed", 2))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 512))
    with dispatch.use_dispatch(backend="interpret"):
        y_single = apply_linear(p_q, x, cfg)
        with use_axis_env(env):
            y_row = apply_linear(p_q, x, cfg, gather="row")
    assert np.array_equal(np.asarray(y_single), np.asarray(y_row))


@sharded
def test_sharded_int8_static_scales(env):
    """Static activation scales ride the shard_map class: the scalar
    act_scale leaf replicates, the plan reports the static class, and
    parity holds for both orientations."""
    from repro.models.pjit_utils import use_axis_env

    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_q = q.quantize_linear(_sharded_family_params("compressed", 2))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 512))
    p_static = dict(p_q)
    p_static[q.ACT_SCALE_KEY] = (
        jnp.max(jnp.abs(x)) / 127.0).astype(jnp.float32)
    with use_axis_env(env):
        for hint in ("col", "row"):
            shard = dispatch.shard_spec_from_env(hint)
            d = dispatch.plan_for(p_static, (32, 512), cfg, dtype=jnp.int8,
                                  shard=shard,
                                  dispatch=dispatch.DispatchConfig(
                                      backend="interpret"))
            assert d.uses_shard_map and d.act_scales == "static"
            with dispatch.use_dispatch(backend="jnp"):
                y_ref = apply_linear(p_static, x, cfg, gather=hint)
            with dispatch.use_dispatch(backend="interpret"):
                y_k = apply_linear(p_static, x, cfg, gather=hint)
            _norm_close(y_k, y_ref, 3e-2)


@sharded
def test_sharded_int8_kernel_actually_runs(env, monkeypatch):
    """The mesh path must invoke the int8 Pallas kernel body per shard,
    not just plan it."""
    import repro.kernels.nm_spmm.kernel as nm_kernel
    from repro.models.pjit_utils import use_axis_env

    calls = []
    real = nm_kernel.nm_spmm_int8

    def spy(*args, **kwargs):
        calls.append(kwargs.get("interpret"))
        return real(*args, **kwargs)

    monkeypatch.setattr(nm_kernel, "nm_spmm_int8", spy)
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_q = q.quantize_linear(_sharded_family_params("compressed", 2))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 512))
    with use_axis_env(env):
        with dispatch.use_dispatch(backend="interpret"):
            apply_linear(p_q, x, cfg, gather="col")
    assert calls == [True]


@sharded
def test_sharded_int8_under_jit(env):
    """The decode loop traces sparse_matmul under jit with the mesh env
    installed — the int8 shard_map class must compose with tracing."""
    from repro.models.pjit_utils import use_axis_env

    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_q = q.quantize_linear(_sharded_family_params("compressed", 2))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 512))
    with use_axis_env(env):
        with dispatch.use_dispatch(backend="jnp"):
            y_ref = apply_linear(p_q, x, cfg, gather="row")
        with dispatch.use_dispatch(backend="interpret"):
            y_k = jax.jit(
                lambda p, x: apply_linear(p, x, cfg, gather="row"))(p_q, x)
    assert y_k.shape == (4, 8, 256)
    _norm_close(y_k, y_ref, 3e-2)


@sharded
def test_quantized_moe_experts_decode_under_mesh(env):
    """Quantized MoE expert stacks must place under BOTH expert-sharding
    branches: the per-out-channel scale leaf slices its out dim with the
    operand in the replicated-token 2D branch (b=1 decode), and rides the
    expert dim in the 1D branch (b divisible by the data axes)."""
    from repro.configs import get_smoke_config
    from repro.launch.shardings import ShardingRules
    from repro.models import decode_step, init_caches, init_params
    from repro.models.pjit_utils import use_axis_env

    cfg = get_smoke_config("qwen3_moe_235b_a22b")
    params = q._quantize_tree(init_params(jax.random.PRNGKey(0), cfg))

    # static scales too: the (E,)-shaped act_scale aux leaf must survive
    # expert placement in both branches (it crashed _ff_dim_divisible)
    def _attach(leaf):
        if not q.is_quantized(leaf):
            return leaf
        key = "w" if "w" in leaf else "values"
        return {**leaf, q.ACT_SCALE_KEY: jnp.full(leaf[key].shape[:-2],
                                                  0.05, jnp.float32)}

    params = q.map_linear_leaves(params, _attach)
    rules = ShardingRules(env, cfg)
    params = jax.device_put(params, rules.tree_shardings(params))
    with use_axis_env(env):
        step = jax.jit(lambda p, c, t, i: decode_step(p, c, t, i, cfg))
        for b in (1, 2):   # 2D (replicated) and 1D (batch-sharded) branches
            caches = init_caches(cfg, b, 8)
            lg, _ = step(params, caches, jnp.ones((b, 1), jnp.int32),
                         jnp.int32(0))
            assert lg.shape == (b, 1, cfg.vocab_size)
            assert bool(jnp.isfinite(lg).all())
