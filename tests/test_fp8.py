"""fp8 (float8_e4m3fn) quantized execution class: round-trip error
bounds vs int8 on the same layouts, kernel-vs-fp32 parity for every
family and N, the three-way {fp32, int8, fp8} registry/autotune dtype
axis, the native-fp8-dot hardware gate, and the sharded execution class
(plan matrix, parity, and raw-partial psum bit-identity on
exact-arithmetic data).
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SparsityConfig, apply_linear, convert_layout, nm
from repro.core import quantize as q
from repro.kernels import autotune, dispatch, registry

FP8 = jnp.float8_e4m3fn


def _norm_close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = np.abs(want).max() + 1e-6
    np.testing.assert_allclose(got / scale, want / scale, atol=tol)


def _w(k=128, o=64, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (k, o), jnp.float32)


def _family_params(family, w, n):
    """Serving-layout params for one kernel family at sparsity n:4 (built
    by hand so n=4 genuinely exercises compressed/gather layouts)."""
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
# storage: fp8 round-trip bounds, and fp8-vs-int8 on the same layout
# ---------------------------------------------------------------------------

def test_fp8_roundtrip_error_bound_per_element():
    """e4m3fn rounds to ~4 mantissa bits: per-element relative error is
    at most one half-ulp (2^-4) for normal values, plus the subnormal
    step near zero — unlike int8, whose error is a flat absmax/127."""
    w = _w(256, 96)
    qv, scale = q.quantize_per_channel(w, FP8)
    assert qv.dtype == FP8 and scale.shape == (96,)
    err = np.abs(np.asarray(q.dequantize(qv, scale)) - np.asarray(w))
    # relative half-ulp for normals + the subnormal quantum (2^-9 of the
    # pre-scale value, i.e. scale * 2^-10 after the half-ulp rounding)
    bound = np.abs(np.asarray(w)) * 2.0 ** -4 + np.asarray(scale) * 2.0 ** -9
    assert (err <= bound + 1e-7).all()
    assert not np.isnan(np.asarray(qv, np.float32)).any()


def test_fp8_vs_int8_roundtrip_same_layout():
    """On an outlier-heavy (log-normal) weight channel, fp8's logarithmic
    step spacing beats int8's uniform grid in mean round-trip error —
    int8 still wins at the top of the range.  Same layout, same scale
    machinery, only the dtype axis differs."""
    key = jax.random.PRNGKey(7)
    w = (jnp.exp(jax.random.normal(key, (512, 8)) * 2.0)
         * jnp.sign(jax.random.normal(jax.random.PRNGKey(8), (512, 8))))
    q8, s8 = q.quantize_per_channel(w, jnp.int8)
    qf, sf = q.quantize_per_channel(w, FP8)
    err8 = np.abs(np.asarray(q.dequantize(q8, s8)) - np.asarray(w))
    errf = np.abs(np.asarray(q.dequantize(qf, sf)) - np.asarray(w))
    assert errf.mean() < err8.mean()
    # both honor the shared symmetric-scale contract
    assert s8.shape == sf.shape == (8,)


def test_fp8_quantize_rows_bound_and_zero_rows():
    x = jnp.concatenate([jax.random.normal(jax.random.PRNGKey(1), (7, 64)),
                         jnp.zeros((1, 64))])
    xq, xs = q.quantize_rows(x, dtype=FP8)
    assert xq.dtype == FP8 and xs.shape == (8, 1)
    err = np.abs(np.asarray(xq, np.float32) * np.asarray(xs)
                 - np.asarray(x, np.float32))
    bound = (np.abs(np.asarray(x)) * 2.0 ** -4
             + np.asarray(xs) * 2.0 ** -9)
    assert (err <= bound + 1e-7).all()
    assert not np.isnan(np.asarray(xs)).any()


def test_fp8_static_scale_saturates_never_nan():
    """e4m3fn has no inf: an unclipped overflow casts to NaN, so the
    static-scale path must clip to ±448 before the cast."""
    x = jnp.asarray([[1.0, -1.0], [1e6, -1e6]], jnp.float32)
    xq, xs = q.quantize_rows_static(x, jnp.float32(1.0), dtype=FP8)
    assert xq.dtype == FP8
    got = np.asarray(xq, np.float32)
    assert not np.isnan(got).any()
    assert got[1, 0] == 448.0 and got[1, 1] == -448.0


def test_convert_layout_fp8_every_mode():
    w = _w()
    dense = convert_layout({"w": w}, SparsityConfig(mode="dense"),
                               "dense", quantize="fp8")
    assert dense["w"].dtype == FP8 and dense["scale"].shape == (64,)
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    comp = convert_layout({"w": w}, cfg, "compressed", quantize="fp8")
    assert comp["values"].dtype == FP8 and "meta_packed" in comp
    gath = convert_layout({"w": w}, SparsityConfig(n=2, m=4, mode="gather"),
                              "gather", quantize="fp8")
    assert gath["values"].dtype == FP8 and "gather_idx" in gath
    rw = convert_layout({"w": w}, cfg, "rowwise", quantize="fp8")
    for seg in rw["rowwise"].values():
        assert seg["values"].dtype == FP8 and "scale" in seg
    with pytest.raises(ValueError):
        convert_layout({"w": w}, cfg, "compressed", quantize="fp4")


def test_quantize_tree_fp8_alias():
    w = _w(64, 32)
    qt = q._quantize_tree({"blk": {"w_in": {"w": w}}}, "fp8")
    assert qt["blk"]["w_in"]["w"].dtype == FP8
    assert q.quant_dtype(qt["blk"]["w_in"]) == jnp.dtype(FP8)


# ---------------------------------------------------------------------------
# kernel parity: fp8 registry entries vs fp32 reference, all families x N
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["dense", "compressed", "gather"])
@pytest.mark.parametrize("n", [1, 2, 4])
def test_fp8_kernel_parity_vs_fp32(family, n):
    if family == "dense" and n != 4:
        pytest.skip("dense has no sparsity axis")
    cfg = SparsityConfig(n=n, m=4, mode=family)
    p_fp = _family_params(family, _w(), n)
    p_q = q.quantize_linear(p_fp, FP8)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 128))
    with dispatch.use_dispatch(backend="jnp"):
        y_fp = apply_linear(p_fp, x, cfg)
        y_qref = apply_linear(p_q, x, cfg)       # dequantize reference
    with dispatch.use_dispatch(backend="interpret"):
        y_qk = apply_linear(p_q, x, cfg)         # fp8 registry kernel
    d = dispatch.plan_for(p_q, (32, 128), cfg, dtype=FP8,
                          dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert d.uses_kernel and d.kernel.endswith("_fp8"), dispatch.describe(d)
    assert "dtype=float8_e4m3fn" in dispatch.describe(d)
    # vs fp32: weight + activation fp8 rounding (~2^-4 relative each)
    _norm_close(y_qk, y_fp, 8e-2)
    # vs the dequantize reference: only activation quantization differs
    _norm_close(y_qk, y_qref, 5e-2)


def test_fp8_kernel_invoked_not_planned(monkeypatch):
    import repro.kernels.nm_spmm.kernel as nm_kernel

    calls = []
    real = nm_kernel.nm_spmm

    def spy(*args, **kwargs):
        calls.append(kwargs.get("interpret"))
        return real(*args, **kwargs)

    monkeypatch.setattr(nm_kernel, "nm_spmm", spy)
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_q = q.quantize_linear(_family_params("compressed", _w(64, 32), 2), FP8)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    with dispatch.use_dispatch(backend="interpret"):
        apply_linear(p_q, x, cfg)
    assert calls == [True]
    calls.clear()
    with dispatch.use_dispatch(backend="jnp"):
        apply_linear(p_q, x, cfg)
    assert calls == []


@pytest.mark.parametrize("family,n", [("dense", 4), ("compressed", 2),
                                      ("gather", 1)])
@pytest.mark.parametrize("b", [1, 3, 33])
def test_fp8_odd_batch_pads_onto_kernel_path(family, n, b):
    """Decode batches off the 32-row quantum (b=1, 3, 33) must stay on
    the fp8 kernel path — the run adapters zero-pad the final row block
    and slice the output — with blocks honoring the quantum."""
    cfg = SparsityConfig(n=n, m=4, mode=family)
    p_q = q.quantize_linear(_family_params(family, _w(), n), FP8)
    x = jax.random.normal(jax.random.PRNGKey(2), (b, 128))
    d = dispatch.plan_for(p_q, (b, 128), cfg, dtype=FP8,
                          dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert d.uses_kernel and d.kernel.endswith("_fp8"), dispatch.describe(d)
    assert d.blocks[0] % 32 == 0, d.blocks   # fitted against padded rows
    with dispatch.use_dispatch(backend="jnp"):
        y_ref = apply_linear(p_q, x, cfg)
    with dispatch.use_dispatch(backend="interpret"):
        y_k = apply_linear(p_q, x, cfg)
    assert y_k.shape == (b, 64)
    _norm_close(y_k, y_ref, 5e-2)


# ---------------------------------------------------------------------------
# registry: the three-way {fp32, int8, fp8} dtype axis
# ---------------------------------------------------------------------------

def test_registry_three_way_dtype_axis():
    table = [("dense", "tile_gemm"), ("compressed", "nm_spmm"),
             ("gather", "nm_spmm_gather")]
    for mode, base in table:
        for dt, suffix in [(jnp.float32, ""), (jnp.int8, "_int8"),
                           (FP8, "_fp8")]:
            sel = registry.select(mode, b=32, ke=128, o=64, n=2, m=4,
                                  dtype=dt, backend="interpret")
            assert sel is not None and sel[0].name == base + suffix, (
                mode, dt, sel and sel[0].name)


def test_fp8_tiling_stricter_than_fp32():
    # ke=40 fits fp32 nm_spmm but no divisor of 40 hits the 32-row
    # quantized sublane quantum — same constraint class as int8
    assert registry.select("compressed", b=32, ke=40, o=64, n=2, m=4,
                           dtype=jnp.float32, backend="interpret") is not None
    assert registry.select("compressed", b=32, ke=40, o=64, n=2, m=4,
                           dtype=FP8, backend="interpret") is None
    d = dispatch.plan(
        dispatch.GemmProblem("compressed", b=32, ke=40, o=64, n=2, m=4,
                             dtype=FP8),
        dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert not d.uses_kernel and "no registered kernel" in d.reason
    assert "float8_e4m3fn" in d.reason


def test_fp8_native_dot_gate(monkeypatch):
    """The fp8 entries require a native fp8 MXU dot on the tpu backend
    (the ``supported`` predicate); interpret mode always emulates.  The
    REPRO_FP8_NATIVE env var overrides the device-kind probe."""
    monkeypatch.setenv("REPRO_FP8_NATIVE", "0")
    assert not registry.fp8_native_dot()
    assert registry.select("compressed", b=32, ke=128, o=64, n=2, m=4,
                           dtype=FP8, backend="tpu") is None
    # interpret emulation is unaffected by the hardware gate
    sel = registry.select("compressed", b=32, ke=128, o=64, n=2, m=4,
                          dtype=FP8, backend="interpret")
    assert sel is not None and sel[0].name == "nm_spmm_fp8"
    monkeypatch.setenv("REPRO_FP8_NATIVE", "1")
    assert registry.fp8_native_dot()
    sel = registry.select("compressed", b=32, ke=128, o=64, n=2, m=4,
                          dtype=FP8, backend="tpu")
    assert sel is not None and sel[0].name == "nm_spmm_fp8"
    # the gate never touches the int8 entries
    monkeypatch.setenv("REPRO_FP8_NATIVE", "0")
    sel = registry.select("compressed", b=32, ke=128, o=64, n=2, m=4,
                          dtype=jnp.int8, backend="tpu")
    assert sel is not None and sel[0].name == "nm_spmm_int8"


def test_fp8_autodiff_falls_back_to_dequant_reference():
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_q = q.quantize_linear(_family_params("compressed", _w(64, 32), 2), FP8)

    def loss(x):
        return jnp.sum(apply_linear(p_q, x, cfg) ** 2)

    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    with dispatch.use_dispatch(backend="interpret"):
        g = jax.grad(loss)(x)
    assert g.shape == x.shape and bool(jnp.any(g != 0))


def test_fp8_shard_spec_plans_shard_map():
    spec = dispatch.ShardSpec(
        mesh=types.SimpleNamespace(shape={"model": 2}), ke="model")
    d = dispatch.plan(
        dispatch.GemmProblem("compressed", b=32, ke=128, o=64, n=2, m=4,
                             dtype=FP8, shard=spec),
        dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert d.uses_kernel and d.uses_shard_map, dispatch.describe(d)
    assert d.kernel == "nm_spmm_fp8" and d.collective == "psum"
    assert d.act_scales == "dynamic" and d.dtype == "float8_e4m3fn"


# ---------------------------------------------------------------------------
# autotune: three-way dtype-distinct cache keys via pretune
# ---------------------------------------------------------------------------

def test_pretune_three_way_dtype_distinct_cache_keys(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    autotune.clear_memory_cache()
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_fp = _family_params("compressed", _w(64, 32), 2)
    tree = {"a": {"w_in": p_fp},
            "b": {"w_in": q.quantize_linear(p_fp, jnp.int8)},
            "c": {"w_in": q.quantize_linear(p_fp, FP8)}}
    with dispatch.use_dispatch(backend="interpret"):
        n_tuned = dispatch.pretune(tree, 4, cfg)
    assert n_tuned == 3    # each dtype twin is a distinct problem
    keys = [autotune.cache_key("nm_spmm", 4, 64, 32, 2, 4, jnp.float32),
            autotune.cache_key("nm_spmm_int8", 4, 64, 32, 2, 4, jnp.int8),
            autotune.cache_key("nm_spmm_fp8", 4, 64, 32, 2, 4, FP8)]
    assert len(set(keys)) == 3
    assert keys[2].endswith("float8_e4m3fn")
    for k in keys:
        assert autotune.lookup("interpret", k) is not None
    autotune.clear_memory_cache()


# ---------------------------------------------------------------------------
# static activation scales on the fp8 class
# ---------------------------------------------------------------------------

def test_fp8_calibration_uses_fp8_qmax():
    """act_scale on an fp8 leaf is absmax/448 (the leaf's own dtype),
    not int8's absmax/127 — both classes can coexist in one tree."""
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_fp = _family_params("compressed", _w(64, 32), 2)
    tree = {"i8": {"w_in": q.quantize_linear(p_fp, jnp.int8)},
            "f8": {"w_in": q.quantize_linear(p_fp, FP8)}}
    x0 = jax.random.normal(jax.random.PRNGKey(3), (4, 64))

    def batch_fn(p):
        with dispatch.use_dispatch(backend="jnp"):
            a = apply_linear(p["i8"]["w_in"], x0, cfg)
            b = apply_linear(p["f8"]["w_in"], x0, cfg)
        return a + b

    calibrated, n_sites = q._calibrate_activation_scales(tree, batch_fn)
    assert n_sites == 2
    absmax = float(jnp.max(jnp.abs(x0)))
    s_i8 = float(calibrated["i8"]["w_in"][q.ACT_SCALE_KEY])
    s_f8 = float(calibrated["f8"]["w_in"][q.ACT_SCALE_KEY])
    assert np.isclose(s_i8, absmax / 127.0, rtol=1e-6)
    assert np.isclose(s_f8, absmax / 448.0, rtol=1e-6)
    d = dispatch.plan_for(calibrated["f8"]["w_in"], (4, 64), cfg, dtype=FP8,
                          dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert d.act_scales == "static"


def test_fp8_static_vs_dynamic_scale_accuracy_bound():
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_fp = _family_params("compressed", _w(), 2)
    p_q = q.quantize_linear(p_fp, FP8)
    x = jax.random.normal(jax.random.PRNGKey(4), (32, 128))
    p_static = dict(p_q)
    p_static[q.ACT_SCALE_KEY] = (
        jnp.max(jnp.abs(x)) / 448.0).astype(jnp.float32)
    with dispatch.use_dispatch(backend="jnp"):
        y_fp = apply_linear(p_fp, x, cfg)
    with dispatch.use_dispatch(backend="interpret"):
        y_dyn = apply_linear(p_q, x, cfg)
        y_static = apply_linear(p_static, x, cfg)
    _norm_close(y_dyn, y_fp, 8e-2)
    _norm_close(y_static, y_fp, 8e-2)
    _norm_close(y_static, y_dyn, 8e-2)


# ---------------------------------------------------------------------------
# fp8 under shard_map (needs 8 forced host devices — the CI fast lane
# runs this file a second time under XLA_FLAGS; single-device skips)
# ---------------------------------------------------------------------------

def sharded(fn):
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


def _exact_fp8_leaf(k=512, o=256, seed=0):
    """A compressed 2:4 fp8 layout whose arithmetic is EXACT in fp32.

    Values are small integers stored as fp8 (integers up to 16 are
    exactly representable in e4m3), the per-channel scale is 1, and the
    matching activations (see ``_exact_rows``) are integers too — every
    product and partial sum stays an integer far below 2^24, so fp32
    accumulation is exact regardless of block/shard split.  That makes
    bit-identity a pure test of the ORDERING contract (one coherent row
    scale, raw-partial psum, single dequantize): any double-dequantize,
    per-shard scale skew, or premature cast breaks equality even on
    integer data.
    """
    rng = np.random.default_rng(seed)
    w = rng.integers(-8, 9, size=(k, o)).astype(np.float32)
    pruned, _ = nm.prune_nm(jnp.asarray(w), 2, 4)
    c = nm.compress_nm(pruned, 2, 4)
    return {"values": c.values.astype(FP8),
            "meta_packed": nm.pack_meta(c.meta),
            q.SCALE_KEY: jnp.ones((o,), jnp.float32)}


def _exact_rows(b=32, k=512, seed=1):
    """Integer activations whose per-row absmax is exactly 448, so the
    dynamic quantization scale is exactly 1 and x quantizes to itself
    (per-shard pmax lifts every local absmax to the same 448)."""
    rng = np.random.default_rng(seed)
    x = rng.integers(-8, 9, size=(b, k)).astype(np.float32)
    x[:, 0] = 448.0
    return jnp.asarray(x)


@sharded
def test_plan_fp8_shard_map_matrix(env):
    """Acceptance: with a mesh active, fp8 dense/2:4/1:4 sites plan the
    shard_map execution class on *_fp8 kernels, not the dequantize
    reference — both TP orientations, with the right collective."""
    from repro.models.pjit_utils import use_axis_env

    dcfg = dispatch.DispatchConfig(backend="interpret")
    cases = [("dense", 4, "tile_gemm_fp8"),
             ("compressed", 2, "nm_spmm_fp8"),
             ("compressed", 1, "nm_spmm_fp8"),
             ("gather", 1, "nm_spmm_gather_fp8")]
    with use_axis_env(env):
        for mode, n, kernel in cases:
            for hint, coll in [("col", "none"), ("row", "psum")]:
                shard = dispatch.shard_spec_from_env(hint)
                d = dispatch.plan(
                    dispatch.GemmProblem(mode, b=32, ke=512, o=256, n=n, m=4,
                                         dtype=FP8, sharded=True, shard=shard),
                    dispatch=dcfg)
                assert d.uses_shard_map and d.kernel == kernel, (
                    mode, n, hint, dispatch.describe(d))
                assert d.collective == coll
                assert d.dtype == "float8_e4m3fn"


@sharded
@pytest.mark.parametrize("family,n", [("dense", 4), ("compressed", 2),
                                      ("gather", 1)])
@pytest.mark.parametrize("hint", ["col", "row"])
def test_sharded_fp8_parity(env, family, n, hint):
    """TP parity: per-shard fp8 kernels vs the jnp dequantize reference,
    within fp8 round-trip bounds."""
    from repro.models.pjit_utils import use_axis_env

    cfg = SparsityConfig(n=n, m=4, mode=family)
    p_q = q.quantize_linear(_family_params(family, _w(512, 256), n), FP8)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 512))
    with use_axis_env(env):
        with dispatch.use_dispatch(backend="jnp"):
            y_ref = apply_linear(p_q, x, cfg, gather=hint)
        with dispatch.use_dispatch(backend="interpret"):
            y_k = apply_linear(p_q, x, cfg, gather=hint)
    _norm_close(y_k, y_ref, 5e-2)


@sharded
def test_sharded_fp8_bit_identical_to_single_device(env):
    """The sharded-contraction ordering contract for fp8: shards quantize
    against the pmax-lifted global row scale, contract to raw fp32
    partials, psum them, and dequantize once.  On exact-arithmetic data
    (see ``_exact_fp8_leaf``) every split produces identical bits, so
    the row-sharded AND col-sharded results must equal the single-device
    kernel bit-for-bit — both for dynamic (pmax) and static scales."""
    from repro.models.pjit_utils import use_axis_env

    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    x = _exact_rows()
    for leaf in (_exact_fp8_leaf(),
                 {**_exact_fp8_leaf(), q.ACT_SCALE_KEY: jnp.float32(1.0)}):
        with dispatch.use_dispatch(backend="interpret"):
            y_single = apply_linear(leaf, x, cfg)
            with use_axis_env(env):
                y_row = apply_linear(leaf, x, cfg, gather="row")
                y_col = apply_linear(leaf, x, cfg, gather="col")
        assert np.array_equal(np.asarray(y_single), np.asarray(y_row))
        assert np.array_equal(np.asarray(y_single), np.asarray(y_col))
        # the data really exercises the kernel: outputs are non-trivial
        assert float(jnp.max(jnp.abs(y_single))) > 0


@sharded
def test_sharded_fp8_kernel_actually_runs(env, monkeypatch):
    """The mesh path must invoke the fp8 Pallas kernel body per shard,
    not just plan it."""
    import repro.kernels.nm_spmm.kernel as nm_kernel
    from repro.models.pjit_utils import use_axis_env

    calls = []
    real = nm_kernel.nm_spmm

    def spy(*args, **kwargs):
        calls.append(kwargs.get("interpret"))
        return real(*args, **kwargs)

    monkeypatch.setattr(nm_kernel, "nm_spmm", spy)
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_q = q.quantize_linear(_family_params("compressed", _w(512, 256), 2), FP8)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 512))
    with use_axis_env(env):
        with dispatch.use_dispatch(backend="interpret"):
            apply_linear(p_q, x, cfg, gather="col")
    assert calls == [True]


@sharded
def test_sharded_fp8_under_jit(env):
    """The decode loop traces sparse_matmul under jit with the mesh env
    installed — the fp8 shard_map class must compose with tracing."""
    from repro.models.pjit_utils import use_axis_env

    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p_q = q.quantize_linear(_family_params("compressed", _w(512, 256), 2), FP8)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 512))
    with use_axis_env(env):
        with dispatch.use_dispatch(backend="jnp"):
            y_ref = apply_linear(p_q, x, cfg, gather="row")
        with dispatch.use_dispatch(backend="interpret"):
            y_k = jax.jit(
                lambda p, x: apply_linear(p, x, cfg, gather="row"))(p_q, x)
    assert y_k.shape == (4, 8, 256)
    _norm_close(y_k, y_ref, 5e-2)
