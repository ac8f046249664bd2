"""Fused epilogue lattice (dequantize -> bias -> activation -> requantize).

Parity contract under test: a FUSED epilogue (applied on the fp32
accumulator tile in VMEM by the kernel flush) must match the UNFUSED
formulation (kernel/jnp GEMM + ``apply_reference``) — and every fallback
tier (jnp reference, autodiff, unfittable tiles, mesh-sharded sites) must
bit-match the reference, never silently change numerics.  The gate-up
dual kernel (``silu_mul``) and the fused requantize chain (producer emits
the consumer's narrow operand) are exercised against the unfused
QUANTIZED path, which is the bit-identical target.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SparsityConfig, nm
from repro.core import quantize as q
from repro.core.sparse_linear import apply_gate_up, apply_linear
from repro.kernels import autotune, dispatch, registry
from repro.kernels import epilogue as epilib
from repro.kernels.dispatch import DispatchConfig, gate_up_matmul, sparse_matmul

KERN = DispatchConfig(backend="interpret")
JNP = DispatchConfig(backend="jnp")

B, K, O = 8, 128, 64


def _w(k=K, o=O, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (k, o), jnp.float32)


def _family_params(family, w, n):
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


def _x(b=B, k=K, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, k), jnp.float32)


def _cfg(family, n):
    mode = {"dense": "dense", "compressed": "compressed",
            "gather": "gather"}[family]
    return SparsityConfig(n=n, m=4, mode=mode)


def _bias(o=O, seed=7):
    return jax.random.normal(jax.random.PRNGKey(seed), (o,), jnp.float32)


POINTS = [
    dict(act=None, bias=True),
    dict(act="silu", bias=False),
    dict(act="gelu", bias=False),
    dict(act="gelu", bias=True),
]


def _epi(point, o=O):
    return epilib.make(act=point["act"],
                       bias=_bias(o) if point["bias"] else None)


# ---------------------------------------------------------------------------
# spec / lattice basics
# ---------------------------------------------------------------------------

def test_spec_point_names_and_identity():
    assert epilib.EpilogueSpec().point == "none"
    assert epilib.EpilogueSpec().is_identity
    s = epilib.EpilogueSpec(act="gelu", bias=True, requant="int8")
    assert s.point == "bias+gelu+requant:int8"
    assert epilib.EpilogueSpec(act="silu_mul").point == "silu_mul"
    with pytest.raises(ValueError):
        epilib.EpilogueSpec(act="tanh")
    with pytest.raises(ValueError):
        epilib.Epilogue(epilib.EpilogueSpec(bias=True))  # operand missing


def test_autotune_keys_distinct_per_lattice_point():
    bare = autotune.cache_key("tile_gemm", B, K, O, 4, 4, jnp.float32)
    fused = autotune.cache_key("tile_gemm", B, K, O, 4, 4, jnp.float32,
                               epilogue="bias+gelu")
    other = autotune.cache_key("tile_gemm", B, K, O, 4, 4, jnp.float32,
                               epilogue="silu")
    assert len({bare, fused, other}) == 3
    assert fused.endswith("_epi[bias+gelu]")


def test_plan_carries_epilogue_and_describe():
    d = dispatch.plan(
        dispatch.GemmProblem("dense", b=B, ke=K, o=O, n=4, m=4,
                             dtype=jnp.float32, epilogue="bias+gelu"),
        dispatch=KERN)
    assert d.epilogue == "bias+gelu" and d.epilogue_fused
    assert "epilogue=bias+gelu[fused]" in dispatch.describe(d)
    # mesh env active without a spec: jnp tier, epilogue applied unfused
    d2 = dispatch.plan(
        dispatch.GemmProblem("dense", b=B, ke=K, o=O, n=4, m=4,
                             dtype=jnp.float32, epilogue="bias+gelu",
                             sharded=True),
        dispatch=KERN)
    assert not d2.epilogue_fused and d2.backend == "jnp"
    assert "epilogue=bias+gelu[jnp]" in dispatch.describe(d2)
    # autodiff declines fusion
    d3 = dispatch.plan(
        dispatch.GemmProblem("dense", b=B, ke=K, o=O, n=4, m=4,
                             dtype=jnp.float32, epilogue="gelu",
                             differentiating=True),
        dispatch=KERN)
    assert not d3.epilogue_fused and d3.backend == "jnp"


# ---------------------------------------------------------------------------
# fused vs unfused parity: every family x lattice point x N
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family,n", [
    ("dense", 4),
    ("compressed", 1), ("compressed", 2), ("compressed", 4),
    ("gather", 1), ("gather", 2), ("gather", 4),
])
@pytest.mark.parametrize("point", POINTS,
                         ids=[f"{p['act']}-bias{p['bias']}" for p in POINTS])
def test_fused_matches_unfused_float(family, n, point):
    params = _family_params(family, _w(), n)
    cfg = _cfg(family, n)
    x = _x()
    epi = _epi(point)
    d = dispatch.plan(
        dispatch.GemmProblem(cfg.mode, b=B, ke=x.shape[1], o=O, n=n, m=4,
                             dtype=jnp.float32, epilogue=epi.spec.point),
        dispatch=KERN)
    assert d.epilogue_fused, dispatch.describe(d)
    got = sparse_matmul(x, params, cfg, dispatch=KERN, epilogue=epi)
    # unfused reference: same GEMM through the jnp tier + apply_reference
    want = sparse_matmul(x, params, cfg, dispatch=JNP, epilogue=epi)
    scale = np.abs(np.asarray(want)).max() + 1e-6
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=5e-6)


@pytest.mark.parametrize("family", ["dense", "compressed", "gather"])
@pytest.mark.parametrize("qdtype", ["int8", "fp8"])
def test_fused_rides_quantized_flush(family, qdtype):
    """For quantized entries the epilogue rides the flush-time dequantize:
    fused output matches kernel-without-epilogue + apply_reference to ~ulp
    (same fp32 accumulator and ops; XLA may contract the dequantize
    multiply and bias add into an FMA inside the kernel flush)."""
    n = 2 if family != "dense" else 4
    params = q.quantize_linear(_family_params(family, _w(), n),
                               "int8" if qdtype == "int8" else "fp8")
    cfg = _cfg(family, n)
    x = _x()
    epi = _epi(dict(act="gelu", bias=True))
    qdt = q.quant_dtype(params)
    d = dispatch.plan(
        dispatch.GemmProblem(cfg.mode, b=B, ke=x.shape[1], o=O, n=n, m=4,
                             dtype=qdt, epilogue=epi.spec.point),
        dispatch=KERN)
    assert d.epilogue_fused, dispatch.describe(d)
    got = sparse_matmul(x, params, cfg, dispatch=KERN, epilogue=epi)
    bare = sparse_matmul(x, params, cfg, dispatch=KERN)
    want = epilib.apply_reference(bare, epi)
    scale = np.abs(np.asarray(want)).max() + 1e-6
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=1e-6)


def test_bias_values_actually_flow():
    params = {"w": _w()}
    cfg = _cfg("dense", 4)
    x = _x()
    bias = _bias()
    got = sparse_matmul(x, params, cfg, dispatch=KERN,
                        epilogue=epilib.make(bias=bias))
    want = x @ params["w"] + bias
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# fallback tiers bit-match the unfused reference
# ---------------------------------------------------------------------------

def test_grad_context_takes_unfused_path_bit_exact():
    params = {"w": _w()}
    cfg = _cfg("dense", 4)
    x = _x()
    epi = _epi(dict(act="gelu", bias=True))

    def f(xx):
        return sparse_matmul(xx, params, cfg, dispatch=KERN,
                             epilogue=epi).sum()

    def f_ref(xx):
        y = xx @ params["w"] + epi.bias
        return jax.nn.gelu(y).sum()

    got = jax.grad(f)(x)
    want = jax.grad(f_ref)(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


def test_unfittable_tiles_fall_back_bit_exact():
    # ke=40 has no divisor on the int8 2:4 contraction quantum (64) ->
    # the kernel declines, the dequantize reference runs, epilogue
    # applies unfused
    params = q.quantize_linear(_family_params("compressed", _w(k=40), 2),
                               "int8")
    cfg = _cfg("compressed", 2)
    x = _x(k=40)
    epi = _epi(dict(act="silu", bias=True))
    d = dispatch.plan(
        dispatch.GemmProblem("compressed", b=B, ke=40, o=O, n=2, m=4,
                             dtype=q.quant_dtype(params),
                             epilogue=epi.spec.point),
        dispatch=KERN)
    assert not d.uses_kernel and not d.epilogue_fused
    got = sparse_matmul(x, params, cfg, dispatch=KERN, epilogue=epi)
    want = epilib.apply_reference(
        sparse_matmul(x, params, cfg, dispatch=JNP), epi)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_rowwise_applies_epilogue_unfused_after_unpermute():
    from repro.core.sparse_linear import init_linear
    cfg = SparsityConfig(n=2, m=4, mode="rowwise")
    params = init_linear(jax.random.PRNGKey(0), K, O, cfg, jnp.float32)
    x = _x()
    epi = _epi(dict(act="gelu", bias=True))
    got = apply_linear(params, x, cfg, epilogue=epi)
    want = epilib.apply_reference(apply_linear(params, x, cfg), epi)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_silu_mul_rejected_outside_gate_up():
    with pytest.raises(ValueError, match="gate_up"):
        sparse_matmul(_x(), {"w": _w()}, _cfg("dense", 4), dispatch=KERN,
                      epilogue=epilib.Epilogue(
                          epilib.EpilogueSpec(act="silu_mul")))


# ---------------------------------------------------------------------------
# gate-up dual kernel (silu_mul)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family,n", [
    ("dense", 4), ("compressed", 2), ("gather", 2),
])
def test_gate_up_fused_matches_two_singles(family, n):
    pg = _family_params(family, _w(seed=1), n)
    pu = _family_params(family, _w(seed=2), n)
    cfg = _cfg(family, n)
    x = _x()
    d = dispatch.plan(
        dispatch.GemmProblem(cfg.mode, b=B, ke=x.shape[1], o=O, n=n, m=4,
                             dtype=jnp.float32, epilogue="silu_mul", dual=True),
        dispatch=KERN)
    assert d.epilogue_fused, dispatch.describe(d)
    got = gate_up_matmul(x, pg, pu, cfg, dispatch=KERN)
    y_g = sparse_matmul(x, pg, cfg, dispatch=KERN)
    y_u = sparse_matmul(x, pu, cfg, dispatch=KERN)
    want = jax.nn.silu(y_g) * y_u
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("family", ["dense", "compressed", "gather"])
@pytest.mark.parametrize("qdtype", ["int8", "fp8"])
def test_gate_up_quantized_fused_matches_singles(family, qdtype):
    n = 2 if family != "dense" else 4
    pg = q.quantize_linear(_family_params(family, _w(seed=1), n), qdtype)
    pu = q.quantize_linear(_family_params(family, _w(seed=2), n), qdtype)
    cfg = _cfg(family, n)
    x = _x()
    got = gate_up_matmul(x, pg, pu, cfg, dispatch=KERN)
    y_g = sparse_matmul(x, pg, cfg, dispatch=KERN)
    y_u = sparse_matmul(x, pu, cfg, dispatch=KERN)
    want = jax.nn.silu(y_g) * y_u
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_gate_up_grad_falls_back_and_reads_x_once():
    """Under autodiff the dual kernel runs forward and its backward pass
    is the VJP of the jnp tier's two plain GEMMs (value parity with the
    reference)."""
    pg, pu = {"w": _w(seed=1)}, {"w": _w(seed=2)}
    cfg = _cfg("dense", 4)
    x = _x()

    def f(xx):
        return gate_up_matmul(xx, pg, pu, cfg, dispatch=KERN).sum()

    def f_ref(xx):
        return (jax.nn.silu(xx @ pg["w"]) * (xx @ pu["w"])).sum()

    got, want = np.asarray(jax.grad(f)(x)), np.asarray(jax.grad(f_ref)(x))
    scale = np.abs(want).max() + 1e-6
    np.testing.assert_allclose(got / scale, want / scale, atol=1e-5)


def test_gate_up_mismatched_pair_falls_back():
    # gate compressed, up dense: no dual plan, two singles, same value
    pg = _family_params("compressed", _w(seed=1), 2)
    pu = {"w": _w(seed=2)}
    cfg = _cfg("compressed", 2)
    got = gate_up_matmul(_x(), pg, pu, cfg, dispatch=KERN)
    y_g = sparse_matmul(_x(), pg, cfg, dispatch=KERN)
    y_u = sparse_matmul(_x(), pu, SparsityConfig(mode="dense"),
                        dispatch=KERN)
    want = jax.nn.silu(y_g) * y_u
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# fused requantize chain (producer emits the consumer's narrow operand)
# ---------------------------------------------------------------------------

def _consumer(qdtype, k=O, o=32, seed=9, act_scale=0.37):
    p = q.quantize_linear({"w": _w(k=k, o=o, seed=seed)}, qdtype)
    p[q.ACT_SCALE_KEY] = jnp.float32(act_scale)
    return p


@pytest.mark.parametrize("qdtype", ["int8", "fp8"])
def test_requant_chain_bit_matches_unfused_quantized_path(qdtype):
    """producer(epilogue gelu+requant) -> consumer(narrow x) must BIT-match
    producer(gelu, float out) -> consumer quantizing the float rows with
    its own static scale.  The fused cast and the consumer's quantize are
    the same formulation on the same fp32 rows."""
    prod = q.quantize_linear(_family_params("dense", _w(), 4), qdtype)
    cons = _consumer(qdtype)
    cfg = _cfg("dense", 4)
    x = _x()
    rq = dispatch.requant_plan(cons, (B,), SparsityConfig(mode="dense"),
                               dispatch=KERN)
    assert rq is not None
    rq_dt, rq_scale = rq
    assert rq_dt == q.quant_dtype(cons).name

    # fused: producer requantizes in its flush, consumer skips quantize
    h_q = sparse_matmul(x, prod, cfg, dispatch=KERN,
                        epilogue=epilib.make(act="gelu", requant=rq_dt,
                                             requant_scale=rq_scale))
    assert h_q.dtype == q.quant_dtype(cons)
    got = sparse_matmul(h_q, cons, SparsityConfig(mode="dense"),
                        dispatch=KERN)

    # unfused: float rows out, consumer's own static-scale quantize
    h_f = sparse_matmul(x, prod, cfg, dispatch=KERN,
                        epilogue=epilib.make(act="gelu"))
    want = sparse_matmul(h_f, cons, SparsityConfig(mode="dense"),
                         dispatch=KERN)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_requant_plan_declines_without_static_scales():
    dcfg = SparsityConfig(mode="dense")
    cons = q.quantize_linear({"w": _w(k=O, o=32)}, "int8")  # no act_scale
    assert dispatch.requant_plan(cons, (B,), dcfg, dispatch=KERN) is None
    # float consumer: nothing to requant to
    assert dispatch.requant_plan({"w": _w(k=O, o=32)}, (B,), dcfg,
                                 dispatch=KERN) is None
    # consumer routed to the jnp tier contracts float rows: no requant
    assert dispatch.requant_plan(_consumer("int8"), (B,), dcfg,
                                 dispatch=JNP) is None
    # and the fusible consumer accepts
    assert dispatch.requant_plan(_consumer("int8"), (B,), dcfg,
                                 dispatch=KERN) is not None


def test_pre_quantized_x_dequantizes_on_fallback():
    """A narrow x reaching a consumer whose decision is NOT a single
    kernel (here: backend=jnp) must be dequantized with the leaf's static
    scale, matching the float-rows path within quantization error."""
    cons = _consumer("int8")
    h = jax.random.normal(jax.random.PRNGKey(4), (B, O), jnp.float32)
    h_q, _ = q.quantize_rows_static(h, cons[q.ACT_SCALE_KEY], jnp.int8)
    got = sparse_matmul(h_q, cons, SparsityConfig(mode="dense"),
                        dispatch=JNP)
    # the fallback's contract: dequantize with the leaf's static scale,
    # then the ordinary float-rows reference — bit-exact by construction
    h_deq = h_q.astype(jnp.float32) * cons[q.ACT_SCALE_KEY]
    want = sparse_matmul(h_deq, cons, SparsityConfig(mode="dense"),
                         dispatch=JNP)
    assert np.array_equal(np.asarray(got), np.asarray(want))

    # and a dtype-mismatched narrow x is an error, not a silent cast
    with pytest.raises(ValueError, match="storage dtype"):
        sparse_matmul(h_q, q.quantize_linear({"w": _w(k=O, o=32)}, "fp8"),
                      SparsityConfig(mode="dense"), dispatch=KERN)


# ---------------------------------------------------------------------------
# model-level: apply_gate_up / apply_mlp parity
# ---------------------------------------------------------------------------

def test_apply_gate_up_matches_two_apply_linear():
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    pg = _family_params("compressed", _w(seed=1), 2)
    pu = _family_params("compressed", _w(seed=2), 2)
    x = _x()
    got = apply_gate_up(pg, pu, x, cfg)
    want = jax.nn.silu(apply_linear(pg, x, cfg)) * apply_linear(pu, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6)


def test_bf16_requant_site_keeps_activation_dtype():
    """serving --quantize int8 --static-scales runs the model in bf16;
    the fused requant chain hands w_out pre-quantized rows, which
    dequantize to fp32 (the scale dtype) — the MLP must return the
    residual stream's own dtype, or the jitted decode loop dies on a
    scan carry dtype mismatch (regression: launch.serve smoke)."""
    from repro.models.layers import apply_mlp, init_mlp

    cfg = SparsityConfig(n=4, m=4, mode="dense")
    p = init_mlp(jax.random.PRNGKey(0), 64, 128, "swiglu", cfg,
                 jnp.bfloat16)
    qp = {k: q.quantize_linear(v, "int8") for k, v in p.items()}
    for v in qp.values():
        v[q.ACT_SCALE_KEY] = jnp.float32(0.05)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64), jnp.bfloat16)
    with dispatch.use_dispatch(backend="interpret"):
        rq = dispatch.requant_plan(qp["w_out"], x.shape[:-1], cfg)
        assert rq is not None and rq[0] == "int8"   # chain engages
        y = apply_mlp(qp, x, "swiglu", cfg)
    assert y.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(y, np.float32)).all()


def test_apply_mlp_swiglu_unchanged_by_rewire():
    from repro.models.layers import apply_mlp, init_mlp
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    p = init_mlp(jax.random.PRNGKey(0), 64, 128, "swiglu", cfg,
                 jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 8, 64), jnp.float32)
    got = apply_mlp(p, x, "swiglu", cfg)
    h = apply_linear(p["w_in"], x, cfg)
    gt = apply_linear(p["w_gate"], x, cfg)
    want = apply_linear(p["w_out"], jax.nn.silu(gt) * h, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)
