"""The nm_spmm family's M:1 mux, in interpret mode, against
``repro.core.nm`` decompression followed by a jnp matmul.

``n`` dividing 4 takes the slab mux (aligned per-field slabs, K permuted
inside each tile, the activation permuted to match); any other ``n``
keeps the row-repeat mux.  Every form (plain, activation-masked, fused
gate-up, raw accumulator) and every value dtype shares the one
expansion, so each case runs one of them end to end.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core import nm
from repro.kernels import dispatch
from repro.kernels.actsparse import block_maps
from repro.kernels.nm_spmm.kernel import mux_form, nm_spmm, slab_order

FP8 = jnp.float8_e4m3fn
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8,
          "fp8": FP8}
_FORMS = ([(f, d) for f in ("plain", "masked", "dual") for d in DTYPES]
          + [("raw", "int8"), ("raw", "fp8")])
# every (n, form, dtype), with batch and K block cycling through
# {8, 16, 128} x {256, 512}
CASES = [(n, form, dt, (8, 16, 128)[i % 3], (256, 512)[i % 2])
         for i, (n, (form, dt)) in enumerate(
             itertools.product((1, 2, 4, 3), _FORMS))]
O = 128


def _weights(key, ke, n, dt):
    """Compressed N:M values in ``dt`` (exact: pruned and compressed in
    f32 from values ``dt`` represents) and packed meta."""
    w = jax.random.normal(key, (ke, O), jnp.float32)
    if dt == "int8":
        w = jnp.clip(jnp.round(w * 30), -127, 127)
    w = w.astype(DTYPES[dt]).astype(jnp.float32)
    c = nm.compress_nm(nm.prune_nm(w, n, 4)[0], n, 4)
    return c.values.astype(DTYPES[dt]), nm.pack_meta(c.meta)


def _acts(key, b, ke, dt, masked, bke):
    x = jax.random.normal(key, (b, ke), jnp.float32)
    if dt == "int8":
        x = jnp.clip(jnp.round(x * 30), -127, 127)
    if masked:
        # the second K block of every row is dead
        x = x.at[:, bke:2 * bke].set(0.0)
    return x.astype(DTYPES[dt])


def _dense(values, pm, n):
    return nm.decompress(values.astype(jnp.float32), nm.unpack_meta(pm),
                         n, 4)


def _mm(x, w):
    return jnp.dot(x.astype(jnp.float32), w,
                   precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize(
    "n,form,dt,b,bke", CASES,
    ids=[f"{f}-{d}-n{n}-b{b}-ke{k}" for n, f, d, b, k in CASES])
def test_mux_matches_decompress_then_matmul(n, form, dt, b, bke):
    assert mux_form(n) == ("slab" if n in (1, 2, 4) else "rows")
    ke = 3 * bke if form == "masked" else 2 * bke
    ks = jax.random.split(jax.random.PRNGKey(n * 100 + bke + b), 5)
    quant = dt in ("int8", "fp8")
    v, pm = _weights(ks[0], ke, n, dt)
    x = _acts(ks[1], b, ke, dt, form == "masked", bke)
    xs = jax.random.uniform(ks[2], (b, 1), jnp.float32, 0.5, 1.5)
    ws = jax.random.uniform(ks[3], (1, O), jnp.float32, 0.5, 1.5)
    acc = jnp.int32 if dt == "int8" else jnp.float32
    blocks = dict(block_b=b, block_o=O, block_k=bke, interpret=True)
    scales = dict(x_scale=xs, w_scale=ws) if quant else {}
    want = _mm(x, _dense(v, pm, n))
    if form == "raw":
        got = nm_spmm(x, v, pm, n, raw=True, **blocks)
        assert got.dtype == acc
        if dt == "int8":  # the int32 accumulator is exact
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            return
    elif quant:
        want = want * xs * ws
    if form == "plain":
        got = nm_spmm(x, v, pm, n, **scales, **blocks)
    elif form == "masked":
        kmap, kmask = block_maps(x, b, bke)
        assert int(kmask.sum()) == kmask.size - kmask.shape[0]
        got = nm_spmm(x, v, pm, n, kmap=kmap, kmask=kmask, **scales,
                      **blocks)
    elif form == "dual":
        vu, pmu = _weights(ks[4], ke, n, dt)
        up = _mm(x, _dense(vu, pmu, n))
        if quant:
            wsu = ws[:, ::-1]
            up = up * xs * wsu
            scales["up_scale"] = wsu
        got = nm_spmm(x, v, pm, n, up=(vu, pmu), **scales, **blocks)
        want = jax.nn.silu(want) * up
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = np.abs(want).max()
    np.testing.assert_allclose(got / scale, want / scale, atol=1e-5)


@pytest.mark.parametrize("n", [1, 2, 4, 3])
@pytest.mark.parametrize("dt", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("bke", [256, 512])
def test_slab_order_is_a_tile_local_permutation(n, dt, bke):
    """The activation permutation only reorders K inside each K tile,
    is the identity on the rows path, and is undone by its inverse."""
    ke = 3 * bke
    idx = jnp.arange(ke, dtype=jnp.int32)[None, :]
    perm = np.asarray(slab_order(idx, n, bke, DTYPES[dt]))[0]
    if mux_form(n) == "rows":
        np.testing.assert_array_equal(perm, np.arange(ke))
        return
    # dense row P*i + F*v + t of a tile sits at F*(v*S + i) + t, with P
    # = 16/n dense rows per meta row, F values per 32-bit word, S = bke/P
    p, f = 16 // n, 4 // jnp.dtype(DTYPES[dt]).itemsize
    s = bke // p
    want = np.empty(bke, np.int64)
    for i, v, t in itertools.product(range(s), range(p // f), range(f)):
        want[f * (v * s + i) + t] = p * i + f * v + t
    for k in range(3):
        np.testing.assert_array_equal(perm[k * bke:(k + 1) * bke],
                                      want + k * bke)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, ke), jnp.float32)
    px = np.asarray(slab_order(x, n, bke, DTYPES[dt]))
    np.testing.assert_array_equal(px, np.asarray(x)[:, perm])
    np.testing.assert_array_equal(px[:, np.argsort(perm)], np.asarray(x))


def test_dispatch_names_the_mux_form():
    """Decisions and their text name the mux (``n`` picks it: no flag),
    and the report counts plan lines by form."""
    for n, form in ((1, "slab"), (2, "slab"), (4, "slab"), (3, "rows")):
        d = dispatch.plan(dispatch.GemmProblem(
            "compressed", b=16, ke=768, o=256, n=n, m=4,
            dtype=jnp.bfloat16), dispatch=dispatch.DispatchConfig(
                backend="interpret"))
        assert d.kernel == "nm_spmm" and d.mux == form, d
        assert f" mux={form}" in dispatch.describe(d)
    d = dispatch.plan(dispatch.GemmProblem(
        "dense", b=16, ke=768, o=256, dtype=jnp.bfloat16),
        dispatch=dispatch.DispatchConfig(backend="interpret"))
    assert d.mux is None and "mux=" not in dispatch.describe(d)


def test_internlm2_2of4_plans_the_slab_mux_everywhere():
    """internlm2-1.8B 2:4, as the benchmark serves it: every decode and
    prefill linear site plans ``mux=slab`` at published widths, and the
    prepared model's dispatch report says so line by line."""
    from repro import serving
    from repro.analysis import audit_model
    from repro.models import init_params

    spec = serving.ServingSpec(layout="compressed", sparsity=(2, 4),
                               backend="tpu", slots=16, max_len=800,
                               block_len=16, prefill_chunk=128)
    audit = audit_model(get_config("internlm2_1_8b"), spec)
    sites = [s for s in audit.sites if s.phase in ("decode", "prefill")
             and not s.path.startswith("attention/")
             and s.decision.mode == "compressed"]
    assert sites
    assert {s.decision.mux for s in sites} == {"slab"}, [
        dispatch.describe(s.decision) for s in sites]

    small = serving.ServingSpec(layout="compressed", sparsity=(2, 4),
                                backend="interpret", slots=4, max_len=64,
                                block_len=8, prefill_chunk=8)
    cfg = small.apply_to(get_smoke_config("internlm2_1_8b"))
    prepared = serving.prepare(init_params(jax.random.PRNGKey(0), cfg),
                               small, cfg=cfg)
    report = prepared.dispatch_report()
    plans = [ln for ln in report if "nm_spmm[" in ln]
    assert plans and all(" mux=slab" in ln for ln in plans), report
    assert f"  nm_spmm mux: {len(plans)} slab / 0 rows site(s)" in report
