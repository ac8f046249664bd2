"""Per-kernel shape/dtype sweeps vs the ref.py pure-jnp oracles
(interpret mode = kernel body executed on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import nm
from repro.kernels.tile_gemm.kernel import tile_gemm
from repro.kernels.tile_gemm.ref import tile_gemm_ref
from repro.kernels.nm_spmm.kernel import nm_spmm
from repro.kernels.nm_spmm.ref import nm_spmm_ref
from repro.kernels.nm_spmm_gather.kernel import nm_spmm_gather
from repro.kernels.nm_spmm_gather.ref import nm_spmm_gather_ref
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import attention_ref


def _allclose(got, want, rtol=2e-6):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = np.abs(want).max() + 1e-6
    np.testing.assert_allclose(got / scale, want / scale, atol=rtol)


# fast lane keeps one representative shape per kernel; the larger
# interpret-mode sweeps are emulation-bound and run in the slow lane
_BIG = pytest.mark.slow


@pytest.mark.parametrize("b,k,o", [
    (128, 512, 128),
    pytest.param(256, 1024, 256, marks=_BIG),
    pytest.param(128, 2048, 384, marks=_BIG),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_tile_gemm_sweep(b, k, o, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (b, k), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, o), jnp.float32).astype(dtype)
    got = tile_gemm(x, w, interpret=True)
    _allclose(got, tile_gemm_ref(x, w))


@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("b,ke,o", [
    (128, 512, 128),
    pytest.param(256, 1024, 256, marks=_BIG),
    pytest.param(128, 2048, 128, marks=_BIG),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_nm_spmm_sweep(n, b, ke, o, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (b, ke), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (ke, o), jnp.float32).astype(dtype)
    pruned, _ = nm.prune_nm(w, n, 4)
    c = nm.compress_nm(pruned, n, 4)
    pm = nm.pack_meta(c.meta)
    got = nm_spmm(x, c.values, pm, n, interpret=True)
    _allclose(got, nm_spmm_ref(x, c.values, pm, n))
    # also exact vs the dense-pruned matmul (lossless end to end)
    _allclose(got, jnp.dot(x, pruned, preferred_element_type=jnp.float32))


@pytest.mark.slow
def test_nm_spmm_block_shapes():
    """Block-shape sweep: result must be invariant to tiling choices."""
    n = 2
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 1024), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (1024, 256), jnp.float32)
    pruned, _ = nm.prune_nm(w, n, 4)
    c = nm.compress_nm(pruned, n, 4)
    pm = nm.pack_meta(c.meta)
    ref = nm_spmm_ref(x, c.values, pm, n)
    for bb, bo, bke in [(128, 128, 512), (256, 256, 1024), (64, 128, 256), (256, 64, 128)]:
        got = nm_spmm(x, c.values, pm, n, block_b=bb, block_o=bo, block_k=bke,
                      interpret=True)
        _allclose(got, ref, rtol=1e-5)


@pytest.mark.parametrize("n", [1, 2])
@pytest.mark.parametrize("b,ke,o", [
    (128, 512, 128),
    pytest.param(256, 1024, 256, marks=_BIG),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_nm_spmm_gather_sweep(n, b, ke, o, dtype):
    kc = ke * n // 4
    vals = jax.random.normal(jax.random.PRNGKey(0), (kc, o), jnp.float32).astype(dtype)
    # random but canonical (sorted within block) shared metadata
    key = jax.random.PRNGKey(42)
    idx = jax.vmap(lambda k: jax.random.choice(k, 4, (n,), replace=False))(
        jax.random.split(key, kc // n)
    )
    idx = jnp.sort(idx, axis=1).reshape(kc).astype(jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, ke), jnp.float32).astype(dtype)
    got = nm_spmm_gather(x, vals, idx, n, interpret=True)
    _allclose(got, nm_spmm_gather_ref(x, vals, idx, n), rtol=1e-5)


@pytest.mark.parametrize("t,d", [
    (256, 64),
    pytest.param(512, 128, marks=_BIG),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(t, d, causal):
    b, hq, hkv = 2, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, t, d), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, t, d), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, t, d), jnp.float32).astype(jnp.bfloat16)
    got = flash_attention_op(q, k, v, causal=causal, block_q=128, block_k=128,
                             interpret=True)
    rep = hq // hkv
    kr = jnp.repeat(k, rep, axis=1).reshape(b * hq, t, d)
    vr = jnp.repeat(v, rep, axis=1).reshape(b * hq, t, d)
    want = attention_ref(q.reshape(b * hq, t, d), kr, vr, causal=causal)
    err = np.abs(np.asarray(got, np.float32).reshape(b * hq, t, d)
                 - np.asarray(want, np.float32)).max()
    assert err < 2e-2, err  # bf16 attention tolerance
