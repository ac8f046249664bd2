"""Mosaic compile tests: the serving path's kernels at internlm2-1.8B
widths, compiled for a described TPU v5e (nothing runs).

Interpret mode accepts block shapes and in-kernel ops that the TPU
compiler refuses; these tests compile each kernel the dispatch engine
plans for the model's linear sites — wq (2048->2048), wk/wv
(2048->1024), the gate-up input (2048->8192), w_out (8192->2048) and the
2048->92544 unembed — with ``interpret=False`` against one chip of a
``v5e:2x2`` topology, and require a Mosaic kernel (``tpu_custom_call``)
in the compiled program.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import quantize as q
from repro.core.sparse_linear import SparsityConfig, init_linear
from repro.kernels import dispatch
from repro.kernels.actsparse import ActivationSpec

D_MODEL, D_KV, D_FF, VOCAB = 2048, 1024, 8192, 92544
SITES = {"wq": (D_MODEL, D_MODEL), "wk": (D_MODEL, D_KV),
         "gate_up": (D_MODEL, D_FF), "w_out": (D_FF, D_MODEL),
         "unembed": (D_MODEL, VOCAB)}
DECODE_B, PREFILL_B = 8, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the compiler logs nowhere, and no compile lands in a persistent
        # cache that only a chip could read back
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            jax.config.update("jax_enable_compilation_cache", cache_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        with dispatch.use_dispatch(backend="tpu"):
            yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_on)


def _linear(sharding, k, o, layout, n, qdtype):
    cfg = (SparsityConfig(mode="dense") if layout == "dense"
           else SparsityConfig(n=n, m=4, mode=layout))
    p = jax.eval_shape(lambda key: init_linear(key, k, o, cfg, jnp.bfloat16),
                       jax.random.PRNGKey(0))
    if qdtype is not None:
        p = jax.eval_shape(lambda leaf: q.quantize_linear(leaf, qdtype), p)
    return cfg, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), p)


def _acts(sharding, b, k):
    return jax.ShapeDtypeStruct((b, k), jnp.bfloat16, sharding=sharding)


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_texts(fn, *args):
    """Each Pallas call of the compiled program as a profiler trace names
    it: its HLO instruction with the operand shapes inline (which
    ``as_text`` leaves out)."""
    from jax._src.lib import xla_client as xc

    opts = xc._xla.HloPrintOptions()
    opts.print_operand_shape = True
    exe = jax.jit(fn).lower(*args).compile().runtime_executable()
    return [ln.strip().removeprefix("ROOT ")
            for m in exe.hlo_modules() for ln in m.to_string(opts).splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


@pytest.mark.parametrize("layout,n,qdtype,kernel", [
    ("dense", 4, None, "tile_gemm"),
    ("dense", 4, "int8", "tile_gemm_int8"),
    ("compressed", 2, None, "nm_spmm"),
    ("compressed", 2, "int8", "nm_spmm_int8"),
    ("compressed", 1, None, "nm_spmm"),
    ("compressed", 1, "int8", "nm_spmm_int8"),
])
def test_linear_sites_compile(one_chip, layout, n, qdtype, kernel):
    """Every linear site at decode (b=8) and prefill-chunk (b=64) batch
    plans the expected kernel, and Mosaic compiles it."""
    for b in (DECODE_B, PREFILL_B):
        for site, (k, o) in SITES.items():
            cfg, p = _linear(one_chip, k, o, layout, n, qdtype)
            d = dispatch.plan_for(p, (b, k), cfg,
                                  dtype=q.quant_dtype(p) or jnp.bfloat16)
            assert d.kernel == kernel and d.backend == "tpu", (site, d)
            text = _compile(
                lambda x, pp: dispatch.sparse_matmul(x, pp, cfg),
                _acts(one_chip, b, k), p)
            assert "tpu_custom_call" in text, (site, b)


@pytest.mark.parametrize("layout,n,qdtype", [
    ("dense", 4, None),
    ("compressed", 2, None),
    ("compressed", 2, "int8"),
])
def test_dual_gate_up_compiles(one_chip, layout, n, qdtype):
    """The fused gate-up kernel (one activation read, silu(g) * u flush)
    at d_model -> d_ff, decode and prefill-chunk batches."""
    cfg, pg = _linear(one_chip, D_MODEL, D_FF, layout, n, qdtype)
    _, pu = _linear(one_chip, D_MODEL, D_FF, layout, n, qdtype)
    for b in (DECODE_B, PREFILL_B):
        text = _compile(
            lambda x, g, u: dispatch.gate_up_matmul(x, g, u, cfg),
            _acts(one_chip, b, D_MODEL), pg, pu)
        assert text.count("tpu_custom_call") >= 1, b


def test_masked_block_skip_compiles(one_chip):
    """The activation-sparsity variant (scalar-prefetched block maps) of
    the 2:4 kernel at the w_out site."""
    cfg, p = _linear(one_chip, D_FF, D_MODEL, "compressed", 2, None)
    act = ActivationSpec("threshold", threshold=0.5)
    d = dispatch.plan(
        dispatch.GemmProblem("compressed", b=DECODE_B, ke=D_FF, o=D_MODEL,
                             n=2, m=4, dtype=jnp.bfloat16,
                             activation=act.point))
    assert d.activation_skip, dispatch.describe(d)
    text = _compile(
        lambda x, pp: dispatch.sparse_matmul(x, pp, cfg, activation=act),
        _acts(one_chip, DECODE_B, D_FF), p)
    assert "tpu_custom_call" in text


def test_row_blocks_stay_on_the_sublane_quantum(one_chip):
    """A batch above the 128-row cap tiles on the 8-row sublane quantum
    (b=200 -> 40 rows, not 100), which Mosaic compiles."""
    cfg, p = _linear(one_chip, D_MODEL, D_MODEL, "compressed", 2, None)
    d = dispatch.plan_for(p, (200, D_MODEL), cfg, dtype=jnp.bfloat16)
    assert d.blocks[0] == 40, d
    text = _compile(lambda x, pp: dispatch.sparse_matmul(x, pp, cfg),
                    _acts(one_chip, 200, D_MODEL), p)
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    """Prefill-chunk attention (16 query heads over 8 KV heads, head_dim
    128) through the engine's flash kernel."""
    qg = jax.ShapeDtypeStruct((1, 8, 2, PREFILL_B, 128), jnp.bfloat16,
                              sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, PREFILL_B, 8, 128), jnp.bfloat16,
                              sharding=one_chip)
    text = _compile(
        lambda a, k, v: dispatch.attention(a, k, v, causal=True, chunk=64),
        qg, kv, kv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("layout,n,qdtype,entry", [
    ("dense", 4, None, "tile_gemm"),
    ("compressed", 2, None, "nm_spmm"),
    ("compressed", 2, "int8", "nm_spmm_int8"),
])
def test_kernels_are_named_in_the_hlo(one_chip, layout, n, qdtype, entry):
    """A trace names each Pallas call by its HLO text: the custom call
    takes the kernel's name, and its ``kernel_metadata`` the registry
    entry that runs it, single and fused gate-up alike."""
    meta = re.compile(r'kernel_metadata=\{\s*"kernel":"(\w+)"\s*\}')
    cfg, p = _linear(one_chip, D_MODEL, D_MODEL, layout, n, qdtype)
    text = _compile(lambda x, pp: dispatch.sparse_matmul(x, pp, cfg),
                    _acts(one_chip, DECODE_B, D_MODEL), p)
    family = entry.removesuffix("_int8")
    assert re.search(rf"%{family}\.\d+ = \S+ custom-call\(", text), entry
    assert meta.findall(text) == [entry]
    cfg, pg = _linear(one_chip, D_MODEL, D_FF, layout, n, qdtype)
    _, pu = _linear(one_chip, D_MODEL, D_FF, layout, n, qdtype)
    text = _compile(
        lambda x, g, u: dispatch.gate_up_matmul(x, g, u, cfg),
        _acts(one_chip, DECODE_B, D_MODEL), pg, pu)
    assert re.search(rf"%{family}_dual\.\d+ = \S+ custom-call\(", text)
    assert meta.findall(text) == [entry]


@pytest.mark.parametrize("n", [2, 1])
def test_nm_spmm_sites_read_as_the_benchmark_reads_them(one_chip, n):
    """Every ``nm_spmm`` site (single and fused gate-up, decode and
    prefill-chunk batch) compiles to ONE Pallas call — the slab mux's
    activation permutation is plain XLA, not a second kernel — that
    exactly ``bench/kernels/linear.json`` claims, and whose operands
    ``bench.trace.linear_counts`` reads as the activation, ``(K_c, O)``
    values and ``(K_c/4, O)`` packed meta."""
    from bench import trace
    from bench.harness import HERE

    classes = trace.load_classes(HERE / "kernels")
    for b in (DECODE_B, PREFILL_B):
        calls = []
        for site, (k, o) in SITES.items():
            cfg, p = _linear(one_chip, k, o, "compressed", n, None)
            calls.append((site, k, o, 1, _kernel_texts(
                lambda x, pp: dispatch.sparse_matmul(x, pp, cfg),
                _acts(one_chip, b, k), p)))
        cfg, pg = _linear(one_chip, D_MODEL, D_FF, "compressed", n, None)
        _, pu = _linear(one_chip, D_MODEL, D_FF, "compressed", n, None)
        calls.append(("gate_up dual", D_MODEL, D_FF, 2, _kernel_texts(
            lambda x, g, u: dispatch.gate_up_matmul(x, g, u, cfg),
            _acts(one_chip, b, D_MODEL), pg, pu)))
        for site, k, o, weights, texts in calls:
            assert len(texts) == 1, (site, b, texts)
            cls = trace.classify(trace.Op(texts[0], 0, 1), classes)
            assert cls is not None and cls["class"] == "linear", (site, b)
            kc = k * n // 4
            assert trace.linear_counts(texts[0]) == (
                2 * b * weights * kc * o,
                b * k * 2 + weights * (kc * o * 2 + kc // 4 * o)
                + b * o * 2), (site, b, texts[0][:300])
