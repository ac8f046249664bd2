"""What the benchmark cells plan, pinned at their own shapes.

A cell's time follows from what ``plan()`` decides at each linear site:
which registry entry runs, its blocks, where it is placed, whether the
epilogue is fused and which M:1 mux the 2:4 kernel uses.  These cases
pin those decisions at the cells' shapes, so a change to the kernel
table or its fitting rules that would move a cell's time fails here
first, on the CPU.

internlm2-1.8B (hidden 2048, 8 KV heads of 128, SwiGLU 8192): every
linear site at the decode batch of 16 slots and at the prefill chunk of
128 rows, dense and 2:4 in bfloat16 and each in its int8 control.
Mistral-Large-Instruct-2407 (hidden 12288, 8 KV heads of 128, SwiGLU
28672) over a (1, 4) mesh: each site's per-chip local problem at b=16,
selected as the ``shard_map`` body selects it; column-parallel sites
split the out dim 4 ways, row-parallel sites split K 4 ways.
"""

import jax.numpy as jnp
import pytest

from repro.kernels import dispatch, registry

TPU = dispatch.DispatchConfig(backend="tpu")

# site -> (in features, out features); the gate-up pair plans as one
# fused dual problem (silu_mul flush)
INTERNLM2_SITES = {"wq": (2048, 2048), "wk": (2048, 1024),
                   "wv": (2048, 1024), "wo": (2048, 2048),
                   "gate_up": (2048, 8192), "w_down": (8192, 2048)}

# (layout, n, dtype, b) -> (entry, blocks, mux): every site of one
# configuration plans the same entry and blocks
INTERNLM2_PLANS = {
    ("dense", 4, "bfloat16", 16): ("tile_gemm", (16, 512, 128), None),
    ("dense", 4, "bfloat16", 128): ("tile_gemm", (128, 512, 128), None),
    ("dense", 4, "int8", 16): ("tile_gemm_int8", (32, 512, 128), None),
    ("dense", 4, "int8", 128): ("tile_gemm_int8", (128, 512, 128), None),
    ("compressed", 2, "bfloat16", 16): ("nm_spmm", (16, 512, 128), "slab"),
    ("compressed", 2, "bfloat16", 128): ("nm_spmm", (128, 512, 128), "slab"),
    ("compressed", 2, "int8", 16): ("nm_spmm_int8", (32, 512, 128), "slab"),
    ("compressed", 2, "int8", 128): ("nm_spmm_int8", (128, 512, 128),
                                     "slab"),
}


@pytest.mark.parametrize("site", sorted(INTERNLM2_SITES))
@pytest.mark.parametrize("layout,n,dtype,b", sorted(INTERNLM2_PLANS))
def test_internlm2_site_plan(layout, n, dtype, b, site):
    k, o = INTERNLM2_SITES[site]
    dual = site == "gate_up"
    d = dispatch.plan(
        dispatch.GemmProblem(layout, b=b, ke=k, o=o, n=n, m=4,
                             dtype=jnp.dtype(dtype),
                             epilogue="silu_mul" if dual else None,
                             dual=dual),
        dispatch=TPU)
    entry, blocks, mux = INTERNLM2_PLANS[(layout, n, dtype, b)]
    assert (d.kernel, d.blocks, d.placement, d.epilogue_fused, d.mux) == (
        entry, blocks, "single", dual, mux), dispatch.describe(d)


H, F = 12288, 28672
COL, ROW = (1, 1, 4), (1, 4, 1)
# site -> (in features, out features, mesh split of (b, ke, o)); under
# shard_map the gate and up projections run as two GEMMs
MISTRAL_SITES = {"wq": (H, H, COL), "wk": (H, 1024, COL),
                 "wv": (H, 1024, COL), "wo": (H, H, ROW),
                 "w_gate": (H, F, COL), "w_up": (H, F, COL),
                 "w_down": (F, H, ROW)}
MISTRAL_PLANS = {"bfloat16": ("nm_spmm", (16, 512, 128)),
                 "int8": ("nm_spmm_int8", (32, 512, 128))}


@pytest.mark.parametrize("site", sorted(MISTRAL_SITES))
@pytest.mark.parametrize("dtype", sorted(MISTRAL_PLANS))
def test_mistral_local_problem_selects(dtype, site):
    k, o, shards = MISTRAL_SITES[site]
    entry, blocks = registry.select(
        "compressed", b=16, ke=k, o=o, n=2, m=4, dtype=jnp.dtype(dtype),
        backend="tpu", shards=shards)
    assert (entry.name, blocks) == MISTRAL_PLANS[dtype]
