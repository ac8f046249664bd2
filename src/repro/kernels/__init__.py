"""Pallas TPU kernels for the perf-critical hot spots.

- ``tile_gemm``: dense blocked GEMM (TILE_GEMM / VEGETA-D baseline)
- ``nm_spmm``: Tier-1 N:M SPMM, in-VMEM decompress (TILE_SPMM_{U,V})
- ``nm_spmm_gather``: Tier-2 lane-aligned reduced-K SPMM (beyond paper)
- ``flash_attention``: chunked online-softmax attention

Each GEMM family builds one ``pallas_call`` on the grid of ``gemm``;
its forms (float or quantized, single or fused gate-up, raw
accumulator, activation block skip, epilogue) are arguments.  All are
validated against ``ref.py`` oracles in interpret mode (CPU); on-TPU
execution uses the same ``pallas_call`` with ``interpret=False``.

The kernels are not called directly by models: the **dispatch engine**
(``registry`` + ``dispatch``) is the single entry point — it maps
``(mode, shape, N:M, dtype, backend)`` to a kernel (or to the jnp
reference formulation) and owns block-size autotuning (``autotune``).
"""

from repro.kernels.dispatch import (  # noqa: F401
    DispatchConfig,
    DispatchDecision,
    ShardSpec,
    attention,
    describe,
    plan,
    plan_for,
    shard_spec_from_env,
    sparse_matmul,
    use_dispatch,
)
from repro.kernels.reasons import ReasonCode, Severity  # noqa: F401
from repro.kernels.registry import detect_backend, select  # noqa: F401
