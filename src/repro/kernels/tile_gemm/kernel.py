"""Dense tile GEMM Pallas kernel — the TILE_GEMM / VEGETA-D baseline.

C (B, O) += X (B, K) @ W (K, O), blocked for VMEM, with the accumulator
tile held in VMEM across the K grid (the "output forwarding"
adaptation: the C tile never round-trips to HBM between accumulating
steps — see ``docs/paper-mapping.md``).

Quantized weights are the VNNI lineage: int8 x int8 tiles contract into
an **int32** accumulator, fp8 (e4m3fn) x fp8 into an **fp32** one
(``preferred_element_type``), and the output is dequantized once, on
the flush, with the per-row activation and per-channel weight scales.
The grid, the operand order and the forms (fused gate-up, quantized,
raw accumulator, block skip, epilogue) are ``repro.kernels.gemm``'s.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import gemm


def _contract(r, acc_dtype):
    """One K step: ``x @ w`` into each accumulator; a fused gate-up reads
    the x tile once for both weights."""
    xv = r.x[...] if len(r.acc) == 2 else None
    for acc, (w,) in zip(r.acc, r.w):
        acc[...] += jnp.dot(r.x[...] if xv is None else xv, w[...],
                            preferred_element_type=acc_dtype)


def tile_gemm(x: jax.Array, w: jax.Array, *, up=None, block_b: int = 128,
              block_k: int = 512, block_o: int = 128, **form) -> jax.Array:
    """Y = X (B, K) @ W (K, O); ``up=(w_up,)`` fuses the gate-up
    ``silu(x @ w) * (x @ w_up)``.  ``form``: the keyword forms of
    :func:`repro.kernels.gemm.spec` (scales, ``raw``, block-skip maps,
    ``out_dtype``, ``interpret``, epilogue)."""
    k, o = w.shape
    assert x.shape[1] == k and (up is None or up[0].shape == w.shape), (
        x.shape, w.shape)
    bks = gemm.blocks(x.shape[0], k, o, block_b, block_k, block_o)
    kernel, kw, operands = gemm.spec("tile_gemm", _contract, x, (w,),
                                     [(bks[1], True)], bks, up=up, **form)
    return pl.pallas_call(kernel, **kw)(*operands)
