"""Lane-aligned N:M SPMM with a *reduced* contraction dim — Tier 2 (beyond paper).

When the 2-bit metadata is shared across all output channels of a weight
tile ("lane-aligned" / vector-wise N:M, Zhu et al. [55]), the activation
can be gathered once per compressed K position and the matmul runs at
``K_c = K_eff * N / M``: the MXU does **N/M of the dense FLOPs** — the
TPU-native realization of "map only nonzeros onto the MACs".

Computes ``Y (B, O) = X (B, K_eff) @ dec(V, idx)`` from
  x: (B, K_eff)       activations, natural row-major layout
  values: (K_c, O)    compressed weights
  idx: (K_c,) int32   shared in-block indices

Activations and output keep their natural layouts in HBM: each
``(BB, BKe)`` x tile is transposed **in VMEM** so the gather runs along
sublanes, the ``(BO, BB)`` accumulator is transposed back on the flush,
and no permuted copy of either ever exists in HBM (DARE's
densifying-gather treatment).  The sublane gather is ≤4 compare+selects
per compressed row (the input selector of the paper's Fig. 8 moved from
silicon to the VPU), exact for every value dtype.  The grid, the
operand order and the forms (fused gate-up, quantized, raw accumulator,
block skip, epilogue) are ``repro.kernels.gemm``'s.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import gemm


def _gather(xt: jax.Array, idx: jax.Array, n: int) -> jax.Array:
    """``(BKe, BB)`` candidates -> the ``(BKc, BB)`` rows the ``(BKc, 1)``
    indices select, in block-major compressed order."""
    bke, bb = xt.shape
    nb = bke // 4
    x3 = xt.reshape(nb, 4, bb)           # candidates per block
    i3 = idx.reshape(nb, n, 1)
    slices = []
    for s in range(n):
        i_s = i3[:, s, :]                # (nb, 1)
        acc = jnp.zeros((nb, bb), xt.dtype)
        for j in range(4):
            acc = acc + jnp.where(i_s == j, x3[:, j, :], jnp.zeros_like(acc))
        slices.append(acc)
    return jnp.stack(slices, axis=1).reshape(nb * n, bb)


def _contract(r, n: int, acc_dtype):
    """One K step: transpose the x tile once, gather it through each
    weight's index stream, and accumulate the reduced-K ``vᵀ @ x_g``."""
    xt = r.x[...].T
    for acc, (v, idx) in zip(r.acc, r.w):
        x_g = _gather(xt, idx[...], n)
        # (BKc, BO)^T contract (BKc, BB) -> (BO, BB): reduced-K MXU matmul
        acc[...] += jax.lax.dot_general(
            v[...], x_g, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=acc_dtype)


def _ws_xs(t, xs, ws_ref):
    # this family's rounding order: (t * ws) * xs
    return t * ws_ref[...] * xs


def nm_spmm_gather(x: jax.Array, values: jax.Array, idx: jax.Array, n: int,
                   *, up=None, block_b: int = 128, block_k: int = 512,
                   block_o: int = 128, **form) -> jax.Array:
    """Y (B, O) = X (B, K_eff) @ dec(values, idx).  M fixed at 4.

    ``up=(values_up, idx_up)`` fuses the gate-up over two weights with
    their own index streams (the gather runs twice, x is read once).
    ``form``: the keyword forms of :func:`repro.kernels.gemm.spec`.
    """
    b, ke = x.shape
    kc, o = values.shape
    assert ke * n == kc * 4, (x.shape, values.shape, n)
    idx = idx.reshape(-1, 1)
    if up is not None:
        up = (up[0], up[1].reshape(-1, 1))
    for v, i in [(values, idx)] + ([up] if up else []):
        assert v.shape == (kc, o) and i.shape == (kc, 1), (v.shape, i.shape)
    bks = gemm.blocks(b, ke, o, block_b, block_k, block_o)
    bkc = bks[1] * n // 4
    kernel, kw, operands = gemm.spec(
        "nm_spmm_gather", lambda r, acc_dtype: _contract(r, n, acc_dtype),
        x, (values, idx), [(bkc, True), (bkc, False)], bks, up=up,
        acc_t=True, dequant=_ws_xs, **form)
    return pl.pallas_call(kernel, **kw)(*operands)
