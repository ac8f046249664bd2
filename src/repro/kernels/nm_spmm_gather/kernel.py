"""Lane-aligned N:M SPMM with a *reduced* contraction dim — Tier 2 (beyond paper).

When the 2-bit metadata is shared across all output channels of a weight
tile ("lane-aligned" / vector-wise N:M, Zhu et al. [55]), the activation
can be gathered once per compressed K position and the matmul runs at
``K_c = K_eff * N / M``: the MXU does **N/M of the dense FLOPs** — the
TPU-native realization of "map only nonzeros onto the MACs".

Computes ``Y_t (O, B) = Vᵀ · X_g`` from
  x_t: (K_eff, B)   activations, K-major layout (gather along sublanes)
  values: (K_c, O)  compressed weights
  idx: (K_c, 1) int32 shared in-block indices

The sublane gather is ≤4 compare+selects per compressed row (the input
selector of the paper's Fig. 8 moved from silicon to the VPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogue import (
    EpilogueSpec, flush_tile, out_dtype_for, tile_in_specs, tile_operands,
)
from repro.kernels.registry import kernel_label

_IDENT = EpilogueSpec()


def _gather_accumulate(xt_ref, v_ref, idx_ref, acc_ref, n: int, acc_dtype):
    """The shared sublane-gather + reduced-K contract step: init the
    accumulator tile on the first K step, select the N kept candidates
    per M-block (≤4 compare+selects per compressed row — exact for float
    and int8 alike), and accumulate ``vᵀ @ x_g``.  ONE body for the
    float and int8 (scaled and raw) kernels, so their numerics cannot
    drift apart."""
    _gather_step(xt_ref[...], v_ref, idx_ref, acc_ref, n, acc_dtype)


def _gather_step(xt, v_ref, idx_ref, acc_ref, n: int, acc_dtype):
    """Same body over an already-read ``(BKe, BB)`` VMEM tile — the dual
    gate-up kernel reads x once and feeds both weights through this."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _gather_contract(xt, v_ref, idx_ref, acc_ref, n, acc_dtype)


def _gather_contract(xt, v_ref, idx_ref, acc_ref, n: int, acc_dtype):
    """The gather + reduced-K dot WITHOUT the init — the masked kernel
    guards this on block liveness while its init runs unconditionally
    (step kk==0 may be dead)."""
    bke, bb = xt.shape
    nb = bke // 4
    x3 = xt.reshape(nb, 4, bb)           # candidates per block
    idx = idx_ref[...]                   # (BKc, 1)
    i3 = idx.reshape(nb, n, 1)
    slices = []
    for s in range(n):
        i_s = i3[:, s, :]                # (nb, 1)
        acc = jnp.zeros((nb, bb), xt.dtype)
        for j in range(4):
            acc = acc + jnp.where(i_s == j, x3[:, j, :], jnp.zeros_like(acc))
        slices.append(acc)
    # interleave s-slices back to block-major compressed order (BKc, BB)
    x_g = jnp.stack(slices, axis=1).reshape(nb * n, bb)
    # (BKc, BO)^T contract (BKc, BB) -> (BO, BB): reduced-K MXU matmul
    acc_ref[...] += jax.lax.dot_general(
        v_ref[...], x_g,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=acc_dtype,
    )


def _gather_kernel(xt_ref, v_ref, idx_ref, o_ref, acc_ref, *, n: int, nk: int):
    _gather_accumulate(xt_ref, v_ref, idx_ref, acc_ref, n, jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def nm_spmm_gather(
    x_t: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    n: int,
    *,
    block_b: int = 128,
    block_o: int = 128,
    block_ke: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Y_t (O, B) = dec(values, idx)ᵀ @ X.  M fixed at 4."""
    ke, b = x_t.shape
    kc, o = values.shape
    assert ke * n == kc * 4, (x_t.shape, values.shape, n)
    assert idx.shape == (kc, 1), idx.shape
    block_b = min(block_b, b)
    block_o = min(block_o, o)
    block_ke = min(block_ke, ke)
    assert b % block_b == 0 and o % block_o == 0 and ke % block_ke == 0
    block_kc = block_ke * n // 4
    nk = ke // block_ke
    return pl.pallas_call(
        lambda xr, vr, ir, orf, acc: _gather_kernel(xr, vr, ir, orf, acc, n=n, nk=nk),
        grid=(b // block_b, o // block_o, nk),
        in_specs=[
            pl.BlockSpec((block_ke, block_b), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((block_kc, block_o), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_kc, 1), lambda i, j, kk: (kk, 0)),
        ],
        out_specs=pl.BlockSpec((block_o, block_b), lambda i, j, kk: (j, i)),
        out_shape=jax.ShapeDtypeStruct((o, b), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_o, block_b), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        **kernel_label("nm_spmm_gather", "nm_spmm_gather",
                       values.dtype),
    )(x_t, values, idx)


def _gather_q_kernel(xt_ref, v_ref, idx_ref, xs_ref, ws_ref, o_ref,
                     acc_ref, *, n: int, nk: int, acc_dtype):
    _gather_accumulate(xt_ref, v_ref, idx_ref, acc_ref, n, acc_dtype)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        deq = acc_ref[...].astype(jnp.float32) * ws_ref[...] * xs_ref[...]
        o_ref[...] = deq.astype(o_ref.dtype)


def _gather_q_raw_kernel(xt_ref, v_ref, idx_ref, o_ref, acc_ref,
                         *, n: int, nk: int, acc_dtype):
    _gather_accumulate(xt_ref, v_ref, idx_ref, acc_ref, n, acc_dtype)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        # raw accumulator out for the psum-then-dequantize ordering
        o_ref[...] = acc_ref[...]


def _nm_spmm_gather_quantized(
    x_t, values, idx, x_scale, w_scale, n, *, acc_dtype,
    block_b, block_o, block_ke, out_dtype, interpret,
) -> jax.Array:
    """Shared pallas_call plumbing for the int8 and fp8 reduced-K
    gather SpMMs — ONE implementation parameterized by the accumulator
    dtype."""
    ke, b = x_t.shape
    kc, o = values.shape
    assert ke * n == kc * 4, (x_t.shape, values.shape, n)
    assert idx.shape == (kc, 1), idx.shape
    raw = x_scale is None
    assert raw == (w_scale is None), "pass both scales or neither"
    if raw:
        out_dtype = acc_dtype
    else:
        assert x_scale.shape == (1, b) and w_scale.shape == (o, 1), (
            x_scale.shape, w_scale.shape)
    block_b = min(block_b, b)
    block_o = min(block_o, o)
    block_ke = min(block_ke, ke)
    assert b % block_b == 0 and o % block_o == 0 and ke % block_ke == 0
    block_kc = block_ke * n // 4
    nk = ke // block_ke
    if raw:
        return pl.pallas_call(
            lambda xr, vr, ir, orf, acc: _gather_q_raw_kernel(
                xr, vr, ir, orf, acc, n=n, nk=nk, acc_dtype=acc_dtype),
            grid=(b // block_b, o // block_o, nk),
            in_specs=[
                pl.BlockSpec((block_ke, block_b), lambda i, j, kk: (kk, i)),
                pl.BlockSpec((block_kc, block_o), lambda i, j, kk: (kk, j)),
                pl.BlockSpec((block_kc, 1), lambda i, j, kk: (kk, 0)),
            ],
            out_specs=pl.BlockSpec((block_o, block_b), lambda i, j, kk: (j, i)),
            out_shape=jax.ShapeDtypeStruct((o, b), acc_dtype),
            scratch_shapes=[pltpu.VMEM((block_o, block_b), acc_dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
            **kernel_label("nm_spmm_gather_raw", "nm_spmm_gather",
                           values.dtype),
        )(x_t, values, idx)
    return pl.pallas_call(
        lambda xr, vr, ir, xsr, wsr, orf, acc: _gather_q_kernel(
            xr, vr, ir, xsr, wsr, orf, acc, n=n, nk=nk, acc_dtype=acc_dtype),
        grid=(b // block_b, o // block_o, nk),
        in_specs=[
            pl.BlockSpec((block_ke, block_b), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((block_kc, block_o), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_kc, 1), lambda i, j, kk: (kk, 0)),
            pl.BlockSpec((1, block_b), lambda i, j, kk: (0, i)),
            pl.BlockSpec((block_o, 1), lambda i, j, kk: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_o, block_b), lambda i, j, kk: (j, i)),
        out_shape=jax.ShapeDtypeStruct((o, b), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_o, block_b), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        **kernel_label("nm_spmm_gather", "nm_spmm_gather",
                       values.dtype),
    )(x_t, values, idx, x_scale, w_scale)


def nm_spmm_gather_int8(
    x_t: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    x_scale: jax.Array,
    w_scale: jax.Array,
    n: int,
    *,
    block_b: int = 128,
    block_o: int = 128,
    block_ke: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Int8 reduced-K variant: Y_t = dec(values*ws, idx)ᵀ @ (x_q*xs).

    x_t: (K_eff, B) int8 K-major activations; values: (K_c, O) int8;
    x_scale: (1, B) f32 per activation row; w_scale: (O, 1) f32
    per-channel.  The sublane gather selects int8 candidates exactly, the
    reduced-K contraction runs int8 x int8 into an int32 accumulator,
    and the flush dequantizes the (O, B) tile once.

    ``x_scale=None``/``w_scale=None`` returns the raw int32 accumulator
    (``out_dtype`` forced to int32) for the psum-then-dequantize sharded
    ordering.
    """
    return _nm_spmm_gather_quantized(
        x_t, values, idx, x_scale, w_scale, n, acc_dtype=jnp.int32,
        block_b=block_b, block_o=block_o, block_ke=block_ke,
        out_dtype=out_dtype, interpret=interpret)


def nm_spmm_gather_fp8(
    x_t: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    x_scale: jax.Array,
    w_scale: jax.Array,
    n: int,
    *,
    block_b: int = 128,
    block_o: int = 128,
    block_ke: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """fp8 (e4m3fn) reduced-K variant: same contract as
    :func:`nm_spmm_gather_int8` with fp8 operands and an **fp32** VMEM
    accumulator.  The sublane gather selects fp8 candidates exactly
    (one value or zero per compressed row), the reduced-K contraction
    runs fp8 x fp8 with ``preferred_element_type=float32``, and the
    flush dequantizes the (O, B) tile once.

    ``x_scale=None``/``w_scale=None`` returns the raw fp32 accumulator
    for the psum-then-dequantize sharded ordering.
    """
    return _nm_spmm_gather_quantized(
        x_t, values, idx, x_scale, w_scale, n, acc_dtype=jnp.float32,
        block_b=block_b, block_o=block_o, block_ke=block_ke,
        out_dtype=out_dtype, interpret=interpret)


# ---------------------------------------------------------------------------
# BK-layout kernels: the gather/transpose fused into the index map.
#
# The adapters historically materialized ``x.T`` (K-major) in HBM before
# the call and ``y_t.T`` after it — two full HBM round trips per linear.
# The ``*_bk`` kernels instead take the activations in their natural
# row-major ``(B, K_eff)`` layout: the BlockSpec index map delivers the
# (BB, BKe) tile and the transpose happens **in VMEM** on the way into
# the sublane gather; the flush transposes the (BO, BB) accumulator back
# and writes the natural ``(B, O)`` output.  Neither permuted operand
# ever exists in HBM (DARE's densifying-gather treatment).
# ---------------------------------------------------------------------------


def _gather_bk_kernel(*refs, n: int, nk: int, acc_dtype, quant: bool,
                      epi: EpilogueSpec):
    """ONE body for the float and scaled-quantized bk-layout kernels.

    Ref order: x (BB, BKe), values, idx, [xs (BB, 1), ws (1, BO)],
    [bias], [rq_scale], out (BB, BO), acc (BO, BB).
    """
    it = list(refs)
    x_ref, v_ref, idx_ref = it[0], it[1], it[2]
    p = 3
    xs_ref = ws_ref = bias_ref = rq_ref = None
    if quant:
        xs_ref, ws_ref = it[p], it[p + 1]
        p += 2
    if epi.bias:
        bias_ref = it[p]
        p += 1
    if epi.requant:
        rq_ref = it[p]
        p += 1
    o_ref, acc_ref = it[p], it[p + 1]

    _gather_step(x_ref[...].T, v_ref, idx_ref, acc_ref, n, acc_dtype)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        t = acc_ref[...].T.astype(jnp.float32)     # (BB, BO), row-major
        if quant:
            # ws before xs: the exact multiply order of the K-major
            # kernel's flush, so the two layouts are bit-identical
            t = t * ws_ref[...] * xs_ref[...]
        o_ref[...] = flush_tile(
            t, epi, o_ref.dtype,
            bias_tile=None if bias_ref is None else bias_ref[...],
            rq_scale=None if rq_ref is None else rq_ref[0, 0])


def nm_spmm_gather_bk(
    x: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    n: int,
    x_scale: jax.Array = None,
    w_scale: jax.Array = None,
    *,
    acc_dtype=jnp.float32,
    block_b: int = 128,
    block_o: int = 128,
    block_ke: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
    epilogue: EpilogueSpec = None,
    bias: jax.Array = None,
    requant_scale=None,
) -> jax.Array:
    """Y (B, O) = X (B, K_eff) @ dec(values, idx) — natural layouts in
    and out, gather and transposes fused into the kernel.  Float when
    ``x_scale is None``; quantized when both scales are given (note the
    row-major scale shapes: ``x_scale (B, 1)``, ``w_scale (1, O)`` —
    unlike the K-major :func:`nm_spmm_gather_int8`).  The scaled flush
    additionally applies an epilogue lattice point.
    """
    epi = epilogue or _IDENT
    b, ke = x.shape
    kc, o = values.shape
    assert ke * n == kc * 4, (x.shape, values.shape, n)
    assert idx.shape == (kc, 1), idx.shape
    quant = x_scale is not None
    assert quant == (w_scale is not None), "pass both scales or neither"
    if quant:
        assert x_scale.shape == (b, 1) and w_scale.shape == (1, o), (
            x_scale.shape, w_scale.shape)
    else:
        acc_dtype = jnp.float32
    block_b = min(block_b, b)
    block_o = min(block_o, o)
    block_ke = min(block_ke, ke)
    assert b % block_b == 0 and o % block_o == 0 and ke % block_ke == 0
    block_kc = block_ke * n // 4
    nk = ke // block_ke
    in_specs = [
        pl.BlockSpec((block_b, block_ke), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((block_kc, block_o), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((block_kc, 1), lambda i, j, kk: (kk, 0)),
    ]
    operands = [x, values, idx]
    if quant:
        in_specs += [
            pl.BlockSpec((block_b, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, block_o), lambda i, j, kk: (0, j)),
        ]
        operands += [x_scale, w_scale]
    in_specs += tile_in_specs(epi, block_o)
    operands += tile_operands(epi, bias, requant_scale, o)
    return pl.pallas_call(
        lambda *refs: _gather_bk_kernel(*refs, n=n, nk=nk,
                                        acc_dtype=acc_dtype, quant=quant,
                                        epi=epi),
        grid=(b // block_b, o // block_o, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, o), out_dtype_for(epi, out_dtype)),
        scratch_shapes=[pltpu.VMEM((block_o, block_b), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        **kernel_label("nm_spmm_gather_bk", "nm_spmm_gather",
                       values.dtype),
    )(*operands)


def _gather_bk_masked_kernel(*refs, n: int, nk: int, acc_dtype, quant: bool,
                             epi: EpilogueSpec):
    """Activation-sparsity (block-skip) bk-layout body.  Ref order:
    kmap, kmask (scalar prefetch), then exactly the
    :func:`_gather_bk_kernel` order.  Init is SPLIT from the gather +
    contract (step kk==0 may be dead); dead x blocks are exact zeros, so
    skipping them is bit-identical and the kmap index maps elide the
    x/values/idx copies."""
    it = list(refs)
    kmask_ref = it[1]
    x_ref, v_ref, idx_ref = it[2], it[3], it[4]
    p = 5
    xs_ref = ws_ref = bias_ref = rq_ref = None
    if quant:
        xs_ref, ws_ref = it[p], it[p + 1]
        p += 2
    if epi.bias:
        bias_ref = it[p]
        p += 1
    if epi.requant:
        rq_ref = it[p]
        p += 1
    o_ref, acc_ref = it[p], it[p + 1]

    i = pl.program_id(0)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kmask_ref[i, kk] != 0)
    def _accumulate():
        _gather_contract(x_ref[...].T, v_ref, idx_ref, acc_ref, n,
                         acc_dtype)

    @pl.when(kk == nk - 1)
    def _flush():
        t = acc_ref[...].T.astype(jnp.float32)     # (BB, BO), row-major
        if quant:
            # ws before xs: match _gather_bk_kernel bit-for-bit
            t = t * ws_ref[...] * xs_ref[...]
        o_ref[...] = flush_tile(
            t, epi, o_ref.dtype,
            bias_tile=None if bias_ref is None else bias_ref[...],
            rq_scale=None if rq_ref is None else rq_ref[0, 0])


def nm_spmm_gather_bk_masked(
    x: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    kmap: jax.Array,
    kmask: jax.Array,
    n: int,
    x_scale: jax.Array = None,
    w_scale: jax.Array = None,
    *,
    acc_dtype=jnp.float32,
    block_b: int = 128,
    block_o: int = 128,
    block_ke: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
    epilogue: EpilogueSpec = None,
    bias: jax.Array = None,
    requant_scale=None,
) -> jax.Array:
    """:func:`nm_spmm_gather_bk` with an in-kernel activation-sparsity
    block skip.  ``kmap``/``kmask`` are ``(B/block_b, K_eff/block_ke)``
    int32 maps from ``repro.kernels.actsparse.block_maps`` over the
    masked ``x``, riding the grid as scalar-prefetch operands.
    Bit-identical to the unmasked kernel on the same masked ``x``.
    """
    epi = epilogue or _IDENT
    b, ke = x.shape
    kc, o = values.shape
    assert ke * n == kc * 4, (x.shape, values.shape, n)
    assert idx.shape == (kc, 1), idx.shape
    quant = x_scale is not None
    assert quant == (w_scale is not None), "pass both scales or neither"
    if quant:
        assert x_scale.shape == (b, 1) and w_scale.shape == (1, o), (
            x_scale.shape, w_scale.shape)
    else:
        acc_dtype = jnp.float32
    block_b = min(block_b, b)
    block_o = min(block_o, o)
    block_ke = min(block_ke, ke)
    assert b % block_b == 0 and o % block_o == 0 and ke % block_ke == 0
    block_kc = block_ke * n // 4
    nk = ke // block_ke
    assert kmap.shape == (b // block_b, nk) == kmask.shape, (
        kmap.shape, kmask.shape, (b // block_b, nk))
    in_specs = [
        pl.BlockSpec((block_b, block_ke),
                     lambda i, j, kk, kmap_, kmask_: (i, kmap_[i, kk])),
        pl.BlockSpec((block_kc, block_o),
                     lambda i, j, kk, kmap_, kmask_: (kmap_[i, kk], j)),
        pl.BlockSpec((block_kc, 1),
                     lambda i, j, kk, kmap_, kmask_: (kmap_[i, kk], 0)),
    ]
    operands = [x, values, idx]
    if quant:
        in_specs += [
            pl.BlockSpec((block_b, 1), lambda i, j, kk, *_: (i, 0)),
            pl.BlockSpec((1, block_o), lambda i, j, kk, *_: (0, j)),
        ]
        operands += [x_scale, w_scale]
    in_specs += tile_in_specs(epi, block_o)
    operands += tile_operands(epi, bias, requant_scale, o)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b // block_b, o // block_o, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_o),
                               lambda i, j, kk, *_: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_o, block_b), acc_dtype)],
    )
    return pl.pallas_call(
        lambda *refs: _gather_bk_masked_kernel(*refs, n=n, nk=nk,
                                               acc_dtype=acc_dtype,
                                               quant=quant, epi=epi),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, o), out_dtype_for(epi, out_dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        **kernel_label("nm_spmm_gather_bk_masked", "nm_spmm_gather",
                       values.dtype),
    )(kmap, kmask, *operands)


def _gather_dual_kernel(*refs, n: int, nk: int, acc_dtype, quant: bool,
                        epi: EpilogueSpec):
    """Fused gate-up for the gather family (bk layout): the x tile is
    read and transposed ONCE and gathered through both weights' index
    streams.  Ref order: x, v_g, idx_g, v_u, idx_u,
    [xs, ws_g, ws_u], [rq_scale], out, acc_g, acc_u.
    """
    it = list(refs)
    x_ref, vg_ref, ig_ref, vu_ref, iu_ref = it[:5]
    p = 5
    xs_ref = wsg_ref = wsu_ref = rq_ref = None
    if quant:
        xs_ref, wsg_ref, wsu_ref = it[p], it[p + 1], it[p + 2]
        p += 3
    if epi.requant:
        rq_ref = it[p]
        p += 1
    o_ref, accg_ref, accu_ref = it[p], it[p + 1], it[p + 2]

    xt = x_ref[...].T                    # ONE read + transpose in VMEM
    _gather_step(xt, vg_ref, ig_ref, accg_ref, n, acc_dtype)
    _gather_step(xt, vu_ref, iu_ref, accu_ref, n, acc_dtype)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        tg = accg_ref[...].T.astype(jnp.float32)
        tu = accu_ref[...].T.astype(jnp.float32)
        if quant:
            xs = xs_ref[...]
            tg = tg * wsg_ref[...] * xs
            tu = tu * wsu_ref[...] * xs
        o_ref[...] = flush_tile(
            tg, epi, o_ref.dtype,
            rq_scale=None if rq_ref is None else rq_ref[0, 0],
            acc2_32=tu)


def nm_spmm_gather_dual_bk(
    x, values_g, idx_g, values_u, idx_u, n: int,
    x_scale=None, wg_scale=None, wu_scale=None, *,
    acc_dtype=jnp.float32,
    block_b: int = 128,
    block_o: int = 128,
    block_ke: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
    epilogue: EpilogueSpec = None,
    requant_scale=None,
) -> jax.Array:
    """Fused gate-up over two lane-aligned compressed weights sharing one
    x: ``silu(x @ dec(v_g)) * (x @ dec(v_u))`` in one pallas_call, bk
    layout in and out.  The two weights keep their own index streams
    (per-site gather metadata), so the activation gather runs twice but
    the HBM read of x happens once.
    """
    epi = epilogue or EpilogueSpec(act="silu_mul")
    assert epi.act == "silu_mul" and not epi.bias, epi.point
    b, ke = x.shape
    kc, o = values_g.shape
    assert ke * n == kc * 4, (x.shape, values_g.shape, n)
    assert values_u.shape == (kc, o)
    assert idx_g.shape == (kc, 1) and idx_u.shape == (kc, 1)
    quant = x_scale is not None
    if quant:
        assert x_scale.shape == (b, 1), x_scale.shape
        assert wg_scale.shape == (1, o) and wu_scale.shape == (1, o)
    else:
        acc_dtype = jnp.float32
    block_b = min(block_b, b)
    block_o = min(block_o, o)
    block_ke = min(block_ke, ke)
    assert b % block_b == 0 and o % block_o == 0 and ke % block_ke == 0
    block_kc = block_ke * n // 4
    nk = ke // block_ke
    v_spec = pl.BlockSpec((block_kc, block_o), lambda i, j, kk: (kk, j))
    i_spec = pl.BlockSpec((block_kc, 1), lambda i, j, kk: (kk, 0))
    in_specs = [
        pl.BlockSpec((block_b, block_ke), lambda i, j, kk: (i, kk)),
        v_spec, i_spec, v_spec, i_spec,
    ]
    operands = [x, values_g, idx_g, values_u, idx_u]
    if quant:
        in_specs += [
            pl.BlockSpec((block_b, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, block_o), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, block_o), lambda i, j, kk: (0, j)),
        ]
        operands += [x_scale, wg_scale, wu_scale]
    rq_spec = EpilogueSpec(requant=epi.requant)
    in_specs += tile_in_specs(rq_spec, block_o)
    operands += tile_operands(rq_spec, None, requant_scale, o)
    return pl.pallas_call(
        lambda *refs: _gather_dual_kernel(*refs, n=n, nk=nk,
                                          acc_dtype=acc_dtype, quant=quant,
                                          epi=epi),
        grid=(b // block_b, o // block_o, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, o), out_dtype_for(epi, out_dtype)),
        scratch_shapes=[pltpu.VMEM((block_o, block_b), acc_dtype),
                        pltpu.VMEM((block_o, block_b), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        **kernel_label("nm_spmm_gather_dual_bk", "nm_spmm_gather",
                       values_g.dtype),
    )(*operands)
