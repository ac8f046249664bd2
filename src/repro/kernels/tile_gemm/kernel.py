"""Dense tile GEMM Pallas kernel — the TILE_GEMM / VEGETA-D baseline.

C (B, O) fp32 += X (B, K) bf16 @ W (K, O) bf16, blocked for VMEM with an
fp32 accumulator tile held in VMEM across the K grid (the "output
forwarding" adaptation: the C tile never round-trips to HBM between
accumulating steps — see DESIGN.md §2).

``tile_gemm_int8`` is the VNNI-lineage variant: int8 x int8 tiles
contract into an **int32** accumulator held in VMEM across the K grid,
and the output is dequantized exactly once on the final flush with the
per-row activation scales and per-channel weight scales.
``tile_gemm_fp8`` is the same contract for the fp8 (e4m3fn) execution
class — fp8 x fp8 tiles contract into an **fp32** VMEM accumulator
(``preferred_element_type``) with the identical single-dequantize flush;
the two share one parameterized pallas_call so the quantized plumbing
cannot drift between dtypes.
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


def _gemm_accumulate(x_ref, w_ref, acc_ref, acc_dtype):
    """Shared init + accumulate step: ONE body for the float and int8
    (scaled and raw) kernels, so their numerics cannot drift apart."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=acc_dtype
    )


def _gemm_kernel(*refs, nk: int, acc_dtype, quant: bool, epi: EpilogueSpec):
    """ONE flush body for the float and scaled-quantized GEMMs.

    Ref order: x, w, [xs, ws (quant)], [bias (epi.bias)],
    [rq_scale (epi.requant)], out, acc — the epilogue operands are
    optional VMEM tiles and the whole lattice point is applied to the
    dequantized fp32 accumulator before the single HBM write-back.
    """
    it = list(refs)
    x_ref, w_ref = it[0], it[1]
    p = 2
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

    _gemm_accumulate(x_ref, w_ref, acc_ref, acc_dtype)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        t = acc_ref[...].astype(jnp.float32)
        if quant:
            t = t * xs_ref[...] * ws_ref[...]
        o_ref[...] = flush_tile(
            t, epi, o_ref.dtype,
            bias_tile=None if bias_ref is None else bias_ref[...],
            rq_scale=None if rq_ref is None else rq_ref[0, 0])


def tile_gemm(
    x: jax.Array,
    w: jax.Array,
    *,
    block_b: int = 128,
    block_o: int = 128,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
    epilogue: EpilogueSpec = None,
    bias: jax.Array = None,
    requant_scale=None,
) -> jax.Array:
    epi = epilogue or _IDENT
    b, k = x.shape
    k2, o = w.shape
    assert k == k2, (x.shape, w.shape)
    block_b = min(block_b, b)
    block_o = min(block_o, o)
    block_k = min(block_k, k)
    assert b % block_b == 0 and o % block_o == 0 and k % block_k == 0
    nk = k // block_k
    return pl.pallas_call(
        lambda *refs: _gemm_kernel(*refs, nk=nk, acc_dtype=jnp.float32,
                                   quant=False, epi=epi),
        grid=(b // block_b, o // block_o, nk),
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_o), lambda i, j, kk: (kk, j)),
        ] + tile_in_specs(epi, block_o),
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, o), out_dtype_for(epi, out_dtype)),
        scratch_shapes=[pltpu.VMEM((block_b, block_o), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        **kernel_label("tile_gemm", "tile_gemm", w.dtype),
    )(x, w, *tile_operands(epi, bias, requant_scale, o))


def _gemm_q_raw_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int, acc_dtype):
    _gemm_accumulate(x_ref, w_ref, acc_ref, acc_dtype)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        # raw accumulator out (int32 / fp32), no extra round-trip: partial
        # products over a sharded contraction are psum'd before the single
        # dequantize on the gathered result
        o_ref[...] = acc_ref[...]


def _tile_gemm_quantized(
    x_q, w_q, x_scale, w_scale, *, acc_dtype,
    block_b, block_o, block_k, out_dtype, interpret,
    epilogue: EpilogueSpec = None, bias=None, requant_scale=None,
) -> jax.Array:
    """Shared pallas_call plumbing for the int8 and fp8 tile GEMMs —
    ONE implementation parameterized by the accumulator dtype, so the
    two quantized execution classes cannot drift apart.  The scaled
    branch additionally takes an epilogue lattice point, applied to the
    dequantized fp32 tile at the flush (the raw branch never does — its
    contract is the exact accumulator for psum-then-dequantize)."""
    epi = epilogue or _IDENT
    b, k = x_q.shape
    k2, o = w_q.shape
    assert k == k2, (x_q.shape, w_q.shape)
    raw = x_scale is None
    assert raw == (w_scale is None), "pass both scales or neither"
    if raw:
        assert epi.is_identity, "raw accumulator kernels take no epilogue"
        out_dtype = acc_dtype
    else:
        assert x_scale.shape == (b, 1) and w_scale.shape == (1, o), (
            x_scale.shape, w_scale.shape)
    block_b = min(block_b, b)
    block_o = min(block_o, o)
    block_k = min(block_k, k)
    assert b % block_b == 0 and o % block_o == 0 and k % block_k == 0
    nk = k // block_k
    if raw:
        return pl.pallas_call(
            lambda xr, wr, orf, acc: _gemm_q_raw_kernel(
                xr, wr, orf, acc, nk=nk, acc_dtype=acc_dtype),
            grid=(b // block_b, o // block_o, nk),
            in_specs=[
                pl.BlockSpec((block_b, block_k), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((block_k, block_o), lambda i, j, kk: (kk, j)),
            ],
            out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((b, o), acc_dtype),
            scratch_shapes=[pltpu.VMEM((block_b, block_o), acc_dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
            **kernel_label("tile_gemm_raw", "tile_gemm", w_q.dtype),
        )(x_q, w_q)
    return pl.pallas_call(
        lambda *refs: _gemm_kernel(*refs, nk=nk, acc_dtype=acc_dtype,
                                   quant=True, epi=epi),
        grid=(b // block_b, o // block_o, nk),
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_o), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_b, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, block_o), lambda i, j, kk: (0, j)),
        ] + tile_in_specs(epi, block_o),
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, o), out_dtype_for(epi, out_dtype)),
        scratch_shapes=[pltpu.VMEM((block_b, block_o), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        **kernel_label("tile_gemm", "tile_gemm", w_q.dtype),
    )(x_q, w_q, x_scale, w_scale,
      *tile_operands(epi, bias, requant_scale, o))


def _gemm_masked_kernel(*refs, nk: int, acc_dtype, quant: bool,
                        epi: EpilogueSpec):
    """Activation-sparsity (block-skip) flush body.

    Ref order: kmap, kmask (scalar prefetch), then exactly the
    :func:`_gemm_kernel` order.  The init is SPLIT from the accumulate —
    unlike ``_gemm_accumulate`` — because step kk==0 may be dead: the
    zero-init must run unconditionally, the dot only on live blocks.
    Dead blocks hold exact zeros (the mask pass produced them), so
    skipping their dot is bit-identical to accumulating them, and their
    index-map entries repeat the previous live block so the HBM->VMEM
    copies are elided too.
    """
    it = list(refs)
    kmap_ref, kmask_ref = it[0], it[1]
    del kmap_ref  # consumed by the index maps; the body keys on kmask
    x_ref, w_ref = it[2], it[3]
    p = 4
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
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=acc_dtype)

    @pl.when(kk == nk - 1)
    def _flush():
        t = acc_ref[...].astype(jnp.float32)
        if quant:
            t = t * xs_ref[...] * ws_ref[...]
        o_ref[...] = flush_tile(
            t, epi, o_ref.dtype,
            bias_tile=None if bias_ref is None else bias_ref[...],
            rq_scale=None if rq_ref is None else rq_ref[0, 0])


def tile_gemm_masked(
    x: jax.Array,
    w: jax.Array,
    kmap: jax.Array,
    kmask: jax.Array,
    x_scale: jax.Array = None,
    w_scale: jax.Array = None,
    *,
    acc_dtype=jnp.float32,
    block_b: int = 128,
    block_o: int = 128,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
    epilogue: EpilogueSpec = None,
    bias: jax.Array = None,
    requant_scale=None,
) -> jax.Array:
    """:func:`tile_gemm` with an in-kernel activation-sparsity block skip.

    ``kmap``/``kmask`` are the ``(B/block_b, K/block_k)`` int32 maps from
    ``repro.kernels.actsparse.block_maps`` over the (masked) ``x``; they
    ride the grid as scalar-prefetch operands — ``kmask`` gates the
    accumulate, ``kmap`` drives the x/w index maps so dead K-blocks are
    never copied in.  Float when ``x_scale is None``; scaled-quantized
    (int8/fp8 by ``acc_dtype``) with both scales, same flush contract as
    the plain kernels.  Output is bit-identical to the unmasked kernel
    on the same masked ``x``.
    """
    epi = epilogue or _IDENT
    b, k = x.shape
    k2, o = w.shape
    assert k == k2, (x.shape, w.shape)
    quant = x_scale is not None
    assert quant == (w_scale is not None), "pass both scales or neither"
    if not quant:
        acc_dtype = jnp.float32
    else:
        assert x_scale.shape == (b, 1) and w_scale.shape == (1, o), (
            x_scale.shape, w_scale.shape)
    block_b = min(block_b, b)
    block_o = min(block_o, o)
    block_k = min(block_k, k)
    assert b % block_b == 0 and o % block_o == 0 and k % block_k == 0
    nk = k // block_k
    assert kmap.shape == (b // block_b, nk) == kmask.shape, (
        kmap.shape, kmask.shape, (b // block_b, nk))

    in_specs = [
        pl.BlockSpec((block_b, block_k),
                     lambda i, j, kk, kmap_, kmask_: (i, kmap_[i, kk])),
        pl.BlockSpec((block_k, block_o),
                     lambda i, j, kk, kmap_, kmask_: (kmap_[i, kk], j)),
    ]
    operands = [x, w]
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
        scratch_shapes=[pltpu.VMEM((block_b, block_o), acc_dtype)],
    )
    return pl.pallas_call(
        lambda *refs: _gemm_masked_kernel(*refs, nk=nk, acc_dtype=acc_dtype,
                                          quant=quant, epi=epi),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, o), out_dtype_for(epi, out_dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        **kernel_label("tile_gemm_masked", "tile_gemm", w.dtype),
    )(kmap, kmask, *operands)


def _gemm_dual_kernel(*refs, nk: int, acc_dtype, quant: bool,
                      epi: EpilogueSpec):
    """Fused gate-up flush: two GEMMs over ONE activation tile read.

    Ref order: x, w_g, w_u, [xs, ws_g, ws_u (quant)],
    [rq_scale (epi.requant)], out, acc_g, acc_u.  The x tile is read
    from VMEM once and contracted against both weight tiles; the flush
    emits ``silu(deq(acc_g)) * deq(acc_u)`` (optionally requantized)
    directly — the gate and up projections never touch HBM.
    """
    it = list(refs)
    x_ref, wg_ref, wu_ref = it[0], it[1], it[2]
    p = 3
    xs_ref = wsg_ref = wsu_ref = rq_ref = None
    if quant:
        xs_ref, wsg_ref, wsu_ref = it[p], it[p + 1], it[p + 2]
        p += 3
    if epi.requant:
        rq_ref = it[p]
        p += 1
    o_ref, accg_ref, accu_ref = it[p], it[p + 1], it[p + 2]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    xv = x_ref[...]  # ONE read feeds both contractions
    accg_ref[...] += jnp.dot(xv, wg_ref[...],
                             preferred_element_type=acc_dtype)
    accu_ref[...] += jnp.dot(xv, wu_ref[...],
                             preferred_element_type=acc_dtype)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        tg = accg_ref[...].astype(jnp.float32)
        tu = accu_ref[...].astype(jnp.float32)
        if quant:
            xs = xs_ref[...]
            tg = tg * xs * wsg_ref[...]
            tu = tu * xs * wsu_ref[...]
        o_ref[...] = flush_tile(
            tg, epi, o_ref.dtype,
            rq_scale=None if rq_ref is None else rq_ref[0, 0],
            acc2_32=tu)


def tile_gemm_dual(
    x, w_g, w_u, x_scale=None, wg_scale=None, wu_scale=None, *,
    acc_dtype=jnp.float32,
    block_b: int = 128,
    block_o: int = 128,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
    epilogue: EpilogueSpec = None,
    requant_scale=None,
) -> jax.Array:
    """Fused gate-up projection: ``silu(x @ w_g) * (x @ w_u)`` in one
    pallas_call.  Float when ``x_scale is None``; quantized (int8/fp8 by
    ``acc_dtype`` int32/fp32) when the three scales are given, with the
    same flush-time dequantize contract as the single-GEMM kernels.
    The epilogue spec must be a ``silu_mul`` point (bias unsupported on
    the dual path); ``requant`` on the spec re-emits the narrow dtype.
    """
    epi = epilogue or EpilogueSpec(act="silu_mul")
    assert epi.act == "silu_mul" and not epi.bias, epi.point
    b, k = x.shape
    k2, o = w_g.shape
    assert k == k2 and w_u.shape == w_g.shape, (x.shape, w_g.shape,
                                                w_u.shape)
    quant = x_scale is not None
    if quant:
        assert x_scale.shape == (b, 1), x_scale.shape
        assert wg_scale.shape == (1, o) and wu_scale.shape == (1, o), (
            wg_scale.shape, wu_scale.shape)
    else:
        acc_dtype = jnp.float32
    block_b = min(block_b, b)
    block_o = min(block_o, o)
    block_k = min(block_k, k)
    assert b % block_b == 0 and o % block_o == 0 and k % block_k == 0
    nk = k // block_k
    x_spec = pl.BlockSpec((block_b, block_k), lambda i, j, kk: (i, kk))
    w_spec = pl.BlockSpec((block_k, block_o), lambda i, j, kk: (kk, j))
    in_specs = [x_spec, w_spec, w_spec]
    operands = [x, w_g, w_u]
    if quant:
        in_specs += [
            pl.BlockSpec((block_b, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, block_o), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, block_o), lambda i, j, kk: (0, j)),
        ]
        operands += [x_scale, wg_scale, wu_scale]
    in_specs += tile_in_specs(EpilogueSpec(requant=epi.requant), block_o)
    operands += tile_operands(EpilogueSpec(requant=epi.requant), None,
                              requant_scale, o)
    return pl.pallas_call(
        lambda *refs: _gemm_dual_kernel(*refs, nk=nk, acc_dtype=acc_dtype,
                                        quant=quant, epi=epi),
        grid=(b // block_b, o // block_o, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, o), out_dtype_for(epi, out_dtype)),
        scratch_shapes=[pltpu.VMEM((block_b, block_o), acc_dtype),
                        pltpu.VMEM((block_b, block_o), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        **kernel_label("tile_gemm_dual", "tile_gemm", w_g.dtype),
    )(*operands)


def tile_gemm_int8(
    x_q: jax.Array,
    w_q: jax.Array,
    x_scale: jax.Array = None,
    w_scale: jax.Array = None,
    *,
    block_b: int = 128,
    block_o: int = 128,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
    epilogue: EpilogueSpec = None,
    bias: jax.Array = None,
    requant_scale=None,
) -> jax.Array:
    """Y = (x_q * x_scale) @ (w_q * w_scale), contracted in int8.

    x_q: (B, K) int8, w_q: (K, O) int8,
    x_scale: (B, 1) f32 per-row, w_scale: (1, O) f32 per-channel.
    The int32 accumulation over K is exact; the two scale vectors are
    applied once, at the flush.

    With ``x_scale=None``/``w_scale=None`` the kernel returns the **raw
    int32 accumulator** instead (``out_dtype`` forced to int32): the
    shard_map execution class contracts each contraction shard to int32
    partials, psums them exactly, and dequantizes once on the result.
    """
    return _tile_gemm_quantized(
        x_q, w_q, x_scale, w_scale, acc_dtype=jnp.int32,
        block_b=block_b, block_o=block_o, block_k=block_k,
        out_dtype=out_dtype, interpret=interpret,
        epilogue=epilogue, bias=bias, requant_scale=requant_scale)


def tile_gemm_fp8(
    x_q: jax.Array,
    w_q: jax.Array,
    x_scale: jax.Array = None,
    w_scale: jax.Array = None,
    *,
    block_b: int = 128,
    block_o: int = 128,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
    epilogue: EpilogueSpec = None,
    bias: jax.Array = None,
    requant_scale=None,
) -> jax.Array:
    """Y = (x_q * x_scale) @ (w_q * w_scale), contracted in fp8 (e4m3fn).

    Same contract as :func:`tile_gemm_int8` with fp8 operands and an
    **fp32** VMEM accumulator (``preferred_element_type=float32`` — the
    Mosaic-native mixed-precision dot).  Scales are applied once at the
    flush; ``x_scale=None``/``w_scale=None`` returns the raw fp32
    accumulator for the psum-then-dequantize sharded ordering.
    """
    return _tile_gemm_quantized(
        x_q, w_q, x_scale, w_scale, acc_dtype=jnp.float32,
        block_b=block_b, block_o=block_o, block_k=block_k,
        out_dtype=out_dtype, interpret=interpret,
        epilogue=epilogue, bias=bias, requant_scale=requant_scale)
