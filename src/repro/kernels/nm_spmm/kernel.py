"""N:M structured-sparse GEMM Pallas kernel — TILE_SPMM_{U,V,T} adaptation.

Computes ``Y (B, O) = X (B, K_eff) @ dec(V, meta) (K_eff, O)`` where the
weight is stored *compressed*: ``V (K_c, O)`` keeps only the N nonzeros per
M=4 block of K, and ``meta_packed (K_c/4, O) uint8`` carries four 2-bit
in-block indices per byte (the mreg adaptation).

TPU mapping of the paper's SPE input-mux (DESIGN.md §2, Tier 1):
  * the dense weight tile **never exists in HBM** — HBM traffic for the
    sparse operand is N/M of dense (+ 2-bit metadata);
  * the M:1 mux becomes a VPU one-hot select producing the expanded
    ``(BK_eff, BO)`` tile in VMEM, amortized over the MXU's BB-deep
    matmul;
  * the fp32 accumulator tile lives in VMEM across the K grid — the
    "output forwarding" equivalent (no C round-trip between accumulating
    instructions).

Two forms of the mux, chosen by ``n`` (:func:`mux_form`):

``slab`` (``n`` divides 4).  Meta byte ``i`` holds the indices of
compressed rows ``4i..4i+3``, and those rows feed whole blocks: the
dense rows ``P*i .. P*i+P-1`` with ``P = 16/n``.  So the field
``(pm >> 2q) & 3`` and a sublane-strided load of the values' 32-bit view
give aligned ``(BK_c/4, BO)`` slabs, one per field, and every dense row
is a lane-local select over 32-bit words: no row ever moves between
sublanes.  The expanded tile comes out with K permuted inside each
tile; the wrapper permutes the activation to the same order
(:func:`slab_order`, plain jnp ahead of the ``pallas_call``).

``rows`` (any other ``n``).  Meta and values are repeated 4x along the
rows and selected against a position pattern: sublane relayouts, kept
for the ``n`` the slabs cannot take.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogue import (
    EpilogueSpec, flush_tile, out_dtype_for, tile_in_specs, tile_operands,
)
from repro.kernels.registry import kernel_label

_IDENT = EpilogueSpec()
_WORD_BITS = 32


def mux_form(n: int) -> str:
    """``"slab"`` when ``n`` divides M=4, else ``"rows"``."""
    return "slab" if 4 % n == 0 else "rows"


def _word_fields(dtype) -> int:
    """Values per 32-bit word: 1 (f32), 2 (bf16), 4 (int8, fp8)."""
    return _WORD_BITS // (jnp.dtype(dtype).itemsize * 8)


def slab_order(x: jax.Array, n: int, block_ke: int, dtype) -> jax.Array:
    """Permute each ``block_ke`` K-tile of ``x (B, K_eff)`` into the row
    order of the slab mux's expanded tile for ``dtype`` values.

    Dense row ``P*i + F*v + t`` of a tile (``P = 16/n`` dense rows per
    meta row, ``F`` values per 32-bit word) is row ``F*(v*S + i) + t``
    of the expanded tile, ``S = block_ke/P``.  Identity for ``rows``.
    """
    if mux_form(n) != "slab":
        return x
    b, ke = x.shape
    f = _word_fields(dtype)
    p = 16 // n
    s = block_ke // p
    return (x.reshape(b, ke // block_ke, s, p // f, f)
            .swapaxes(2, 3).reshape(b, ke))


def _moved(word: jax.Array, t: int, t2: int, bits: int, f: int) -> jax.Array:
    """Field ``t`` of each 32-bit ``word`` moved to field ``t2``, the
    other fields cleared (a mask only where a shift leaves one)."""
    d = bits * (t2 - t)
    if d > 0:
        word = lax.shift_left(word, jnp.uint32(d))
    elif d < 0:
        word = lax.shift_right_logical(word, jnp.uint32(-d))
    if any(0 <= u + t2 - t < f for u in range(f) if u != t):
        word = lax.bitwise_and(word,
                               jnp.uint32(((1 << bits) - 1) << (bits * t2)))
    return word


def _slab_mux(v_ref, pm_ref, n: int) -> jax.Array:
    """The relayout-free M:1 mux: ``(BK_c, BO)`` values + ``(BK_c/4, BO)``
    packed meta -> the ``(BK_c*4/n, BO)`` expanded tile, rows in
    :func:`slab_order`.

    Compressed row ``4i+q`` is field ``q % F`` of word ``4i/F + q//F`` of
    the values' 32-bit view; a stride-``4/F`` load gathers one word per
    meta row.  Output word ``v`` of meta row ``i`` packs dense positions
    ``F*v .. F*v+F-1``; position ``4g+j`` takes the field ``q`` of block
    ``g`` (``q = g*n .. g*n+n-1``) whose index is ``j``, else 0.  Indices
    are unique within a block, so at most one field matches and the
    select chain is exact for every dtype.  All the work is 32-bit
    integer compares, selects, shifts and ORs, written as ``lax``
    primitives: the body is traced once per ``pallas_call``, and each
    program of a served model traces several.
    """
    bkc, bo = v_ref.shape
    f = _word_fields(v_ref.dtype)
    bits = _WORD_BITS // f
    s = bkc // 4
    pm = lax.convert_element_type(pm_ref[...], jnp.int32)
    # idx_q == j  <=>  (pm & 3 << 2q) == j << 2q: no shift per field
    idx = [lax.bitwise_and(pm, 3 << (2 * q)) for q in range(4)]
    w_ref = v_ref.bitcast(jnp.uint32)
    words = [w_ref[pl.ds(u, s, stride=4 // f), :] for u in range(4 // f)]
    zero = lax.full((s, bo), 0, jnp.uint32)
    moved = {}
    out = []
    for v in range(16 // n // f):
        word = None
        for t2 in range(f):
            g, j = divmod(f * v + t2, 4)
            sel = zero
            for q in range(g * n, (g + 1) * n):
                if (q, t2) not in moved:
                    u, t = divmod(q, f)
                    moved[q, t2] = _moved(words[u], t, t2, bits, f)
                sel = lax.select(lax.eq(idx[q], j << (2 * q)),
                                 moved[q, t2], sel)
            word = sel if word is None else lax.bitwise_or(word, sel)
        out.append(word)
    return pltpu.bitcast(lax.concatenate(out, 0), v_ref.dtype)


def _expand_rows4(a: jax.Array) -> jax.Array:
    """(R, C) -> (4R, C), each row repeated 4x (lane dim preserved)."""
    r, c = a.shape
    return jnp.broadcast_to(a[:, None, :], (r, 4, c)).reshape(r * 4, c)


def _unpack_meta_tile(pm: jax.Array) -> jax.Array:
    """(R/4, C) uint8 packed -> (R, C) int32 indices in [0, 4)."""
    r4, c = pm.shape
    p = _expand_rows4(pm.astype(jnp.int32))
    sh = (jax.lax.broadcasted_iota(jnp.int32, (4 * r4, c), 0) % 4) * 2
    return (p >> sh) & 3


def _decompress_tile(v: jax.Array, idx: jax.Array, n: int) -> jax.Array:
    """Expand (BKc, BO) values/indices -> (BKc*4/n, BO) dense weight tile.

    The in-VMEM "M:1 mux": slot j of each block receives the value whose
    2-bit index equals j.  Indices are unique within a block, so the sum
    over the N kept slots has at most one nonzero term per position and is
    exact in bf16.  int8 values are muxed in int32 (Mosaic's vector
    integer ops take i16/i32 only) and narrowed back for the int8 MXU dot.
    """
    bkc, bo = v.shape
    nb = bkc // n
    bke = nb * 4
    mux_dt = jnp.int32 if jnp.issubdtype(v.dtype, jnp.integer) else v.dtype
    j_pat = jax.lax.broadcasted_iota(jnp.int32, (bke, bo), 0) % 4
    v3 = v.astype(mux_dt).reshape(nb, n, bo)
    i3 = idx.reshape(nb, n, bo)
    out = jnp.zeros((bke, bo), mux_dt)
    for s in range(n):
        vs = _expand_rows4(v3[:, s, :])
        ix = _expand_rows4(i3[:, s, :])
        out = out + jnp.where(ix == j_pat, vs, jnp.zeros_like(vs))
    return out.astype(v.dtype)


def _mux_tile(v_ref, pm_ref, n: int) -> jax.Array:
    """The expanded ``(BK_c*4/n, BO)`` weight tile, through the mux form
    that ``n`` selects (:func:`mux_form`)."""
    if mux_form(n) == "slab":
        return _slab_mux(v_ref, pm_ref, n)
    return _decompress_tile(v_ref[...], _unpack_meta_tile(pm_ref[...]), n)


def _spmm_accumulate(x_ref, v_ref, pm_ref, acc_ref, n: int, acc_dtype):
    """The shared mux-expand + contract step: init the accumulator tile on
    the first K step, decompress the values tile through the in-VMEM M:1
    mux, and accumulate ``x @ w``.  ONE body for the float and int8
    (scaled and raw) kernels, so their numerics cannot drift apart."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _mux_tile(v_ref, pm_ref, n)
    acc_ref[...] += jnp.dot(x_ref[...], w, preferred_element_type=acc_dtype)


def _spmm_kernel(*refs, n: int, nk: int, acc_dtype, quant: bool,
                 epi: EpilogueSpec):
    """ONE flush body for the float and scaled-quantized N:M SpMMs.

    Ref order: x, values, meta, [xs, ws (quant)], [bias], [rq_scale],
    out, acc — the epilogue lattice point is applied to the dequantized
    fp32 accumulator tile before the single HBM write-back.
    """
    it = list(refs)
    x_ref, v_ref, pm_ref = it[0], it[1], it[2]
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

    # the M:1 mux is exact for narrow dtypes too: at most one nonzero per
    # expanded slot (int8 stays in [-127, 127]; fp8 x + 0 is exact)
    _spmm_accumulate(x_ref, v_ref, pm_ref, acc_ref, n, acc_dtype)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        t = acc_ref[...].astype(jnp.float32)
        if quant:
            t = t * xs_ref[...] * ws_ref[...]
        o_ref[...] = flush_tile(
            t, epi, o_ref.dtype,
            bias_tile=None if bias_ref is None else bias_ref[...],
            rq_scale=None if rq_ref is None else rq_ref[0, 0])


def nm_spmm(
    x: jax.Array,
    values: jax.Array,
    meta_packed: jax.Array,
    n: int,
    *,
    block_b: int = 128,
    block_o: int = 128,
    block_ke: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
    epilogue: EpilogueSpec = None,
    bias: jax.Array = None,
    requant_scale=None,
) -> jax.Array:
    """Y = X @ dec(values, meta).  M is fixed at 4 (paper's detailed design).

    x: (B, K_eff) -- K_eff = K_c * 4 / n
    values: (K_c, O), meta_packed: (K_c/4, O) uint8
    """
    epi = epilogue or _IDENT
    b, ke = x.shape
    kc, o = values.shape
    assert ke * n == kc * 4, (x.shape, values.shape, n)
    assert meta_packed.shape == (kc // 4, o), meta_packed.shape
    block_b = min(block_b, b)
    block_o = min(block_o, o)
    block_ke = min(block_ke, ke)
    assert b % block_b == 0 and o % block_o == 0 and ke % block_ke == 0
    block_kc = block_ke * n // 4
    assert block_kc % 4 == 0, "block_ke*n/4 must be a multiple of 4 for packing"
    nk = ke // block_ke
    x = slab_order(x, n, block_ke, values.dtype)
    return pl.pallas_call(
        lambda *refs: _spmm_kernel(*refs, n=n, nk=nk, acc_dtype=jnp.float32,
                                   quant=False, epi=epi),
        grid=(b // block_b, o // block_o, nk),
        in_specs=[
            pl.BlockSpec((block_b, block_ke), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_kc, block_o), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_kc // 4, block_o), lambda i, j, kk: (kk, j)),
        ] + tile_in_specs(epi, block_o),
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, o), out_dtype_for(epi, out_dtype)),
        scratch_shapes=[pltpu.VMEM((block_b, block_o), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        **kernel_label("nm_spmm", "nm_spmm", values.dtype),
    )(x, values, meta_packed, *tile_operands(epi, bias, requant_scale, o))


def _spmm_q_raw_kernel(x_ref, v_ref, pm_ref, o_ref, acc_ref,
                       *, n: int, nk: int, acc_dtype):
    _spmm_accumulate(x_ref, v_ref, pm_ref, acc_ref, n, acc_dtype)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        # raw accumulator out (int32 / fp32): the sharded-contraction
        # class psums these partials and dequantizes once on the result
        o_ref[...] = acc_ref[...]


def _nm_spmm_quantized(
    x_q, values, meta_packed, x_scale, w_scale, n, *, acc_dtype,
    block_b, block_o, block_ke, out_dtype, interpret,
    epilogue: EpilogueSpec = None, bias=None, requant_scale=None,
) -> jax.Array:
    """Shared pallas_call plumbing for the int8 and fp8 N:M SpMMs —
    ONE implementation parameterized by the accumulator dtype.  The
    scaled branch takes an epilogue lattice point applied at the flush;
    the raw branch never does (its contract is the exact accumulator)."""
    epi = epilogue or _IDENT
    b, ke = x_q.shape
    kc, o = values.shape
    assert ke * n == kc * 4, (x_q.shape, values.shape, n)
    assert meta_packed.shape == (kc // 4, o), meta_packed.shape
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
    block_ke = min(block_ke, ke)
    assert b % block_b == 0 and o % block_o == 0 and ke % block_ke == 0
    block_kc = block_ke * n // 4
    assert block_kc % 4 == 0, "block_ke*n/4 must be a multiple of 4 for packing"
    nk = ke // block_ke
    x_q = slab_order(x_q, n, block_ke, values.dtype)
    if raw:
        return pl.pallas_call(
            lambda xr, vr, pr, orf, acc: _spmm_q_raw_kernel(
                xr, vr, pr, orf, acc, n=n, nk=nk, acc_dtype=acc_dtype),
            grid=(b // block_b, o // block_o, nk),
            in_specs=[
                pl.BlockSpec((block_b, block_ke), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((block_kc, block_o), lambda i, j, kk: (kk, j)),
                pl.BlockSpec((block_kc // 4, block_o), lambda i, j, kk: (kk, j)),
            ],
            out_specs=pl.BlockSpec((block_b, block_o), lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((b, o), acc_dtype),
            scratch_shapes=[pltpu.VMEM((block_b, block_o), acc_dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
            **kernel_label("nm_spmm_raw", "nm_spmm", values.dtype),
        )(x_q, values, meta_packed)
    return pl.pallas_call(
        lambda *refs: _spmm_kernel(*refs, n=n, nk=nk, acc_dtype=acc_dtype,
                                   quant=True, epi=epi),
        grid=(b // block_b, o // block_o, nk),
        in_specs=[
            pl.BlockSpec((block_b, block_ke), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_kc, block_o), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_kc // 4, block_o), lambda i, j, kk: (kk, j)),
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
        **kernel_label("nm_spmm", "nm_spmm", values.dtype),
    )(x_q, values, meta_packed, x_scale, w_scale,
      *tile_operands(epi, bias, requant_scale, o))


def _spmm_masked_kernel(*refs, n: int, nk: int, acc_dtype, quant: bool,
                        epi: EpilogueSpec):
    """Activation-sparsity (block-skip) flush body for the compressed
    family.  Ref order: kmap, kmask (scalar prefetch), then exactly the
    :func:`_spmm_kernel` order.  Init is SPLIT from the accumulate (step
    kk==0 may be dead); the mux-expand + dot run only on live blocks —
    dead x blocks are exact zeros, so the skip is bit-identical and the
    kmap-driven index maps elide the x/values/meta copies too."""
    it = list(refs)
    kmask_ref = it[1]
    x_ref, v_ref, pm_ref = it[2], it[3], it[4]
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
        w = _mux_tile(v_ref, pm_ref, n)
        acc_ref[...] += jnp.dot(x_ref[...], w,
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


def nm_spmm_masked(
    x: jax.Array,
    values: jax.Array,
    meta_packed: jax.Array,
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
    """:func:`nm_spmm` with an in-kernel activation-sparsity block skip —
    the sparse-activation x N:M-weight SpGEMM case.  ``kmap``/``kmask``
    are ``(B/block_b, K_eff/block_ke)`` int32 maps from
    ``repro.kernels.actsparse.block_maps`` over the masked ``x``; they
    ride the grid as scalar-prefetch operands.  Float when ``x_scale is
    None``; scaled-quantized with both scales (``acc_dtype`` int32 for
    int8, fp32 for fp8).  Bit-identical to the unmasked kernel on the
    same masked ``x``.
    """
    epi = epilogue or _IDENT
    b, ke = x.shape
    kc, o = values.shape
    assert ke * n == kc * 4, (x.shape, values.shape, n)
    assert meta_packed.shape == (kc // 4, o), meta_packed.shape
    quant = x_scale is not None
    assert quant == (w_scale is not None), "pass both scales or neither"
    if not quant:
        acc_dtype = jnp.float32
    else:
        assert x_scale.shape == (b, 1) and w_scale.shape == (1, o), (
            x_scale.shape, w_scale.shape)
    block_b = min(block_b, b)
    block_o = min(block_o, o)
    block_ke = min(block_ke, ke)
    assert b % block_b == 0 and o % block_o == 0 and ke % block_ke == 0
    block_kc = block_ke * n // 4
    assert block_kc % 4 == 0, "block_ke*n/4 must be a multiple of 4 for packing"
    nk = ke // block_ke
    x = slab_order(x, n, block_ke, values.dtype)
    assert kmap.shape == (b // block_b, nk) == kmask.shape, (
        kmap.shape, kmask.shape, (b // block_b, nk))

    in_specs = [
        pl.BlockSpec((block_b, block_ke),
                     lambda i, j, kk, kmap_, kmask_: (i, kmap_[i, kk])),
        pl.BlockSpec((block_kc, block_o),
                     lambda i, j, kk, kmap_, kmask_: (kmap_[i, kk], j)),
        pl.BlockSpec((block_kc // 4, block_o),
                     lambda i, j, kk, kmap_, kmask_: (kmap_[i, kk], j)),
    ]
    operands = [x, values, meta_packed]
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
        lambda *refs: _spmm_masked_kernel(*refs, n=n, nk=nk,
                                          acc_dtype=acc_dtype, quant=quant,
                                          epi=epi),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, o), out_dtype_for(epi, out_dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        **kernel_label("nm_spmm_masked", "nm_spmm", values.dtype),
    )(kmap, kmask, *operands)


def _spmm_dual_kernel(*refs, n: int, nk: int, acc_dtype, quant: bool,
                      epi: EpilogueSpec):
    """Fused gate-up flush for the compressed family: two N:M SpMMs over
    ONE activation tile read.  Ref order: x, v_g, pm_g, v_u, pm_u,
    [xs, ws_g, ws_u (quant)], [rq_scale], out, acc_g, acc_u.
    """
    it = list(refs)
    x_ref, vg_ref, pmg_ref, vu_ref, pmu_ref = it[:5]
    p = 5
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

    xv = x_ref[...]  # ONE read feeds both mux-expanded contractions
    wg = _mux_tile(vg_ref, pmg_ref, n)
    wu = _mux_tile(vu_ref, pmu_ref, n)
    accg_ref[...] += jnp.dot(xv, wg, preferred_element_type=acc_dtype)
    accu_ref[...] += jnp.dot(xv, wu, preferred_element_type=acc_dtype)

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


def nm_spmm_dual(
    x, values_g, meta_g, values_u, meta_u, n: int,
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
    """Fused gate-up over two compressed N:M weights sharing one x:
    ``silu(x @ dec(v_g)) * (x @ dec(v_u))`` in one pallas_call.  Float
    when ``x_scale is None``; quantized when the three scales are given
    (``acc_dtype`` int32 for int8, fp32 for fp8).
    """
    epi = epilogue or EpilogueSpec(act="silu_mul")
    assert epi.act == "silu_mul" and not epi.bias, epi.point
    b, ke = x.shape
    kc, o = values_g.shape
    assert ke * n == kc * 4, (x.shape, values_g.shape, n)
    assert values_u.shape == (kc, o)
    assert meta_g.shape == (kc // 4, o) and meta_u.shape == (kc // 4, o)
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
    assert block_kc % 4 == 0, "block_ke*n/4 must be a multiple of 4 for packing"
    nk = ke // block_ke
    x = slab_order(x, n, block_ke, values_g.dtype)
    x_spec = pl.BlockSpec((block_b, block_ke), lambda i, j, kk: (i, kk))
    v_spec = pl.BlockSpec((block_kc, block_o), lambda i, j, kk: (kk, j))
    pm_spec = pl.BlockSpec((block_kc // 4, block_o), lambda i, j, kk: (kk, j))
    in_specs = [x_spec, v_spec, pm_spec, v_spec, pm_spec]
    operands = [x, values_g, meta_g, values_u, meta_u]
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
        lambda *refs: _spmm_dual_kernel(*refs, n=n, nk=nk,
                                        acc_dtype=acc_dtype, quant=quant,
                                        epi=epi),
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
        **kernel_label("nm_spmm_dual", "nm_spmm", values_g.dtype),
    )(*operands)


def nm_spmm_int8(
    x_q: jax.Array,
    values: jax.Array,
    meta_packed: jax.Array,
    x_scale: jax.Array,
    w_scale: jax.Array,
    n: int,
    *,
    block_b: int = 128,
    block_o: int = 128,
    block_ke: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
    epilogue: EpilogueSpec = None,
    bias: jax.Array = None,
    requant_scale=None,
) -> jax.Array:
    """Int8 VNNI-lineage variant: Y = (x_q*xs) @ dec(values*ws, meta).

    x_q: (B, K_eff) int8; values: (K_c, O) int8; meta_packed as in
    :func:`nm_spmm`; x_scale: (B, 1) f32; w_scale: (1, O) f32.  The
    compressed int8 values expand through the same in-VMEM M:1 mux, the
    MXU contracts int8 x int8 into an int32 VMEM accumulator, and both
    scale vectors are applied once at the flush — int8 values + 2-bit
    metadata is exactly the paper's tile-register storage model.

    ``x_scale=None``/``w_scale=None`` returns the raw int32 accumulator
    (``out_dtype`` forced to int32) for the psum-then-dequantize sharded
    ordering.
    """
    return _nm_spmm_quantized(
        x_q, values, meta_packed, x_scale, w_scale, n, acc_dtype=jnp.int32,
        block_b=block_b, block_o=block_o, block_ke=block_ke,
        out_dtype=out_dtype, interpret=interpret,
        epilogue=epilogue, bias=bias, requant_scale=requant_scale)


def nm_spmm_fp8(
    x_q: jax.Array,
    values: jax.Array,
    meta_packed: jax.Array,
    x_scale: jax.Array,
    w_scale: jax.Array,
    n: int,
    *,
    block_b: int = 128,
    block_o: int = 128,
    block_ke: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
    epilogue: EpilogueSpec = None,
    bias: jax.Array = None,
    requant_scale=None,
) -> jax.Array:
    """fp8 (e4m3fn) variant: same contract as :func:`nm_spmm_int8` with
    fp8 operands and an **fp32** VMEM accumulator.  The in-VMEM M:1 mux
    is exact for fp8 (each expanded slot receives one value or zero),
    the MXU contracts fp8 x fp8 with ``preferred_element_type=float32``,
    and both scales are applied once at the flush.

    ``x_scale=None``/``w_scale=None`` returns the raw fp32 accumulator
    for the psum-then-dequantize sharded ordering.
    """
    return _nm_spmm_quantized(
        x_q, values, meta_packed, x_scale, w_scale, n, acc_dtype=jnp.float32,
        block_b=block_b, block_o=block_o, block_ke=block_ke,
        out_dtype=out_dtype, interpret=interpret,
        epilogue=epilogue, bias=bias, requant_scale=requant_scale)
