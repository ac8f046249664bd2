"""N:M structured-sparse GEMM Pallas kernel — TILE_SPMM_{U,V,T} adaptation.

Computes ``Y (B, O) = X (B, K_eff) @ dec(V, meta) (K_eff, O)`` where the
weight is stored *compressed*: ``V (K_c, O)`` keeps only the N nonzeros per
M=4 block of K, and ``meta_packed (K_c/4, O) uint8`` carries four 2-bit
in-block indices per byte (the mreg adaptation).

TPU mapping of the paper's SPE input-mux (``docs/paper-mapping.md``):
  * the dense weight tile **never exists in HBM** — HBM traffic for the
    sparse operand is N/M of dense (+ 2-bit metadata);
  * the M:1 mux becomes a VPU one-hot select producing the expanded
    ``(BK_eff, BO)`` tile in VMEM, amortized over the MXU's BB-deep
    matmul;
  * the accumulator tile lives in VMEM across the K grid — the "output
    forwarding" equivalent (no C round-trip between accumulating
    instructions).  The grid, the operand order and the forms (fused
    gate-up, quantized, raw accumulator, block skip, epilogue) are
    ``repro.kernels.gemm``'s; the mux is exact for every value dtype
    (each expanded slot receives one value or zero).

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

from repro.kernels import gemm

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


def _contract(r, n: int, acc_dtype):
    """One K step: expand each weight tile through the M:1 mux and
    accumulate ``x @ w``; a fused gate-up reads the x tile once."""
    xv = r.x[...] if len(r.acc) == 2 else None
    ws = [_mux_tile(v, pm, n) for v, pm in r.w]
    for acc, w in zip(r.acc, ws):
        acc[...] += jnp.dot(r.x[...] if xv is None else xv, w,
                            preferred_element_type=acc_dtype)


def nm_spmm(x: jax.Array, values: jax.Array, meta_packed: jax.Array, n: int,
            *, up=None, block_b: int = 128, block_k: int = 512,
            block_o: int = 128, **form) -> jax.Array:
    """Y = X @ dec(values, meta).  M is fixed at 4 (paper's detailed design).

    x: (B, K_eff) -- K_eff = K_c * 4 / n
    values: (K_c, O), meta_packed: (K_c/4, O) uint8
    ``up=(values_up, meta_up)`` fuses the gate-up ``silu(x @ dec(values))
    * (x @ dec(values_up))``.  ``form``: the keyword forms of
    :func:`repro.kernels.gemm.spec`.
    """
    b, ke = x.shape
    kc, o = values.shape
    assert ke * n == kc * 4, (x.shape, values.shape, n)
    for v, pm in [(values, meta_packed)] + ([up] if up else []):
        assert v.shape == (kc, o) and pm.shape == (kc // 4, o), (
            v.shape, pm.shape)
    bks = gemm.blocks(b, ke, o, block_b, block_k, block_o)
    bkc = bks[1] * n // 4
    assert bkc % 4 == 0, "block_k*n/4 must be a multiple of 4 for packing"
    x = slab_order(x, n, bks[1], values.dtype)
    kernel, kw, operands = gemm.spec(
        "nm_spmm", lambda r, acc_dtype: _contract(r, n, acc_dtype), x,
        (values, meta_packed), [(bkc, True), (bkc // 4, True)], bks,
        up=up, **form)
    return pl.pallas_call(kernel, **kw)(*operands)
