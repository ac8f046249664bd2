"""The grid the three GEMM families share.

``tile_gemm``, ``nm_spmm`` and ``nm_spmm_gather`` each build one
``pallas_call`` computing ``Y (B, O) = X (B, K) @ W (K, O)`` over a
``(B/bb, O/bo, K/bk)`` grid, with the accumulator tile held in VMEM
across the K axis (int32 for int8 weights, fp32 otherwise) and written
back once.  A family differs only in how its weight is stored (its
weight *leaves*, each with its own block) and in how one K step
contracts an x tile against them; :func:`spec` builds everything else,
and :func:`_body` is the one grid body.  The forms are arguments:

- ``up``: a second weight of the same shape, contracted against the
  same x tile into a second accumulator (fused gate-up); the flush
  emits ``silu(g) * u``;
- ``x_scale`` ``(B, 1)``, ``w_scale`` and ``up_scale`` ``(1, O)``:
  quantized operands, dequantized once at the flush;
- ``raw``: the undequantized accumulator out, so partial products over
  a sharded contraction are psum'd before the one dequantize;
- ``kmap`` / ``kmask``: ``(B/bb, K/bk)`` activation block-skip maps
  (``repro.kernels.actsparse.block_maps``), scalar-prefetched:
  ``kmask`` gates the K step, ``kmap`` re-addresses a dead block to the
  last live one so its copies are elided.  Dead x blocks are exact
  zeros, so the skip is bit-identical;
- ``epilogue`` with ``bias`` / ``requant_scale``: the lattice point
  the flush applies (``repro.kernels.epilogue``).

Operand order: ``[kmap, kmask], x, weight leaves, [up leaves],
[x_scale, w_scale, [up_scale]], [bias], [requant scale]``.  Without
block-skip maps the call takes ``grid=`` / ``in_specs=`` directly.
"""

from __future__ import annotations

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogue import (
    EpilogueSpec, flush_tile, out_dtype_for, tile_in_specs, tile_operands,
)
from repro.kernels.registry import kernel_label


@dataclasses.dataclass(frozen=True)
class _Form:
    """Which operands one call carries: the layout of its kernel refs."""

    leaves: int            # weight leaves per weight
    dual: bool
    quant: bool
    raw: bool
    masked: bool
    epi: EpilogueSpec


def _unpack(refs, f: _Form) -> types.SimpleNamespace:
    it = iter(refs)

    def take(k):
        return [next(it) for _ in range(k)]

    nw = 2 if f.dual else 1
    kmap, kmask = take(2) if f.masked else (None, None)
    x = next(it)
    w = [tuple(take(f.leaves)) for _ in range(nw)]
    xs, ws = (next(it), take(nw)) if f.quant else (None, None)
    bias = next(it) if f.epi.bias else None
    rq = next(it) if f.epi.requant else None
    out = next(it)
    return types.SimpleNamespace(kmap=kmap, kmask=kmask, x=x, w=w, xs=xs,
                                 ws=ws, bias=bias, rq=rq, out=out,
                                 acc=take(nw))


def _xs_ws(t, xs, ws_ref):
    return t * xs * ws_ref[...]


def _body(*refs, form: _Form, nk: int, contract, read, dequant):
    """Zero the accumulators on the first K step, run the family's
    ``contract`` (only on live blocks when masked: the init stays
    unconditional because step 0 may be dead), and flush on the last:
    the raw accumulator, or the dequantized tiles through the epilogue."""
    r = _unpack(refs, form)
    i, kk = pl.program_id(0), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        for acc in r.acc:
            acc[...] = jnp.zeros_like(acc)

    if r.kmask is None:
        contract(r)
    else:
        pl.when(r.kmask[i, kk] != 0)(lambda: contract(r))

    @pl.when(kk == nk - 1)
    def _flush():
        if form.raw:
            r.out[...] = read(r.acc[0])
            return
        ts = [read(acc).astype(jnp.float32) for acc in r.acc]
        if form.quant:
            xs = r.xs[...]
            ts = [dequant(t, xs, ws) for t, ws in zip(ts, r.ws)]
        r.out[...] = flush_tile(
            ts[0], form.epi, r.out.dtype,
            bias_tile=None if r.bias is None else r.bias[...],
            rq_scale=None if r.rq is None else r.rq[0, 0],
            acc2_32=ts[1] if form.dual else None)


def blocks(b: int, k: int, o: int, block_b: int, block_k: int,
           block_o: int):
    """The blocks clamped to the problem; each must split its dim."""
    bb, bk, bo = min(block_b, b), min(block_k, k), min(block_o, o)
    assert b % bb == 0 and k % bk == 0 and o % bo == 0, (
        (b, k, o), (bb, bk, bo))
    return bb, bk, bo


def _k_block(i, kk, refs):
    """The K block a grid step reads: ``kmap``'s entry when masked."""
    return refs[0][i, kk] if refs else kk


def _leaf_spec(rows: int, bo):
    """A weight leaf's block: ``rows`` of the K block, and ``bo`` columns
    along O (or, with ``bo=None``, the leaf's single column)."""
    if bo is None:
        return pl.BlockSpec((rows, 1),
                            lambda i, j, kk, *r: (_k_block(i, kk, r), 0))
    return pl.BlockSpec((rows, bo),
                        lambda i, j, kk, *r: (_k_block(i, kk, r), j))


def spec(family: str, contract, x, w, leaf_rows, bks, *, up=None,
         x_scale=None, w_scale=None, up_scale=None, raw=False, kmap=None,
         kmask=None, out_dtype=jnp.float32, interpret=False, epilogue=None,
         bias=None, requant_scale=None, acc_t=False, dequant=_xs_ws):
    """``(kernel, pallas_call keywords, operands)`` of one family call.

    ``w`` (and ``up``) are the weight's leaves; ``leaf_rows`` gives each
    leaf's block as ``(rows, along_o)``; ``bks`` the clamped
    ``(bb, bk, bo)`` (:func:`blocks`).  ``contract(r, acc_dtype)`` is the
    family's K step over the unpacked refs.  ``acc_t``: the accumulator
    tile is ``(bo, bb)`` and is transposed on the way out.  The form
    arguments are described in the module docstring.
    """
    b, o = x.shape[0], w[0].shape[1]
    bb, bk, bo = bks
    nk = x.shape[1] // bk
    dual, quant = up is not None, x_scale is not None
    masked = kmap is not None
    ws = [w_scale] + ([up_scale] if dual else [])
    assert all((s is not None) == quant for s in ws), "every scale or none"
    epi = epilogue or EpilogueSpec(act="silu_mul" if dual else None)
    assert dual == (epi.act == "silu_mul") and not (dual and epi.bias), (
        epi.point)
    if raw:
        assert not quant and epi.is_identity, "raw calls take no scales"
    if quant:
        assert x_scale.shape == (b, 1) and all(
            s.shape == (1, o) for s in ws), (x_scale.shape, ws[0].shape)
    if masked:
        assert kmap.shape == (b // bb, nk) == kmask.shape, (
            kmap.shape, kmask.shape, (b // bb, nk))
    acc = jnp.int32 if jnp.dtype(w[0].dtype) == jnp.int8 else jnp.float32
    leaf_specs = [_leaf_spec(rows, bo if along_o else None)
                  for rows, along_o in leaf_rows]
    in_specs = [pl.BlockSpec((bb, bk),
                             lambda i, j, kk, *r: (i, _k_block(i, kk, r)))]
    in_specs += leaf_specs * len(ws)
    operands = [x, *w, *(up or ())]
    if quant:
        in_specs += [pl.BlockSpec((bb, 1), lambda i, j, kk, *_: (i, 0))]
        in_specs += [pl.BlockSpec((1, bo),
                                  lambda i, j, kk, *_: (0, j))] * len(ws)
        operands += [x_scale, *ws]
    in_specs += tile_in_specs(epi, bo)
    operands += tile_operands(epi, bias, requant_scale, o)
    grid = dict(grid=(b // bb, o // bo, nk), in_specs=in_specs,
                out_specs=pl.BlockSpec((bb, bo), lambda i, j, kk, *_: (i, j)),
                scratch_shapes=[pltpu.VMEM((bo, bb) if acc_t else (bb, bo),
                                           acc)] * len(ws))
    form_name = family + ("_raw" if raw else "_masked" if masked
                          else "_dual" if dual else "")
    kw = dict(out_shape=jax.ShapeDtypeStruct(
                  (b, o), acc if raw else out_dtype_for(epi, out_dtype)),
              compiler_params=pltpu.CompilerParams(
                  dimension_semantics=("parallel", "parallel", "arbitrary")),
              interpret=interpret,
              **kernel_label(form_name, family, w[0].dtype))
    if masked:
        kw["grid_spec"] = pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=2,
                                                       **grid)
        operands = [kmap, kmask, *operands]
    else:
        kw.update(grid)
    form = _Form(leaves=len(w), dual=dual, quant=quant, raw=raw,
                masked=masked, epi=epi)
    kernel = functools.partial(
        _body, form=form, nk=nk,
        contract=functools.partial(contract, acc_dtype=acc),
        read=(lambda a: a[...].T) if acc_t else (lambda a: a[...]),
        dequant=dequant)
    return kernel, kw, operands
