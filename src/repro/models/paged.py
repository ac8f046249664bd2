"""Paged-KV decode: block-table attention + chunked prefill for serving.

The serving engine (``repro.serving``) stores KV in fixed-size blocks —
one pool per attention slot, shaped ``(num_blocks, block_len, Hkv, D)``
with the usual stacked ``(stage_count, repeat)`` leading dims — and maps
each request's logical positions onto physical blocks through a per-slot
block table.  This module is the model-side contract: the same
stage/slot walker as contiguous decode (:func:`repro.models.transformer.
cached_stack`), with the attention mixer swapped for a scatter-into-pool
/ gather-by-table pair.

Numerics match contiguous decode exactly: the gathered keys are the very
values the contiguous cache would hold, every position outside
``j <= pos`` (plus the sliding-window band on local layers) is masked to
``NEG_INF`` before the softmax, so the extra pool entries contribute
exactly 0 probability and the outputs are bit-identical per request
(asserted in ``tests/test_serving.py``).

KV-cache quantization rides the same dtype-parametric scale machinery as
the weight side (``repro.core.quantize``): with ``kv_qdtype`` set, pools
store int8 / fp8 values plus a per-(position, head) float32 scale leaf,
written by ``quantize_rows`` over the head vector and dequantized on
gather.

Position handling is per-token: ``positions`` has shape ``(B, T)`` so a
batched decode step (``T=1``, one position per slot — ragged lengths) and
a prefill chunk (``B=1``, ``T=chunk``) share one attention body.  Writes
are masked: idle slots and padding tokens scatter into the reserved
scratch block 0, which no table row ever references for a live position.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.quantize import canonical_qdtype, quantize_rows
from repro.core.sparse_linear import apply_linear

from .attention import NEG_INF, _grouped, _project_qkv
from .config import ModelConfig
from .layers import embed
from .pjit_utils import AxisEnv, axis_env
from .ssm import decode_mamba_block, init_ssm_cache
from .transformer import build_layout, cached_stack

Params = Dict[str, Any]

__all__ = [
    "init_paged_caches",
    "paged_cache_shardings",
    "paged_decode_step",
    "paged_prefill_chunk",
    "reset_slot_state",
]


def init_paged_caches(
    cfg: ModelConfig,
    num_blocks: int,
    block_len: int,
    batch: int,
    kv_qdtype: Optional[str] = None,
) -> List[Dict[str, Any]]:
    """Paged cache pytree mirroring the stage/slot layout.

    Attention slots hold block pools ``(num_blocks, block_len, Hkv, D)``
    (`num_blocks` INCLUDES the reserved scratch block 0); SSM slots keep
    their per-request recurrent state ``(batch, ...)`` exactly as in the
    contiguous path — Mamba state is O(1) per request, so paging applies
    to attention only.  With ``kv_qdtype`` the pools store the narrow
    dtype plus per-(position, head) scales.
    """
    store_dt = cfg.jnp_dtype if kv_qdtype is None else canonical_qdtype(kv_qdtype)
    layout = build_layout(cfg)
    caches = []
    for st in layout:
        stage_c = {}
        for j, slot in enumerate(st.slots):
            if slot.mixer in ("attn", "attn_local"):
                shape = (num_blocks, block_len, cfg.num_kv_heads, cfg.head_dim)
                one = {"k": jnp.zeros(shape, store_dt),
                       "v": jnp.zeros(shape, store_dt)}
                if kv_qdtype is not None:
                    sshape = shape[:-1]
                    one["k_scale"] = jnp.zeros(sshape, jnp.float32)
                    one["v_scale"] = jnp.zeros(sshape, jnp.float32)
            else:
                one = init_ssm_cache(cfg, batch)
            stage_c[f"slot{j}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (st.count, slot.repeat) + a.shape),
                one,
            )
        caches.append(stage_c)
    return caches


# the (heads, slots) dims of each paged-cache leaf: pools and their
# int8/fp8 scales keep blocks and positions whole; per-slot SSM state
# ``(count, repeat, slots, ...)`` puts its slots over the batch axes
_CACHE_DIMS = {"k": (-2, None), "v": (-2, None),
               "k_scale": (-1, None), "v_scale": (-1, None),
               "state": (3, 2), "conv": (None, 2)}


def heads_spec(env: AxisEnv, shape, heads_dim: Optional[int] = None,
               batch_dim: Optional[int] = None) -> P:
    """Heads at ``heads_dim`` over the model axis where whole heads
    divide it, ``batch_dim`` over the batch axes where it divides them,
    every other dim whole.  Trailing whole dims are left out of the
    spec, as a compiled program states its outputs' placement: a pool
    born under this spec and one a paged step returns then key the same
    compiled step."""
    mesh, spec = env.mesh, [None] * len(shape)
    if (heads_dim is not None
            and shape[heads_dim] % mesh.shape[env.model_axis] == 0):
        spec[heads_dim] = env.model_axis
    if (batch_dim is not None and shape[batch_dim]
            % math.prod(mesh.shape[a] for a in env.batch_axes) == 0):
        spec[batch_dim] = env.physical("batch")
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def paged_cache_shardings(env: AxisEnv, caches) -> Any:
    """Where paged caches (:func:`init_paged_caches`, one layer's or the
    stacked tree) live on ``env``'s mesh: a pool's KV heads over the
    model axis where whole heads divide it, as the column-parallel
    ``wk``/``wv`` shard them, and its scales like their pool; SSM state
    by its heads and slots.  The engine births its pools under this and
    both paged steps take and return them so."""
    def fn(path, leaf):
        heads, batch = _CACHE_DIMS.get(getattr(path[-1], "key", None),
                                       (None, None))
        return NamedSharding(env.mesh,
                             heads_spec(env, leaf.shape, heads, batch))

    return jax.tree_util.tree_map_with_path(fn, caches)


def _on_heads(env: Optional[AxisEnv], x: jax.Array) -> jax.Array:
    """(B, T, H, D) -> the same, heads over model, batch over DP."""
    if env is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(
        env.mesh, heads_spec(env, x.shape, 2, batch_dim=0)))


def _place_pools(env: Optional[AxisEnv], caches):
    """Caches under :func:`paged_cache_shardings`; no-op off-mesh."""
    if env is None:
        return caches
    return jax.lax.with_sharding_constraint(
        caches, paged_cache_shardings(env, caches))


def _write_kv(cache, k_new, v_new, phys, off, kv_qdtype):
    """Scatter N new (head, dim) vectors into the pools at (phys, off)."""
    out = dict(cache)
    if kv_qdtype is None:
        out["k"] = cache["k"].at[phys, off].set(k_new.astype(cache["k"].dtype))
        out["v"] = cache["v"].at[phys, off].set(v_new.astype(cache["v"].dtype))
        return out
    n, h, d = k_new.shape
    kq, ks = quantize_rows(k_new.reshape(n * h, d), dtype=kv_qdtype)
    vq, vs = quantize_rows(v_new.reshape(n * h, d), dtype=kv_qdtype)
    out["k"] = cache["k"].at[phys, off].set(kq.reshape(n, h, d))
    out["v"] = cache["v"].at[phys, off].set(vq.reshape(n, h, d))
    out["k_scale"] = cache["k_scale"].at[phys, off].set(ks.reshape(n, h))
    out["v_scale"] = cache["v_scale"].at[phys, off].set(vs.reshape(n, h))
    return out


def _gather_kv(cache, table, kv_qdtype, out_dtype):
    """Block-table gather -> (B, W*block_len, Hkv, D) contiguous views."""
    k = cache["k"][table]                       # (B, W, BL, H, D)
    b, w, bl, h, d = k.shape
    k = k.reshape(b, w * bl, h, d)
    v = cache["v"][table].reshape(b, w * bl, h, d)
    if kv_qdtype is not None:
        ks = cache["k_scale"][table].reshape(b, w * bl, h)
        vs = cache["v_scale"][table].reshape(b, w * bl, h)
        k = (k.astype(jnp.float32) * ks[..., None]).astype(out_dtype)
        v = (v.astype(jnp.float32) * vs[..., None]).astype(out_dtype)
    return k, v


def _paged_attention(
    p: Params,
    x: jax.Array,             # (B, T, d)
    cache: Dict[str, jax.Array],
    positions: jax.Array,     # (B, T) int32 per-token positions
    table: jax.Array,         # (B, W) int32 physical block ids
    write_mask: jax.Array,    # (B, T) bool: False -> scratch block
    cfg: ModelConfig,
    *,
    is_global: bool,
    block_len: int,
    kv_qdtype: Optional[str],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    b, t, _ = x.shape
    # q, the new KV rows, the pool and its gather all keep their heads
    # where the column-parallel projections leave them: each chip writes,
    # gathers and attends its own heads, and no pool leaves its chip
    env = axis_env()
    q, k_new, v_new = (_on_heads(env, a)
                       for a in _project_qkv(p, x, cfg, positions))
    cache = _place_pools(env, cache)
    blk = positions // block_len
    off = positions % block_len
    phys = jnp.take_along_axis(table, blk, axis=1)          # (B, T)
    phys = jnp.where(write_mask, phys, 0).reshape(b * t)
    off = jnp.where(write_mask, off, 0).reshape(b * t)
    new_cache = _write_kv(
        cache,
        k_new.reshape(b * t, cfg.num_kv_heads, cfg.head_dim),
        v_new.reshape(b * t, cfg.num_kv_heads, cfg.head_dim),
        phys, off, kv_qdtype)
    new_cache = _place_pools(env, new_cache)

    k, v = (_on_heads(env, a)
            for a in _gather_kv(new_cache, table, kv_qdtype, x.dtype))
    qg = _grouped(q, cfg)                                   # (B,Hkv,G,T,D)
    scale = cfg.head_dim**-0.5
    s = jnp.einsum(
        "bhgqd,bkhd->bhgqk", qg * jnp.asarray(scale, qg.dtype), k,
        preferred_element_type=jnp.float32,
    )                                                       # (B,Hkv,G,T,L)
    j = jnp.arange(k.shape[1])
    valid = j[None, None, :] <= positions[:, :, None]       # (B, T, L)
    if not is_global and cfg.window > 0:
        valid = valid & (positions[:, :, None] - j[None, None, :] < cfg.window)
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    # same fp32-probability contract as decode_attention_block
    o = jnp.einsum(
        "bhgqk,bkhd->bhgqd",
        pr.astype(v.dtype) if cfg.attn_p_bf16 else pr, v,
        preferred_element_type=jnp.float32,
    )
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, t, cfg.attn_dim).astype(x.dtype)
    return apply_linear(p["wo"], o, cfg.sparsity, gather="row"), new_cache


def _masked_decode_mamba(p, x, cache, update_mask, cfg):
    """One SSM decode step whose state update is gated per batch row —
    idle / prefilling slots in a batched decode must not advance their
    recurrent state."""
    o, c2 = decode_mamba_block(p, x, cache, cfg)
    def _sel(a, b_):
        m = update_mask.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b_)
    return o, jax.tree.map(_sel, c2, cache)


def _prefill_mamba(p, x, cache, n_valid, cfg):
    """Chunked SSM prefill as an exact per-token scan of the decode step
    (token t's update is dropped once ``t >= n_valid``)."""
    c = x.shape[1]

    def step(lc, t):
        xt = jax.lax.dynamic_slice_in_dim(x, t, 1, axis=1)
        o, c2 = decode_mamba_block(p, xt, lc, cfg)
        keep = t < n_valid
        nc = jax.tree.map(lambda a, b_: jnp.where(keep, a, b_), c2, lc)
        return nc, o[:, 0]

    cache, outs = jax.lax.scan(step, cache, jnp.arange(c))
    return outs.transpose(1, 0, 2), cache


def _prefill_mamba_slot(p, x, cache, n_valid, slot_idx, cfg):
    """SSM prefill targeting one batch row of the slots-wide cache.

    Prefill runs one request (``x`` is batch 1) but the engine's SSM
    caches are batch=slots, so the scan works on a sliced row and the
    result is scattered back into row ``slot_idx`` — every other slot's
    recurrent state passes through untouched."""
    row = jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, slot_idx, 1, axis=0),
        cache)
    o, row = _prefill_mamba(p, x, row, n_valid, cfg)
    new = jax.tree.map(
        lambda full, r: jax.lax.dynamic_update_slice_in_dim(
            full, r, slot_idx, axis=0),
        cache, row)
    return o, new


@partial(jax.jit, static_argnames=("cfg", "block_len", "kv_qdtype"))
def paged_decode_step(
    params: Params,
    caches: List[Dict[str, Any]],
    tokens: jax.Array,        # (B, 1) int32
    positions: jax.Array,     # (B,) int32: per-slot index of the new token
    table: jax.Array,         # (B, W) int32
    active: jax.Array,        # (B,) bool
    cfg: ModelConfig,
    block_len: int,
    kv_qdtype: Optional[str] = None,
) -> Tuple[jax.Array, List[Dict[str, Any]]]:
    """Batched single-token decode against block tables.

    Each slot advances its OWN position (ragged lengths); inactive slots
    (``active=False``) write to the scratch block, leave SSM state
    untouched, and their logits are garbage the scheduler discards.
    Jitted at module level with the (hashable) config static, so every
    Engine instance over the same config shares one trace.
    """
    x = embed(params["embed"], tokens)
    pos2 = positions[:, None]
    wmask = active[:, None]

    def mixer(slot, lp, lc, h):
        if slot.mixer in ("attn", "attn_local"):
            return _paged_attention(
                lp["mixer"], h, lc, pos2, table, wmask, cfg,
                is_global=slot.mixer == "attn",
                block_len=block_len, kv_qdtype=kv_qdtype)
        return _masked_decode_mamba(lp["mixer"]["mamba"], h, lc, active, cfg)

    logits, caches = cached_stack(params, caches, x, cfg, mixer)
    return logits, _place_pools(axis_env(), caches)


@partial(jax.jit, static_argnames=("cfg", "block_len", "kv_qdtype"))
def paged_prefill_chunk(
    params: Params,
    caches: List[Dict[str, Any]],
    tokens: jax.Array,        # (1, C) int32
    pos0: jax.Array,          # scalar int32: position of tokens[0, 0]
    table: jax.Array,         # (1, W) int32
    n_valid: jax.Array,       # scalar int32: tokens beyond this are padding
    slot_idx: jax.Array,      # scalar int32: engine slot being prefilled
    cfg: ModelConfig,
    block_len: int,
    kv_qdtype: Optional[str] = None,
) -> Tuple[jax.Array, List[Dict[str, Any]]]:
    """One prefill chunk for one request: C prompt tokens enter the pools
    in a single forward (in-chunk causality via the position mask), so a
    long prompt costs ceil(P/C) model calls instead of P lockstep steps.
    Returns logits for every chunk position; the scheduler samples from
    the last valid one when the prompt completes.

    ``table`` already selects the request's physical blocks, so the
    attention side needs no slot index; ``slot_idx`` exists for the SSM
    side, whose caches are slot-addressed (batch=slots) and must update
    exactly the admitted row.
    """
    c = tokens.shape[1]
    x = embed(params["embed"], tokens)
    positions = pos0 + jnp.arange(c, dtype=jnp.int32)[None, :]
    wmask = (jnp.arange(c) < n_valid)[None, :]

    def mixer(slot, lp, lc, h):
        if slot.mixer in ("attn", "attn_local"):
            return _paged_attention(
                lp["mixer"], h, lc, positions, table, wmask, cfg,
                is_global=slot.mixer == "attn",
                block_len=block_len, kv_qdtype=kv_qdtype)
        return _prefill_mamba_slot(lp["mixer"]["mamba"], h, lc, n_valid,
                                   slot_idx, cfg)

    logits, caches = cached_stack(params, caches, x, cfg, mixer)
    return logits, _place_pools(axis_env(), caches)


def reset_slot_state(caches, slot_index: int):
    """Zero one batch row of every per-request (SSM) cache leaf.

    Attention pools are block-addressed and need no reset (freed blocks
    are only read again after being rewritten); Mamba conv/state is slot-
    addressed, so admission of a new request into a recycled slot must
    clear it.  Pool leaves (block-indexed leading dim) are left alone —
    they are distinguished structurally by key.
    """
    def _reset(path, a):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("conv", "state"):
            # leaves are (stage_count, repeat, batch, ...): batch is dim 2
            return a.at[:, :, slot_index].set(0)
        return a
    return jax.tree_util.tree_map_with_path(_reset, caches)
