"""Unified model stack for all assigned families.

Depth is expressed as **stages** of scanned **super-blocks** so HLO size is
O(1) in layer count (critical for 88–94-layer configs at 512 devices):

  dense/moe/encoder : 1 stage, super-block = [attn + (mlp|moe)]
  ssm (mamba2)      : 1 stage, super-block = [mamba]
  hybrid (jamba)    : 1 stage of 9 super-blocks, each
                      [3x(mamba+mlp), 4x(mamba+moe), 1x(attn+mlp)]
                      (1:7 attn ratio, MoE on half the layers — coarser
                      interleaving than HF Jamba, recorded in DESIGN.md)
  local:global (gemma3): stages of [5x local-attn + 1x global-attn] periods
                      + a remainder stage, so local layers carry
                      window-sized caches (honest long_500k costs).

Each slot in a super-block may repeat; repeated slots are inner-scanned.
Caches (KV / conv+SSM state) mirror the stage/slot structure with the same
stacked leading dims, so decode threads them through the same scans.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import (
    attention_block,
    decode_attention_block,
    init_attention,
    init_kv_cache,
)
from .config import ModelConfig
from .layers import apply_mlp, embed, init_embedding, init_mlp, init_rms_norm, rms_norm
from .moe import apply_moe, init_moe
from .pjit_utils import constrain
from .ssm import decode_mamba_block, init_mamba, init_ssm_cache, mamba_block

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Slot:
    mixer: str            # attn | attn_local | mamba
    ffn: str              # mlp | moe | none
    repeat: int = 1


@dataclasses.dataclass(frozen=True)
class Stage:
    count: int
    slots: Tuple[Slot, ...]


def build_layout(cfg: ModelConfig) -> Tuple[Stage, ...]:
    if cfg.family == "ssm":
        return (Stage(cfg.num_layers, (Slot("mamba", "none"),)),)
    if cfg.family == "hybrid":
        period = cfg.hybrid_period
        assert cfg.num_layers % period == 0
        nb = cfg.num_layers // period
        n_moe = period // max(cfg.moe_every, 1)
        n_mlp = (period - 1) - n_moe
        return (
            Stage(
                nb,
                (
                    Slot("mamba", "mlp", n_mlp),
                    Slot("mamba", "moe", n_moe),
                    Slot("attn", "mlp", 1),
                ),
            ),
        )
    if cfg.local_global_period > 0 and cfg.window > 0:
        p = cfg.local_global_period
        full, rem = divmod(cfg.num_layers, p)
        stages: List[Stage] = [
            Stage(full, (Slot("attn_local", "mlp", p - 1), Slot("attn", "mlp", 1)))
        ]
        if rem:
            stages.append(Stage(1, (Slot("attn_local", "mlp", rem),)))
        return tuple(stages)
    ffn = "moe" if cfg.num_experts > 0 else "mlp"
    return (Stage(cfg.num_layers, (Slot("attn", ffn),)),)


def layout_num_layers(cfg: ModelConfig) -> int:
    return sum(
        st.count * sum(sl.repeat for sl in st.slots) for st in build_layout(cfg)
    )


# ------------------------------------------------------------------ init
def _init_slot(key, slot: Slot, cfg: ModelConfig) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {"norm1": init_rms_norm(cfg.d_model)}
    if slot.mixer in ("attn", "attn_local"):
        p["mixer"] = init_attention(k1, cfg)
    else:
        p["mixer"] = {"mamba": init_mamba(k1, cfg)}
    if slot.ffn != "none":
        p["norm2"] = init_rms_norm(cfg.d_model)
        if slot.ffn == "moe":
            p["ffn"] = init_moe(k3, cfg)
        else:
            p["ffn"] = init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.act, cfg.sparsity, cfg.jnp_dtype)
    return p


def _stack_init(key, n_outer: int, n_inner: int, init_fn):
    keys = jax.random.split(key, n_outer * n_inner).reshape(n_outer, n_inner, 2)
    return jax.vmap(jax.vmap(init_fn))(keys)


def init_params(key, cfg: ModelConfig) -> Params:
    layout = build_layout(cfg)
    keys = jax.random.split(key, len(layout) + 3)
    params: Params = {}
    if cfg.frontend != "audio_frames":
        params["embed"] = init_embedding(keys[0], cfg.vocab_size, cfg.d_model, cfg.jnp_dtype)
    else:
        params["frame_proj"] = init_embedding(keys[0], cfg.d_model, cfg.d_model, cfg.jnp_dtype)
    if not cfg.tie_embeddings:
        params["unembed"] = init_embedding(keys[1], cfg.vocab_size, cfg.d_model, cfg.jnp_dtype).T
    params["final_norm"] = init_rms_norm(cfg.d_model)
    stages = []
    for si, (st, k) in enumerate(zip(layout, keys[3:])):
        slot_keys = jax.random.split(k, len(st.slots))
        stage_params = {}
        for j, (slot, sk) in enumerate(zip(st.slots, slot_keys)):
            stage_params[f"slot{j}"] = _stack_init(
                sk, st.count, slot.repeat, lambda kk, slot=slot: _init_slot(kk, slot, cfg)
            )
        stages.append(stage_params)
    params["stages"] = stages
    return params


# ------------------------------------------------------------------ apply
def _apply_slot(p: Params, x: jax.Array, slot: Slot, cfg: ModelConfig) -> jax.Array:
    h = rms_norm(x, p["norm1"]["gamma"], cfg.rms_norm_eps)
    if slot.mixer == "attn":
        x = x + attention_block(p["mixer"], h, cfg, is_global=True)
    elif slot.mixer == "attn_local":
        x = x + attention_block(p["mixer"], h, cfg, is_global=False)
    else:
        x = x + mamba_block(p["mixer"]["mamba"], h, cfg)
    if slot.ffn != "none":
        h = rms_norm(x, p["norm2"]["gamma"], cfg.rms_norm_eps)
        if slot.ffn == "moe":
            x = x + apply_moe(p["ffn"], h, cfg)
        else:
            x = x + apply_mlp(p["ffn"], h, cfg.act, cfg.sparsity)
    x = constrain(x, "batch", None, None)
    return x


def _remat(fn, cfg: ModelConfig):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
    elif cfg.remat_policy == "dots_nobatch":
        # saves projection outputs but NOT attention-score matrices
        # (batch-dim dots) -- the Megatron-style selective remat default
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


def apply_stack(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    layout = build_layout(cfg)
    for st, stage_params in zip(layout, params["stages"]):
        def super_block(x, sb_params, st=st):
            for j, slot in enumerate(st.slots):
                sp = sb_params[f"slot{j}"]
                if slot.repeat == 1:
                    x = _apply_slot(jax.tree.map(lambda a: a[0], sp), x, slot, cfg)
                else:
                    def layer(x, lp, slot=slot):
                        return _apply_slot(lp, x, slot, cfg), None
                    x, _ = jax.lax.scan(layer, x, sp)
            return x, None

        body = _remat(super_block, cfg)
        x, _ = jax.lax.scan(body, x, stage_params)
    return x


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
) -> jax.Array:
    """Train/prefill forward -> logits (B, T, V)."""
    if cfg.frontend == "audio_frames":
        x = embeds @ params["frame_proj"].astype(embeds.dtype)
    elif cfg.frontend == "vision_patches":
        tok_x = embed(params["embed"], tokens)
        x = jnp.concatenate([embeds.astype(tok_x.dtype), tok_x], axis=1)
    else:
        x = embed(params["embed"], tokens)
    x = constrain(x, "batch", None, None)
    x = apply_stack(params, x, cfg)
    x = rms_norm(x, params["final_norm"]["gamma"], cfg.rms_norm_eps)
    unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = x @ unembed.astype(x.dtype)
    return constrain(logits, "batch", None, "model")


# ------------------------------------------------------------------ decode
def init_caches(cfg: ModelConfig, batch: int, max_len: int) -> List[Dict[str, Any]]:
    """Cache pytree mirroring the stage/slot structure (stacked dims)."""
    layout = build_layout(cfg)
    caches = []
    for st in layout:
        stage_c = {}
        for j, slot in enumerate(st.slots):
            if slot.mixer in ("attn", "attn_local"):
                one = init_kv_cache(cfg, batch, max_len, local=slot.mixer == "attn_local")
            else:
                one = init_ssm_cache(cfg, batch)
            stage_c[f"slot{j}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a, (st.count, slot.repeat) + a.shape
                ),
                one,
            )
        caches.append(stage_c)
    return caches


def cached_stack(
    params: Params,
    caches: List[Dict[str, Any]],
    x: jax.Array,            # (B, T, d) embedded input
    cfg: ModelConfig,
    mixer_fn,                # (slot, lp, lc, h) -> (mixer_out, new_cache)
) -> Tuple[jax.Array, List[Dict[str, Any]]]:
    """Shared cache-threading stack walker for every decode-side path.

    Walks the stage/slot layout exactly like :func:`decode_step` always
    did (scan over super-blocks, inner scan over repeated slots), but the
    mixer application is pluggable: the contiguous decode passes the
    ring-buffer/linear-cache mixers, the paged serving path
    (``repro.models.paged``) passes block-table mixers.  Norms, residuals,
    the FFN/MoE half of every slot, and the final unembedding stay in one
    place so the two cache disciplines cannot drift.
    """
    layout = build_layout(cfg)
    new_caches = []
    for st, stage_params, stage_cache in zip(layout, params["stages"], caches):
        def super_block(x, inp, st=st):
            sb_params, sb_cache = inp
            new_c = {}
            for j, slot in enumerate(st.slots):
                sp, sc = sb_params[f"slot{j}"], sb_cache[f"slot{j}"]

                def one(x, lp, lc, slot=slot):
                    h = rms_norm(x, lp["norm1"]["gamma"], cfg.rms_norm_eps)
                    o, c = mixer_fn(slot, lp, lc, h)
                    x = x + o
                    if slot.ffn != "none":
                        h = rms_norm(x, lp["norm2"]["gamma"], cfg.rms_norm_eps)
                        if slot.ffn == "moe":
                            x = x + apply_moe(lp["ffn"], h, cfg)
                        else:
                            x = x + apply_mlp(lp["ffn"], h, cfg.act, cfg.sparsity)
                    return x, c

                if slot.repeat == 1:
                    x, c = one(
                        x,
                        jax.tree.map(lambda a: a[0], sp),
                        jax.tree.map(lambda a: a[0], sc),
                    )
                    new_c[f"slot{j}"] = jax.tree.map(lambda a: a[None], c)
                else:
                    def layer(x, inp, slot=slot):
                        lp, lc = inp
                        return one(x, lp, lc, slot=slot)
                    x, cs = jax.lax.scan(layer, x, (sp, sc))
                    new_c[f"slot{j}"] = cs
            return x, new_c

        x, ncs = jax.lax.scan(super_block, x, (stage_params, stage_cache))
        new_caches.append(ncs)
    x = rms_norm(x, params["final_norm"]["gamma"], cfg.rms_norm_eps)
    unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = x @ unembed.astype(x.dtype)
    return logits, new_caches


def decode_step(
    params: Params,
    caches: List[Dict[str, Any]],
    tokens: jax.Array,       # (B, 1) int32
    pos: jax.Array,          # scalar int32
    cfg: ModelConfig,
) -> Tuple[jax.Array, List[Dict[str, Any]]]:
    x = embed(params["embed"], tokens)

    def mixer(slot, lp, lc, h):
        if slot.mixer in ("attn", "attn_local"):
            return decode_attention_block(
                lp["mixer"], h, lc, pos, cfg, is_global=slot.mixer == "attn")
        return decode_mamba_block(lp["mixer"]["mamba"], h, lc, cfg)

    return cached_stack(params, caches, x, cfg, mixer)
