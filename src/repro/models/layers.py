"""Shared layers: norms, RoPE, embeddings, MLP (N:M-sparsifiable)."""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core.sparse_linear import (
    SparsityConfig, apply_gate_up, apply_linear, init_linear)

from .pjit_utils import constrain

Params = Dict[str, Any]


# ---------------------------------------------------------------- norms
_RMS_EPS = 1e-6     # ModelConfig.rms_norm_eps's default


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x: jax.Array, gamma: jax.Array,
             eps: float = _RMS_EPS) -> jax.Array:
    """RMSNorm with a hand-written VJP; ``eps`` is the model's own
    (``ModelConfig.rms_norm_eps``).

    Autodiff through the fp32-upcast norm generates ~15 fp32 (B,T,d)
    intermediates per call (measured as a top byte dominator at 88 layers
    -- EXPERIMENTS §Perf); the closed-form backward needs 3.
    """
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps))
            * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def _rms_fwd(x, gamma, eps=_RMS_EPS):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)                      # (..., 1) tiny
    y = ((xf * r) * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)
    return y, (x, gamma, r)


def _rms_bwd(eps, res, dy):
    x, gamma, r = res
    xf = x.astype(jnp.float32)
    g = dy.astype(jnp.float32) * (1.0 + gamma.astype(jnp.float32))
    dot = jnp.mean(g * xf, axis=-1, keepdims=True)    # (..., 1)
    dx = (r * g - xf * (r**3) * dot).astype(x.dtype)
    dgamma = jnp.sum(
        dy.astype(jnp.float32) * xf * r,
        axis=tuple(range(dy.ndim - 1)),
    ).astype(gamma.dtype)
    return dx, dgamma


rms_norm.defvjp(_rms_fwd, _rms_bwd)


def init_rms_norm(d: int) -> Params:
    return {"gamma": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------- RoPE
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., T, H, D); positions: broadcastable to (..., T)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, D/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., T, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- MLP
def init_mlp(key, d: int, ff: int, act: str, sp: SparsityConfig, dtype) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_in": init_linear(ks[0], d, ff, sp, dtype)}
    if act == "swiglu":
        p["w_gate"] = init_linear(ks[1], d, ff, sp, dtype)
    p["w_out"] = init_linear(ks[2], ff, d, sp, dtype, scale=ff**-0.5)
    return p


def apply_mlp(p: Params, x: jax.Array, act: str, sp: SparsityConfig) -> jax.Array:
    from repro.kernels import dispatch, epilogue as epilib

    # Will w_out consume quantized rows against a static calibrated
    # scale?  Then the producing kernel requantizes in its flush and
    # w_out contracts the narrow rows directly (one function decides
    # for both sides, so they can never disagree).
    rq = dispatch.requant_plan(
        p["w_out"], x.shape[:-1], sp,
        shard=dispatch.shard_spec_from_env("row"))
    requant, rq_scale = rq if rq is not None else (None, None)
    if act == "swiglu":
        # gate and up contract the SAME activation tile — one gate-up
        # dispatch reads it once (fused dual kernel when the plan
        # allows, one concatenated GEMM otherwise)
        h = apply_gate_up(p["w_gate"], p["w_in"], x, sp, gather="col",
                          epilogue=epilib.make(act="silu_mul",
                                               requant=requant,
                                               requant_scale=rq_scale))
    else:
        h = apply_linear(
            p["w_in"], x, sp, gather="col",
            epilogue=epilib.make(act="gelu", requant=requant,
                                 requant_scale=rq_scale))
    h = constrain(h, "batch", None, "model")
    # when h arrives pre-quantized, w_out dequantizes to fp32 (the
    # scale dtype) — restore the residual stream's activation dtype
    return apply_linear(p["w_out"], h, sp, gather="row").astype(x.dtype)


# ---------------------------------------------------------------- embed
def init_embedding(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * d**-0.5).astype(dtype)


def embed(table: jax.Array, ids: jax.Array) -> jax.Array:
    return jnp.take(table, ids, axis=0)
