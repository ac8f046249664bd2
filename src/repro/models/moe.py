"""Mixture-of-Experts with expert parallelism on the model axis.

Default path (distributed): ``shard_map`` over the model axis — experts
are sharded E/|model| per rank, every rank routes the full local token
set, gathers a *capacity* of tokens per local expert, runs the expert FFN
(dense, N:M-sparsifiable), scatter-adds weighted outputs, and a single
``psum`` over the model axis combines contributions — the same collective
footprint as the Megatron-TP all-reduce it replaces.

Single-device path (no AxisEnv): identical routing math, loop over all
experts via ``lax.scan`` on stacked weights.

Capacity semantics: per-(data-shard, expert) top-C selection (Switch-style
local dispatch) — tokens over capacity are dropped, standard for
capacity-factor MoE.

Expert execution (``cfg.moe_expert_path``): the default ``"gather"`` path
scatters a capacity of tokens per expert into a dense tile; ``"spgemm"``
instead zeroes the unrouted rows of the FULL token set and runs the
expert FFN as a sparse x sparse contraction — the routing holes become
dynamic activation sparsity (``ActivationSpec("zeros")``) against the
expert's N:M weights, so the masked kernels skip whole dead row-blocks.
Because the FFN is row-independent the two paths are bit-identical on
fp32; spgemm additionally passes ``local=True`` so the expert linears
may plan kernels even inside the MoE's own shard_map body (the nesting
problem the gather path sidesteps by falling back to jnp).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.sparse_linear import (
    SparsityConfig, apply_gate_up, apply_linear, init_linear)
from repro.kernels.actsparse import ActivationSpec

from .config import ModelConfig
from .pjit_utils import axis_env

Params = Dict[str, Any]


def init_moe(key, cfg: ModelConfig) -> Params:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    sp, dt = cfg.sparsity, cfg.jnp_dtype

    def stack(k, kin, kout, scale):
        keys = jax.random.split(k, e)
        return jax.vmap(
            lambda kk: init_linear(kk, kin, kout, sp, dt, scale=scale)
        )(keys)

    p = {
        "router": (jax.random.normal(ks[0], (d, e), jnp.float32) * d**-0.5),
        "w_in": stack(ks[1], d, ff, d**-0.5),
        "w_out": stack(ks[3], ff, d, ff**-0.5),
    }
    if cfg.act == "swiglu":
        p["w_gate"] = stack(ks[2], d, ff, d**-0.5)
    return p


def _expert_ffn(wp: Params, x: jax.Array, cfg: ModelConfig,
                activation: ActivationSpec = None,
                local: bool = False) -> jax.Array:
    from repro.kernels import dispatch, epilogue as epilib

    rq = dispatch.requant_plan(wp["w_out"], x.shape[:-1], cfg.sparsity)
    requant, rq_scale = rq if rq is not None else (None, None)
    if cfg.act == "swiglu":
        # one gate-up dispatch per expert: the expert's token tile is
        # read once (hint-less site — inside shard_map/scan bodies)
        h = apply_gate_up(wp["w_gate"], wp["w_in"], x, cfg.sparsity,
                          epilogue=epilib.make(act="silu_mul",
                                               requant=requant,
                                               requant_scale=rq_scale),
                          activation=activation, local=local)
    else:
        h = apply_linear(
            wp["w_in"], x, cfg.sparsity,
            epilogue=epilib.make(act="gelu", requant=requant,
                                 requant_scale=rq_scale),
            activation=activation, local=local)
    # pre-quantized h dequantizes to fp32 in w_out — keep the expert
    # output in the token dtype the combine expects.  The FFN is
    # row-wise, so zeroed (unrouted) input rows stay zero in h and the
    # "zeros" activation class carries through to w_out.
    return apply_linear(wp["w_out"], h, cfg.sparsity,
                        activation=activation, local=local).astype(x.dtype)


def _route(router: jax.Array, xf: jax.Array, cfg: ModelConfig):
    """xf: (Tloc, d) -> combine weights (Tloc, E) (zero for unrouted)."""
    logits = (xf.astype(jnp.float32)) @ router          # (T, E)
    gates, ids = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(gates, axis=-1)
    full = jnp.zeros_like(logits)
    full = jnp.put_along_axis(full, ids, gates, axis=-1, inplace=False)
    return full                                          # (T, E)


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    """Per-expert gather capacity for one routed token set.

    Note the length-1 decode semantics: a step routes only B tokens, so
    ``min(tokens, ...)`` caps at B and — since an expert can receive at
    most ``tokens`` tokens — step decode NEVER drops, while a parallel
    forward with a small capacity factor may.  Decode-vs-forward parity
    therefore needs a drop-free capacity factor on the forward side
    (tests use moe_capacity_factor=16); routing itself is step-invariant:
    ``lax.top_k`` tie-breaks deterministically by lowest index in both
    paths, and router logits are fp32.
    """
    c = int(math.ceil(tokens * cfg.top_k / cfg.num_experts * cfg.moe_capacity_factor))
    return min(tokens, max(8, c))


def _spgemm_expert_body(xf: jax.Array, cap: int, cfg: ModelConfig,
                        local: bool):
    """Expert body for the sparse x sparse path (``moe_expert_path``).

    No gather of the inputs: the capacity winners keep their combine
    weight, every other row of the full token set is zeroed, and the
    expert FFN runs as SpGEMM — the masked kernels skip the dead
    row-blocks via the ``"zeros"`` activation class.  The capacity drop
    (top-C per expert) and the weighted scatter-add combine are
    replicated verbatim from the gather path (same scatter, same
    multiply — an elementwise ``acc + y*w`` form would let XLA contract
    it to an FMA inside the scan body and drift one ulp), and the FFN
    is row-independent, so outputs are bit-identical on fp32.
    """

    def expert_body(acc, inp):
        wp, w_e = inp                                    # w_e: (T,) combine wts
        score = jnp.where(w_e > 0, w_e, -jnp.inf)
        top_w, top_idx = jax.lax.top_k(score, cap)       # capacity winners
        keep = top_w > 0
        w_tok = jnp.zeros((xf.shape[0],), jnp.float32).at[top_idx].set(
            jnp.where(keep, top_w, 0.0))
        routed = (w_tok > 0)[:, None]                    # (T, 1)
        x_full = xf * routed.astype(xf.dtype)
        y = _expert_ffn(wp, x_full, cfg,
                        activation=ActivationSpec("zeros"), local=local)
        y_e = jnp.take(y, top_idx, axis=0)               # (cap, d)
        y_e = y_e * (jnp.where(keep, top_w, 0.0)[:, None]).astype(y.dtype)
        acc = acc.at[top_idx].add(y_e)
        return acc, None

    return expert_body


def _moe_local(p: Params, x: jax.Array, cfg: ModelConfig, n_local: int) -> jax.Array:
    """Experts stacked (n_local, ...). x: (B, T, d) -> (B, T, d)."""
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    weights = _route(p["router"], xf, cfg)               # (T, E) [global E]
    cap = _capacity(b * t, cfg)

    def expert_body(carry, inp):
        wp, w_e = inp                                    # w_e: (T,) combine wts
        acc = carry
        score = jnp.where(w_e > 0, w_e, -jnp.inf)
        top_w, top_idx = jax.lax.top_k(score, cap)       # (cap,)
        keep = top_w > 0
        x_e = jnp.take(xf, top_idx, axis=0)              # (cap, d)
        y_e = _expert_ffn(wp, x_e, cfg)
        y_e = y_e * (jnp.where(keep, top_w, 0.0)[:, None]).astype(y_e.dtype)
        acc = acc.at[top_idx].add(y_e)
        return acc, None

    if cfg.moe_expert_path == "spgemm":
        expert_body = _spgemm_expert_body(xf, cap, cfg, local=False)

    # weights columns for the local experts only (offset handled by caller
    # slicing p["router"]-aligned weight matrix — here full when local=E)
    w_cols = weights[:, :n_local].T                      # (n_local, T)
    experts = {k: v for k, v in p.items() if k != "router"}
    acc0 = jnp.zeros_like(xf)
    acc, _ = jax.lax.scan(expert_body, acc0, (experts, w_cols))
    return acc.reshape(b, t, d)


def _moe_shardmap(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    env = axis_env()
    mesh = env.mesh
    model = env.model_axis
    batch_phys = env.physical("batch")
    e_local = cfg.num_experts // mesh.shape[model]

    experts = {k: v for k, v in p.items() if k != "router"}

    def local_fn(router, experts_loc, x_loc, psum_axes):
        b, t, d = x_loc.shape
        xf = x_loc.reshape(b * t, d)
        weights = _route(router, xf, cfg)                # (T, E) full routing
        rank = jax.lax.axis_index(model)
        w_local = jax.lax.dynamic_slice_in_dim(
            weights, rank * e_local, e_local, axis=1
        )                                                # (T, e_local)
        cap = _capacity(b * t, cfg)

        def expert_body(acc, inp):
            wp, w_e = inp
            score = jnp.where(w_e > 0, w_e, -jnp.inf)
            top_w, top_idx = jax.lax.top_k(score, cap)
            keep = top_w > 0
            x_e = jnp.take(xf, top_idx, axis=0)
            y_e = _expert_ffn(wp, x_e, cfg)
            y_e = y_e * (jnp.where(keep, top_w, 0.0)[:, None]).astype(y_e.dtype)
            return acc.at[top_idx].add(y_e), None

        if cfg.moe_expert_path == "spgemm":
            # full-token SpGEMM dissolves the experts-inside-shard_map
            # nesting: local=True lets each expert linear plan a kernel
            # on its per-rank slice instead of declining to jnp
            expert_body = _spgemm_expert_body(xf, cap, cfg, local=True)

        acc0 = jnp.zeros_like(xf)
        acc, _ = jax.lax.scan(expert_body, acc0, (experts_loc, w_local.T))
        acc = jax.lax.psum(acc, psum_axes)
        return acc.reshape(b, t, d)

    # decode with tiny batches (e.g. long_500k, B=1): replicate the batch
    # over the data axes instead of sharding it
    bp = batch_phys if isinstance(batch_phys, tuple) else (batch_phys,)
    dp_total = 1
    for a in bp:
        dp_total *= mesh.shape[a]
    replicated = x.shape[0] % dp_total != 0
    x_spec = P() if replicated else P(batch_phys)

    def _batch_sliced_dim(key: str, leaf_key: str, v) -> int:
        """Size of the dim espec() slices over the batch axes, or 0 when
        the leaf keeps no batch-axis slicing (gather_idx, act_scale,
        w_out's per-out-channel scale)."""
        if leaf_key == "scale":
            return 0 if key == "w_out" else v.shape[-1]
        if leaf_key == "gather_idx" or v.ndim < 3:
            return 0
        return v.shape[-2] if key == "w_out" else v.shape[-1]

    def _ff_dim_divisible() -> bool:
        for k, sub in experts.items():
            for lk, v in sub.items():
                dim = _batch_sliced_dim(k, lk, v)
                if dim and dim % dp_total != 0:
                    return False
        return True

    ff_ok = replicated and _ff_dim_divisible()
    if ff_ok:
        # 2D expert sharding for replicated-token decode: keep the FSDP
        # (d_ff over the batch axes) shard LOCAL -- each rank computes an
        # ff-partial for its expert slice and one psum over (model + batch
        # axes) combines; no per-layer expert all-gather (EXPERIMENTS
        # §Perf hillclimb 2).
        def espec(key, leaf_key, v):
            if leaf_key == "scale":
                # per-out-channel quantization scale (E, O): slice O with
                # the operand's out dim (w_in/w_gate shard ff over the
                # batch axes; w_out's sliced dim is its contraction)
                return P(model, None) if key == "w_out" else P(model, batch_phys)
            if leaf_key == "gather_idx" or v.ndim < 3:
                # contraction-indexed metadata and scalar-ish aux leaves
                # (act_scale): expert dim only
                return P(model) if v.ndim else P()
            if key == "w_out":
                return P(model, batch_phys, None)
            return P(model, None, batch_phys)

        expert_specs = {
            k: {lk: espec(k, lk, lv) for lk, lv in sub.items()}
            for k, sub in experts.items()
        }
        psum_axes = (model,) + bp
    else:
        expert_specs = jax.tree.map(lambda _: P(model), experts)
        psum_axes = (model,)

    def wrapped(router, experts_loc, x_loc):
        return local_fn(router, experts_loc, x_loc, psum_axes)

    return shard_map(
        wrapped,
        mesh=mesh,
        in_specs=(P(), expert_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(p["router"], experts, x)


def apply_moe(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    env = axis_env()
    if env is None:
        return _moe_local(p, x, cfg, cfg.num_experts)
    return _moe_shardmap(p, x, cfg)
