"""Chunked online-softmax (flash) attention Pallas kernel.

The perf-critical hot-spot for prefill/long-context shapes.  Causal,
single-head body; batch*heads mapped onto grid dim 0 (GQA handled by the
wrapper repeating KV head indices in the BlockSpec index map).

State (running max m, running sum l, fp32 accumulator) lives in VMEM
scratch across the KV grid — the attention-side analogue of the VEGETA
accumulator-residency ("output forwarding") pattern.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.registry import kernel_label


NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, block_q: int, block_k: int, nkv: int, causal: bool,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body():
        q = q_ref[0]                       # (BQ, D)
        k = k_ref[0]                       # (BK, D)
        v = v_ref[0]                       # (BK, D)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                           # (BQ, BK)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]                # (BQ, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)             # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    if causal:
        # skip fully-masked KV blocks above the diagonal
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ki == nkv - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """q, k, v: (BH, T, D) -> (BH, T, D).  GQA repeat handled by caller."""
    bh, tq, d = q.shape
    bh2, tk, d2 = k.shape
    assert bh == bh2 and d == d2 and v.shape == k.shape
    if scale is None:
        scale = d ** -0.5
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    assert tq % block_q == 0 and tk % block_k == 0
    nkv = tk // block_k
    kern = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_k=block_k,
        nkv=nkv, causal=causal,
    )
    return pl.pallas_call(
        kern,
        grid=(bh, tq // block_q, nkv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        **kernel_label("flash_attention", "flash_attention"),
    )(q, k, v)
