"""Plain float32 reference of the InternLM2 decoder, and its seeded weights.

The architecture, as published (arXiv:2403.17297 and the model's
``config.json``): token embedding; ``num_hidden_layers`` pre-norm blocks
of RMSNorm -> grouped-query attention with rotary positions (rotate-half
form) -> residual, RMSNorm -> SwiGLU MLP ``down(silu(gate x) * up x)`` ->
residual; a final RMSNorm and an untied output head.  No biases.

Everything here is straightforward ``jax.numpy`` in float32 with
``Precision.HIGHEST`` on every contraction (a TPU float32 matmul is one
bfloat16 pass otherwise).  It imports nothing of the program under test.

The weights are made here, from the seed, and the benchmark hands the
very same bfloat16 values to the program: both sides call
:func:`layer_weights` and :func:`embedding_weights`.  A 2:4 configuration
keeps the two largest magnitudes of every 4 consecutive input rows of
each output column (ties to the lower index), and zeroes the rest.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    """Sizes the forward needs, from the configuration file's keys."""
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim", d // heads)
    return dict(d=d, heads=heads, kv_heads=cfg["num_key_value_heads"],
                head_dim=head_dim, ff=cfg["intermediate_size"],
                vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]))


def linear_shapes(cfg: dict) -> Dict[str, Tuple[int, int]]:
    """(in, out) of every linear of one layer."""
    z = dims(cfg)
    d, q, kv, ff = (z["d"], z["heads"] * z["head_dim"],
                    z["kv_heads"] * z["head_dim"], z["ff"])
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of up to 64 bits (``PRNGKey`` keeps only 32)."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is not a 64-bit unsigned number")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def prune(w: jax.Array, n: int, m: int) -> jax.Array:
    """Keep the ``n`` largest magnitudes of each ``m`` consecutive input
    rows, per output column; ties go to the lower row."""
    k, o = w.shape
    blocks = w.reshape(k // m, m, o)
    order = jnp.argsort(-jnp.abs(blocks.astype(jnp.float32)), axis=1,
                        stable=True)
    rank = jnp.argsort(order, axis=1, stable=True)
    return jnp.where(rank < n, blocks, 0).reshape(k, o).astype(w.dtype)


def layer_weights(key: jax.Array, layer, cfg: dict,
                  sparsity: Optional[Sequence[int]]) -> Dict[str, jax.Array]:
    """bfloat16 linears of one layer, ``N(0, 1/in)``, pruned to N:M."""
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    out = {}
    for i, (name, (k, o)) in enumerate(linear_shapes(cfg).items()):
        w = jax.random.normal(jax.random.fold_in(lk, i), (k, o), jnp.float32)
        w = (w * k ** -0.5).astype(jnp.bfloat16)
        if sparsity is not None and sparsity[0] < sparsity[1]:
            w = prune(w, sparsity[0], sparsity[1])
        out[name] = w
    return out


def embedding_weights(key: jax.Array,
                      cfg: dict) -> Tuple[jax.Array, jax.Array]:
    """bfloat16 token embedding ``(vocab, d)``, ``N(0, 1)``, and output head
    ``(d, vocab)``, ``N(0, 1/d)``.  An embedding row's mean square is then
    about 1, far above any norm's epsilon."""
    z = dims(cfg)
    ek = jax.random.fold_in(key, 2)
    emb = jax.random.normal(jax.random.fold_in(ek, 0), (z["vocab"], z["d"]),
                            jnp.float32)
    head = jax.random.normal(jax.random.fold_in(ek, 1), (z["d"], z["vocab"]),
                             jnp.float32) * z["d"] ** -0.5
    return emb.astype(jnp.bfloat16), head.astype(jnp.bfloat16)


# ---------------------------------------------------------------- forward
def rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, positions, theta):
    """Rotate-half rotary embedding. x: (S, T, H, D); positions: (T,)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # (T, D/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _mm(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision=HIGHEST)


@partial(jax.jit, static_argnames=("cfg_items", "q_block"))
def layer_forward(x, w, cfg_items, q_block):
    """One block over ``x`` (S, T, d) float32, causal from position 0."""
    cfg = dict(cfg_items)
    z = dims(cfg)
    s, t, _ = x.shape
    hq, hkv, dh = z["heads"], z["kv_heads"], z["head_dim"]
    pos = jnp.arange(t)
    h = rms_norm(x, z["eps"])
    q = rope(_mm(h, w["wq"]).reshape(s, t, hq, dh), pos, z["theta"])
    k = rope(_mm(h, w["wk"]).reshape(s, t, hkv, dh), pos, z["theta"])
    v = _mm(h, w["wv"]).reshape(s, t, hkv, dh)
    rep = hq // hkv
    k = jnp.repeat(k, rep, axis=2)        # query head i reads kv head i // rep
    v = jnp.repeat(v, rep, axis=2)

    def attend(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, axis=1)
        sc = jnp.einsum("sqhd,skhd->shqk", qi, k,
                        precision=HIGHEST) * dh ** -0.5
        qpos = i * q_block + jnp.arange(q_block)
        sc = jnp.where(qpos[:, None] >= pos[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("shqk,skhd->sqhd", p, v, precision=HIGHEST)

    o = jax.lax.map(attend, jnp.arange(t // q_block))      # (nb, S, qb, H, D)
    o = o.transpose(1, 0, 2, 3, 4).reshape(s, t, hq * dh)
    x = x + _mm(o, w["wo"])
    h = rms_norm(x, z["eps"])
    x = x + _mm(jax.nn.silu(_mm(h, w["w_gate"])) * _mm(h, w["w_up"]),
                w["w_down"])
    return x


@partial(jax.jit, static_argnames=("cfg_items",))
def head_logits(x, rows, head, cfg_items):
    """Final norm and output head at ``rows`` (S, P) of ``x`` (S, T, d)."""
    z = dims(dict(cfg_items))
    xr = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return _mm(rms_norm(xr, z["eps"]), head)


@partial(jax.jit, static_argnames=("cfg", "sparsity"))
def _layer_weights_jit(key, layer, cfg, sparsity):
    return layer_weights(key, layer, dict(cfg), sparsity)


@partial(jax.jit, static_argnames=("cfg",))
def _embedding_weights_jit(key, cfg):
    return embedding_weights(key, dict(cfg))


def logits_at(seed: int, cfg: dict, sparsity: Optional[Sequence[int]],
              tokens: np.ndarray, rows: np.ndarray, q_block: int = 512
              ) -> np.ndarray:
    """float32 logits ``(S, P, vocab)`` of the sequences ``tokens``
    (S, T), read at positions ``rows`` (S, P).  The padded length T is
    cut into query blocks of at most ``q_block``; weights are made and
    dropped one layer at a time, so the reference holds one layer's
    float32 weights beside the activations."""
    z = dims(cfg)
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    sp = None if sparsity is None else tuple(sparsity)
    key = seed_key(seed)
    t = tokens.shape[1]
    qb = min(q_block, t)
    if t % qb:
        raise ValueError(f"padded length {t} is not a multiple of {qb}")
    emb, head = _embedding_weights_jit(key, items)
    x = jnp.take(emb, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    del emb
    for layer in range(z["layers"]):
        w = _layer_weights_jit(key, layer, items, sp)
        x = layer_forward(x, w, items, qb)
    out = head_logits(x, jnp.asarray(rows), head, items)
    return np.asarray(out)


def gaps(logits: np.ndarray, served: np.ndarray,
         valid: np.ndarray) -> np.ndarray:
    """At each served position, how far the served token's logit lies
    below the best logit there (0 where the served token is the
    reference's own choice, and where ``valid`` is False)."""
    best = logits.max(axis=-1)
    got = np.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
    return np.where(valid, best - got, 0.0)
