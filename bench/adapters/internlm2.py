"""The program's side of the InternLM2 family: its ``ModelConfig`` and its
parameter tree, holding the very weights ``bench/reference/internlm2.py``
makes from the seed.

A family's adapter is found by the configuration's ``family`` key, as its
reference is; a later family (a GELU MLP, another parameter tree) brings
an adapter of its own instead of editing this one.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# the program's parameter names for the reference's linears
PROGRAM_NAMES = {"wq": ("mixer", "wq"), "wk": ("mixer", "wk"),
                 "wv": ("mixer", "wv"), "wo": ("mixer", "wo"),
                 "w_gate": ("ffn", "w_gate"), "w_up": ("ffn", "w_in"),
                 "w_down": ("ffn", "w_out")}
# configuration-file keys -> fields of repro's ModelConfig
CONFIG_FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
                 "num_attention_heads": "num_heads",
                 "num_key_value_heads": "num_kv_heads",
                 "intermediate_size": "d_ff", "vocab_size": "vocab_size",
                 "rope_theta": "rope_theta", "torch_dtype": "dtype"}


def model_config(cfg: dict):
    """repro's ModelConfig for the configuration file ``cfg``: the arch's
    preset with every size the file states."""
    from repro.configs import get_config

    base = get_config(cfg["program"]["arch"])
    fields = {f: cfg[k] for k, f in CONFIG_FIELDS.items() if k in cfg}
    fields["head_dim"] = cfg.get("head_dim", cfg["hidden_size"]
                                 // cfg["num_attention_heads"])
    fields["tie_embeddings"] = bool(cfg.get("tie_word_embeddings", False))
    mc = dataclasses.replace(base, **fields)
    if mc.family != "dense" or mc.act != "swiglu":
        raise ValueError(f"{cfg['name']}: the internlm2 adapter covers dense "
                         f"SwiGLU decoders, not {mc.family}/{mc.act}")
    return mc


def _leaf(w, layout: str, sparsity):
    """One layer's (in, out) linear in the program's layout, with the
    program's leading repeat axis of 1."""
    from repro.core import nm

    if layout == "dense":
        return {"w": w[None]}
    if layout == "compressed":
        n, m = sparsity
        c = nm.compress_nm(w, n, m)
        return {"values": c.values[None],
                "meta_packed": nm.pack_meta(c.meta)[None]}
    raise ValueError(f"no tree adapter for layout {layout!r}")


def build(ref, cfg: dict, shardings=None):
    """The jitted build of the program's tree from a key.

    The embedding and the head are made whole; the layers one at a time
    under ``lax.map``: each layer's dense bfloat16 weights are made by
    ``ref.layer_weights``, pruned and compressed, and written into the
    stacked leaves before the next layer's are made, so the build holds
    one layer's dense weights beside the tree.  ``shardings`` (a tree of
    shardings shaped as the result, or ``None``) places every leaf as it
    is built; XLA carries each leaf's placement into the loop that
    writes it.  (A constraint on each layer inside the loop made a
    v5e:2x2 compile hold more, not less.)"""
    serve = cfg["program"]
    sparsity = serve.get("sparsity")
    sp = None if sparsity is None else tuple(sparsity)
    layers, d = cfg["num_hidden_layers"], cfg["hidden_size"]

    def fn(key):
        emb, head = ref.embedding_weights(key, cfg)

        def one_layer(i):
            ws = ref.layer_weights(key, i, cfg, sp)
            out = {"mixer": {}, "ffn": {}}
            for name, (group, pname) in PROGRAM_NAMES.items():
                out[group][pname] = _leaf(ws[name], serve["layout"], sp)
            return out

        slot = jax.lax.map(one_layer, jnp.arange(layers))
        slot["norm1"] = {"gamma": jnp.zeros((layers, 1, d), jnp.float32)}
        slot["norm2"] = {"gamma": jnp.zeros((layers, 1, d), jnp.float32)}
        return {"embed": emb, "unembed": head,
                "final_norm": {"gamma": jnp.zeros((d,), jnp.float32)},
                "stages": [{"slot0": slot}]}

    return jax.jit(fn, out_shardings=shardings)


def program_params(ref, seed: int, cfg: dict, shardings=None):
    """The program's parameter tree, made on the device in one call
    (:func:`build`), placed by ``shardings`` where given."""
    return build(ref, cfg, shardings)(ref.seed_key(seed))
