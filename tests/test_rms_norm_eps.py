"""``ModelConfig.rms_norm_eps``: every RMSNorm reads it, and its default
(1e-6, the value the norm had fixed before the field) leaves internlm2's
logits bit-identical."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import forward, init_params
from repro.models.config import ModelConfig
from repro.models.layers import rms_norm


def _closed_form(x, gamma, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)) * (1.0 + gamma)


@pytest.mark.parametrize("eps", [1e-6, 1e-5, 0.5])
def test_rms_norm_adds_the_epsilon_it_is_given(eps):
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16)) * 1e-2
    gamma = jnp.linspace(-0.5, 0.5, 16)
    np.testing.assert_array_equal(np.asarray(rms_norm(x, gamma, eps)),
                                  np.asarray(_closed_form(x, gamma, eps)))
    # the hand-written VJP takes the same epsilon
    grad = jax.grad(lambda x_: rms_norm(x_, gamma, eps).sum())(x)
    want = jax.grad(lambda x_: _closed_form(x_, gamma, eps).sum())(x)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


def test_default_is_the_epsilon_the_norm_had():
    assert ModelConfig.__dataclass_fields__["rms_norm_eps"].default == 1e-6
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8))
    np.testing.assert_array_equal(np.asarray(rms_norm(x, jnp.zeros(8))),
                                  np.asarray(rms_norm(x, jnp.zeros(8), 1e-6)))


def test_internlm2_logits_bit_identical_under_the_default():
    cfg = get_smoke_config("internlm2_1_8b")
    assert cfg.rms_norm_eps == 1e-6
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                cfg.vocab_size)
    base = forward(params, cfg, tokens=tokens)
    same = forward(params, dataclasses.replace(cfg, rms_norm_eps=1e-6),
                   tokens=tokens)
    # the field reaches every norm: a different epsilon moves the logits
    other = forward(params, dataclasses.replace(cfg, rms_norm_eps=0.5),
                    tokens=tokens)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(same))
    assert not np.array_equal(np.asarray(base), np.asarray(other))


def test_mistral_preset_states_its_published_numbers():
    cfg = get_config("mistral_large_123b")
    assert (cfg.rms_norm_eps, cfg.rope_theta) == (1e-5, 1e6)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (
        88, 12288, 96, 8, 128, 28672, 32768)
    assert not cfg.tie_embeddings
