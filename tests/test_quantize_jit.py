"""``quantize_per_channel`` is jitted (so its float32 copies fuse away at
real widths): the jitted values and scales are those the same function
gives op by op, bit for bit, for every narrow dtype."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize as q


@pytest.mark.parametrize("shape", [(512, 384), (3, 512, 384)])
@pytest.mark.parametrize("dtype", ["int8", "fp8"])
def test_jitted_quantization_equals_eager(shape, dtype):
    w = jax.random.normal(jax.random.PRNGKey(len(shape)), shape)
    w = (w * jnp.linspace(1e-3, 4.0, shape[-1])).astype(jnp.bfloat16)
    w = w.at[..., 7].set(0)                   # an all-zero channel
    jitted = q.quantize_per_channel(w, dtype)
    with jax.disable_jit():
        eager = q.quantize_per_channel(w, dtype)
    for got, want in zip(jitted, eager):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)))
