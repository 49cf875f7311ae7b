"""mrd_combine + rmsnorm kernels vs oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.collectives.compression import quantize
from repro.kernels.mrd_combine.ops import mrd_combine
from repro.kernels.mrd_combine.ref import mrd_combine_ref
from repro.kernels.rmsnorm.ops import rmsnorm as rmsnorm_kernel
from repro.kernels.rmsnorm.ref import rmsnorm_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,bn", [(1024, 512), (4096, 1024), (2048, 2048)])
def test_mrd_combine_matches_ref(dtype, n, bn):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (n,), dtype)
    g = jax.random.normal(ks[1], (n,), jnp.float32)
    q, s = quantize(g)
    out = mrd_combine(x, q, s, bn=bn, interpret=True)
    ref = mrd_combine_ref(x, q, s)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2
    )


def test_mrd_combine_equals_collective_receive_math():
    """kernel(x, quantize(g)) == x + dequant(quantize(g)) — the exact op the
    compressed reduce-scatter performs per stage."""
    from repro.collectives.compression import dequantize

    x = jnp.linspace(-2, 2, 512, dtype=jnp.float32)
    g = jnp.sin(jnp.arange(512, dtype=jnp.float32))
    q, s = quantize(g)
    out = mrd_combine(x, q, s, bn=512, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(x + dequantize(q, s)), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,d,bt", [(64, 128, 32), (100, 256, 64), (16, 512, 16)])
def test_rmsnorm_matches_ref(dtype, T, d, bt):
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(ks[0], (T, d), dtype) * 3
    w = jax.random.normal(ks[1], (d,), jnp.float32) * 0.1
    out = rmsnorm_kernel(x, w, bt=bt, interpret=True)
    ref = rmsnorm_ref(x, w)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol)


def test_rmsnorm_matches_model_layer():
    from repro.models.layers import rmsnorm as model_rmsnorm

    x = jax.random.normal(jax.random.PRNGKey(2), (8, 4, 64), jnp.float32)
    w = jnp.full((64,), 0.05, jnp.float32)
    ref = model_rmsnorm(x, w)
    out = rmsnorm_kernel(x.reshape(-1, 64), w, bt=8, interpret=True).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@given(
    nblocks=st.integers(1, 8),
    seed=st.integers(0, 1000),
)
@settings(max_examples=20, deadline=None)
def test_mrd_combine_property(nblocks, seed):
    n = nblocks * 256
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (n,), jnp.float32) * 10
    g = jax.random.normal(ks[1], (n,), jnp.float32) * 5
    q, s = quantize(g)
    out = mrd_combine(x, q, s, bn=n, interpret=True)
    # quantization error bound: |err| <= amax_block / 254 per element
    err = np.asarray(out) - (np.asarray(x) + np.asarray(g))
    bound = np.repeat(np.abs(np.asarray(g).reshape(-1, 256)).max(1), 256) / 254 + 1e-6
    assert np.all(np.abs(err) <= bound * 1.01)
