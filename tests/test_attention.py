"""flash_scan (tiled online-softmax) vs full attention equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models.attention import attention


def _qkv(key, B, Sq, Skv, H, KV, hd, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Skv, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, Skv, KV, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 16])
@pytest.mark.parametrize("HKV", [(4, 4), (8, 2)])
def test_flash_equals_full(causal, window, HKV):
    H, KV = HKV
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 48, 48, H, KV, 16)
    full = attention(q, k, v, causal=causal, window=window, impl="full")
    flash = attention(q, k, v, causal=causal, window=window, impl="flash_scan", chunk=16)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(full), rtol=2e-5, atol=2e-5)


@given(
    sq=st.integers(1, 40),
    skv=st.integers(8, 70),
    chunk=st.sampled_from([8, 16, 32]),
    seed=st.integers(0, 1000),
)
@settings(max_examples=25, deadline=None)
def test_flash_equals_full_property(sq, skv, chunk, seed):
    q, k, v = _qkv(jax.random.PRNGKey(seed), 1, sq, skv, 4, 2, 8)
    # decode-style: q positions continue after the kv prefix when sq < skv
    off = max(skv - sq, 0)
    full = attention(q, k, v, causal=True, q_offset=off, impl="full")
    flash = attention(q, k, v, causal=True, q_offset=off, impl="flash_scan", chunk=chunk)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(full), rtol=3e-5, atol=3e-5)


def test_flash_valid_len_masking():
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 1, 64, 4, 4, 8)
    full = attention(q, k, v, causal=False, impl="full", k_valid_len=37)
    flash = attention(q, k, v, causal=False, impl="flash_scan", chunk=16, k_valid_len=37)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(full), rtol=2e-5, atol=2e-5)


def test_flash_grads_match_full():
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 24, 24, 4, 2, 8)

    def loss(impl):
        return lambda q_: jnp.sum(
            attention(q_, k, v, causal=True, impl=impl, chunk=8) ** 2
        )

    gf = jax.grad(loss("full"))(q)
    gs = jax.grad(loss("flash_scan"))(q)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gf), rtol=1e-4, atol=1e-4)
