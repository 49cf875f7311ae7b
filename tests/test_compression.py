"""int8 blockwise compression: error bounds + error-feedback property."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.collectives import compression as C


@given(
    nblocks=st.integers(1, 16),
    scale=st.floats(1e-3, 1e3),
    seed=st.integers(0, 10000),
)
@settings(max_examples=40, deadline=None)
def test_quantization_error_bound(nblocks, scale, seed):
    """|x - deq(q(x))| <= amax_block/254 elementwise (half-ulp of the grid)."""
    n = nblocks * C.BLOCK
    x = jnp.asarray(
        np.random.default_rng(seed).standard_normal(n) * scale, jnp.float32
    )
    q, s = C.quantize(x)
    err = np.abs(np.asarray(C.dequantize(q, s) - x))
    amax = np.abs(np.asarray(x)).reshape(nblocks, C.BLOCK).max(1)
    bound = np.repeat(amax / 254.0, C.BLOCK) + 1e-7
    assert np.all(err <= bound * 1.01)


def test_quantize_preserves_zeros_and_signs():
    x = jnp.asarray([0.0] * 128 + [1.0] * 64 + [-1.0] * 64, jnp.float32)
    q, s = C.quantize(x)
    deq = np.asarray(C.dequantize(q, s))
    assert np.all(deq[:128] == 0.0)
    assert np.all(deq[128:192] > 0)
    assert np.all(deq[192:] < 0)


def test_error_feedback_converges():
    """EF-SGD property: with error feedback, the *accumulated* transmitted
    signal tracks the accumulated true signal (bias-free compression)."""
    rng = np.random.default_rng(0)
    n = 512
    true_sum = np.zeros(n)
    sent_sum = np.zeros(n)
    ef = jnp.zeros(n, jnp.float32)
    for _ in range(50):
        g = jnp.asarray(rng.standard_normal(n) * 0.1, jnp.float32)
        with_ef = g + ef
        q, s = C.quantize(with_ef)
        sent = C.dequantize(q, s)
        ef = with_ef - sent
        true_sum += np.asarray(g)
        sent_sum += np.asarray(sent)
    # residual error is bounded by one step's quantization error, not O(T)
    resid = np.abs(true_sum - sent_sum)
    assert resid.max() < 0.01, resid.max()


def test_wire_bytes_factor():
    assert abs(C.wire_bytes_factor(4) - (1 + 4 / 256) / 4) < 1e-9
    assert C.wire_bytes_factor(2) < 0.51
