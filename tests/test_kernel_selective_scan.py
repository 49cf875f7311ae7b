"""Pallas selective-scan kernel vs oracle + vs the model's chunked scan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.selective_scan.ops import selective_scan
from repro.kernels.selective_scan.ref import selective_scan_ref


def _inputs(seed, B, S, D, N, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    # decay in (0, 1): well-conditioned recurrence like exp(dt*A)
    decay = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, D, N))).astype(dtype)
    bx = (jax.random.normal(ks[1], (B, S, D, N)) * 0.1).astype(dtype)
    cs = jax.random.normal(ks[2], (B, S, N), dtype)
    return decay, bx, cs


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,D,N,bd,chunk",
    [
        (1, 64, 16, 8, 16, 16),
        (2, 128, 32, 16, 16, 32),
        (1, 96, 64, 4, 32, 64),  # S not multiple of chunk -> padding path
    ],
)
def test_kernel_matches_ref(dtype, B, S, D, N, bd, chunk):
    decay, bx, cs = _inputs(0, B, S, D, N, dtype)
    out = selective_scan(decay, bx, cs, bd=bd, chunk=chunk, interpret=True)
    ref, _ = selective_scan_ref(decay, bx, cs)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **tol)


@given(
    s=st.integers(4, 80),
    d=st.sampled_from([8, 16]),
    n=st.sampled_from([4, 8]),
    chunk=st.sampled_from([8, 16, 32]),
    seed=st.integers(0, 100),
)
@settings(max_examples=15, deadline=None)
def test_kernel_matches_ref_property(s, d, n, chunk, seed):
    decay, bx, cs = _inputs(seed, 1, s, d, n)
    out = selective_scan(decay, bx, cs, bd=d, chunk=chunk, interpret=True)
    ref, _ = selective_scan_ref(decay, bx, cs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_kernel_matches_model_recurrence():
    """The kernel computes the exact recurrence the mamba1 block uses, on
    inputs produced by the model's own SSM-input projection."""
    from repro.configs import registry
    from repro.models.ssm import _mamba1_ssm_inputs, mamba1_init

    cfg = registry.get_smoke_config("falcon-mamba-7b")
    p = mamba1_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, S, di = 2, 48, cfg.d_inner
    x1 = jax.random.normal(jax.random.PRNGKey(1), (B, S, di), jnp.float32)
    decay, bx, cs = _mamba1_ssm_inputs(p, x1, cfg)
    y_ref, _ = selective_scan_ref(decay, bx, cs)
    y_kernel = selective_scan(decay, bx, cs, bd=di, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_ref), rtol=1e-4, atol=1e-4)
