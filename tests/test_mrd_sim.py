"""Exhaustive correctness of the MRD executors (sim backend) for arbitrary p,
including non-powers-of-two — the paper's headline case."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import mrd
from repro.core.topology import pivot


def _stack(p, shape, dtype, seed):
    rng = np.random.default_rng(seed)
    if np.issubdtype(dtype, np.integer):
        return jnp.asarray(rng.integers(-50, 50, size=(p, *shape)).astype(dtype))
    return jnp.asarray((rng.standard_normal((p, *shape)) * 10).astype(dtype))


@given(
    p=st.integers(min_value=1, max_value=33),
    seed=st.integers(0, 2**31 - 1),
    op=st.sampled_from(["sum", "max", "min"]),
)
@settings(max_examples=80, deadline=None)
def test_sim_allreduce_matches_reference(p, seed, op):
    x = _stack(p, (7,), np.float32, seed)
    out = mrd.sim_allreduce(x, op=op)
    ref = {"sum": x.sum(0), "max": x.max(0), "min": x.min(0)}[op]
    np.testing.assert_allclose(
        np.asarray(out), np.broadcast_to(ref, (p, 7)), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32, "bfloat16"])
@pytest.mark.parametrize("p", [3, 8, 13])
def test_sim_allreduce_dtypes(p, dtype):
    if dtype == "bfloat16":
        x = jnp.asarray(np.arange(p * 5).reshape(p, 5), jnp.bfloat16)
    else:
        x = _stack(p, (5,), dtype, 0)
    out = mrd.sim_allreduce(x, op="sum")
    np.testing.assert_allclose(
        np.asarray(out, np.float64),
        np.broadcast_to(np.asarray(x, np.float64).sum(0), (p, 5)),
        rtol=1e-2 if dtype == "bfloat16" else 1e-6,
    )


@given(
    p=st.integers(min_value=1, max_value=33),
    mult=st.integers(min_value=1, max_value=3),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=60, deadline=None)
def test_sim_reduce_scatter_segments(p, mult, seed):
    p0, _, _ = pivot(p)
    n = p0 * mult
    x = _stack(p, (n,), np.float32, seed)
    out = np.asarray(mrd.sim_reduce_scatter(x))
    ref = np.asarray(x.sum(0))
    for i in range(p0):  # pivot ranks hold natural-order segments
        np.testing.assert_allclose(
            out[i], ref[i * mult : (i + 1) * mult], rtol=1e-5, atol=1e-4
        )


@given(
    p=st.integers(min_value=1, max_value=33),
    mult=st.integers(min_value=1, max_value=3),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=60, deadline=None)
def test_sim_rabenseifner_allreduce(p, mult, seed):
    p0, _, _ = pivot(p)
    n = p0 * mult
    x = _stack(p, (n,), np.float32, seed)
    out = np.asarray(mrd.sim_rabenseifner_allreduce(x))
    ref = np.broadcast_to(np.asarray(x.sum(0)), (p, n))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


def test_sim_allreduce_multidim_and_pytree_shape():
    p = 6
    x = _stack(p, (3, 4), np.float32, 1)
    out = mrd.sim_allreduce(x, op="max")
    np.testing.assert_allclose(np.asarray(out), np.broadcast_to(x.max(0), (p, 3, 4)))


def test_sim_allreduce_jit_and_grad():
    """The collective is differentiable (needed if used inside training math)."""
    p = 5
    x = _stack(p, (4,), np.float32, 2)

    f = jax.jit(lambda v: mrd.sim_allreduce(v, op="sum").sum())
    g = jax.grad(lambda v: mrd.sim_allreduce(v, op="sum")[0].sum())(x)
    # d(sum_i x_i)/dx_j = 1 for every j contributing to row 0's total
    np.testing.assert_allclose(np.asarray(g), np.ones((p, 4)), rtol=1e-6)
    assert np.isfinite(float(f(x)))
