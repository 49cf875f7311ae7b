"""Property tests on the asynchrony registries: every delay model respects
the paper's two fairness conditions by construction, and every certifying
protocol is sound against a model-derived residual bound."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.asynchrony import (
    DELAY_MODELS,
    AsyncConfig,
    get_delay_model,
    make_solver,
    run,
)

MODEL_NAMES = sorted(DELAY_MODELS)


def _drive_model(name, p, max_delay, force_every, seed, ticks=48):
    """Sample `ticks` ticks of a model, carrying last_active like the engine."""
    cfg = AsyncConfig(
        p=p, max_delay=max_delay, force_every=force_every,
        activity=0.5, seed=seed,
    )
    model = get_delay_model(name)
    params = model.default_params(cfg, p)
    state = model.init_state(p)
    base = jax.random.PRNGKey(seed)
    last_active = jnp.zeros((p,), jnp.int32)
    out = []
    for t in range(1, ticks + 1):
        k_model, _ = jax.random.split(jax.random.fold_in(base, t))
        active, delays, state = model.sample(
            params, state, jnp.int32(t), k_model, last_active,
            p=p, max_delay=max_delay, force_every=force_every,
        )
        out.append((t, np.asarray(active), np.asarray(delays), np.asarray(last_active)))
        last_active = jnp.where(active, t, last_active)
    return out


def _check_fairness(rows, max_delay, force_every):
    for t, active, delays, last_active in rows:
        assert delays.dtype == np.int32
        assert (delays >= 0).all() and (delays <= max_delay).all(), (
            f"tick {t}: delay out of [0, {max_delay}]"
        )
        starved = (t - last_active) >= force_every
        assert active[starved].all(), (
            f"tick {t}: starved worker not forced active"
        )


@pytest.mark.parametrize("name", MODEL_NAMES)
def test_delay_model_fairness_example(name):
    """Example-based floor (runs even without hypothesis): bounds + forced
    activity hold for every registered model."""
    rows = _drive_model(name, p=6, max_delay=3, force_every=4, seed=0)
    _check_fairness(rows, max_delay=3, force_every=4)
    # every worker iterates infinitely often: implied count lower bound
    total_active = sum(a.astype(int) for _, a, _, _ in rows)
    assert (total_active >= len(rows) // 4 - 1).all()


@given(
    name=st.sampled_from(MODEL_NAMES),
    p=st.integers(2, 9),
    max_delay=st.integers(1, 5),
    force_every=st.integers(2, 7),
    seed=st.integers(0, 1000),
)
@settings(max_examples=25, deadline=None)
def test_delay_model_fairness_property(name, p, max_delay, force_every, seed):
    """Hypothesis-hardened: across random shapes/bounds/seeds, every model's
    emissions respect max_delay and forced-activity fairness."""
    rows = _drive_model(name, p, max_delay, force_every, seed, ticks=32)
    _check_fairness(rows, max_delay, force_every)


# ---------------------------------------------------------------------------
# Protocol soundness (paper S3, hardened): certification => residual bound
# ---------------------------------------------------------------------------

# n divisible by every p in the sweep (incl. non-powers-of-two 3 and 5)
_N = 120
_SHIFT = 0.5  # contraction rho(|T|) <= 2/(2+shift) = 0.8


def _bound(fp, protocol, eps):
    """Model-derived certified-residual bound.

    ``exact`` certifies ``||f(x̄)-x̄|| < eps`` on the frozen snapshot —
    the bound is eps itself.  ``inexact``/``interval`` certify that update
    magnitudes cleared eps; for a contraction with factor rho, an update
    magnitude d at a point x bounds the residual by d·(1+rho)/(1-rho)
    (standard fixed-point perturbation: ||f(x)-x|| <= ||x_new - x||·(1+rho)
    /(1-rho) along the iteration).
    """
    if protocol == "exact":
        return eps
    rho = fp.contraction
    assert rho is not None and rho < 1
    return eps * (1 + rho) / (1 - rho)


@pytest.mark.parametrize("protocol", ["inexact", "exact", "interval"])
@pytest.mark.parametrize("p", [2, 3, 4, 5, 8])
def test_protocol_soundness(protocol, p):
    fp = make_solver("poisson1d", n=_N, shift=_SHIFT, seed=0)
    eps = 1e-5
    for seed in (0, 3):
        cfg = AsyncConfig(
            p=p, detection=protocol, eps=eps, max_ticks=80000,
            seed=seed, max_delay=3, activity=0.6,
        )
        r = run(fp, cfg)
        assert r.detected, f"{protocol} never fired (p={p}, seed={seed})"
        bound = _bound(fp, protocol, eps)
        assert r.true_res < bound, (
            f"{protocol} certified a bad solution: true_res={r.true_res:.3e} "
            f">= bound={bound:.3e} (p={p}, seed={seed})"
        )


@pytest.mark.parametrize("protocol", ["inexact", "exact", "interval"])
def test_protocol_soundness_under_stragglers(protocol):
    """Soundness must survive adversarial environments, not just iid ones."""
    fp = make_solver("poisson1d", n=_N, shift=_SHIFT, seed=0)
    eps = 1e-5
    cfg = AsyncConfig(
        p=4, detection=protocol, eps=eps, max_ticks=80000,
        seed=0, max_delay=4, activity=0.6, delay_model="straggler",
    )
    r = run(fp, cfg)
    assert r.detected
    assert r.true_res < _bound(fp, protocol, eps)
