"""EF-SGD error feedback for the ``compressed`` grad-sync mode: the residual
accumulator's algebra, and a convergence curve where plain int8 quantization
stalls but error feedback recovers full convergence."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.collectives import plans
from repro.collectives.transforms import dequantize, ef_roundtrip, quantize


def test_ef_roundtrip_conserves_the_intended_send():
    """sendable + new_ef == grad + ef bit-exactly: nothing is ever lost,
    only delayed."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1024) * 10.0, jnp.float32)
    ef = jnp.asarray(rng.standard_normal(1024) * 0.01, jnp.float32)
    sendable, new_ef = ef_roundtrip(x, ef)
    np.testing.assert_array_equal(
        np.asarray(sendable + new_ef), np.asarray(x + ef)
    )
    # sendable is on the quantization grid: re-quantizing is lossless
    q, s = quantize(sendable)
    np.testing.assert_allclose(
        np.asarray(dequantize(q, s)), np.asarray(sendable), rtol=1e-6, atol=1e-7
    )


def test_ef_accumulates_sub_quantum_signal():
    """A constant gradient far below the block's quantization step is dropped
    forever without EF, but crosses the grid within ~amax/(254*g) steps
    with it."""
    n = 256
    big = jnp.zeros((n,), jnp.float32).at[0].set(1.0)  # sets amax -> step ~ 1/254
    tiny = jnp.full((n,), 1e-4, jnp.float32)  # far below 1/254
    g = big + tiny

    sent_plain = dequantize(*quantize(g))
    assert float(jnp.max(jnp.abs(sent_plain[1:]))) == 0.0  # dropped

    ef = jnp.zeros((n,), jnp.float32)
    delivered = jnp.zeros((n,), jnp.float32)
    for _ in range(60):  # 1e-4 * 60 > (1/127)/2: must cross the grid
        sendable, ef = ef_roundtrip(g, ef)
        delivered = delivered + sendable
    # the tiny coordinates were delivered after all — in whole quanta, so
    # the per-tick average is lumpy but unmistakably nonzero
    mean_tail = float(jnp.mean(delivered[1:])) / 60
    assert 0.5e-4 < mean_tail < 2e-4, mean_tail


def test_compressed_ef_beats_plain_compressed_convergence():
    """Distributed SGD through a fully-quantized int8 collective (the MRD
    butterfly quantizes *every* contribution at *every* stage — no rank's
    raw buffer leaks into the result), p=4, sim executor: an ill-scaled
    quadratic whose per-block gradients hide small coordinates under a
    large one.  Plain int8 quantization stalls well above the solution;
    the same run with the EF-SGD residual fold converges several times
    closer.  This is the same ``ef_roundtrip`` fold the ``compressed``
    grad-sync strategy runs per bucket (``gradsync/mrd_zero1.py``)."""
    p, n = 4, 1024  # n % 256 == 0 (int8 block alignment)
    rng = np.random.default_rng(0)
    # per-rank targets; each 256-block has one large coordinate so amax/127
    # dwarfs the rest of the block's gradient entries
    base = rng.uniform(0.5e-3, 1.5e-3, size=n).astype(np.float32)
    base[::256] = 1.0
    targets = jnp.asarray(
        np.stack([base * (1.0 + 0.1 * r) for r in range(p)]), jnp.float32
    )
    t_mean = jnp.mean(targets, axis=0)

    plan = plans.allreduce_plan(schedule="mrd", p=p, op="sum", transform="int8")
    lr = 0.2

    def train(use_ef, steps=150):
        x = jnp.zeros((n,), jnp.float32)
        ef = jnp.zeros((p, n), jnp.float32)
        for _ in range(steps):
            g = jnp.broadcast_to(x, (p, n)) - targets  # per-rank grads
            if use_ef:
                g, ef = jax.vmap(ef_roundtrip)(g, ef)
            mean_g = plan.run(g)[0] / p
            x = x - lr * mean_g
        return float(jnp.max(jnp.abs(x - t_mean)))

    err_plain = train(use_ef=False)
    err_ef = train(use_ef=True)
    assert err_ef < 0.4 * err_plain, (err_ef, err_plain)
    assert err_ef < 2.5e-4, err_ef


def test_trainconfig_wires_error_feedback_state():
    """The compressed strategy carries opt['ef'] iff error feedback is on
    (builder-level check; the multi-device trajectory runs in
    tests/test_train_distributed.py)."""
    from jax.sharding import AxisType
    from repro.configs import registry
    from repro.distributed import step as step_lib

    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1], axis_types=(AxisType.Auto,))
    for ef_on in (True, False):
        tcfg = step_lib.TrainConfig(
            grad_sync="compressed", monitor=False, error_feedback=ef_on
        )
        _, init_state, state_specs, _ = step_lib.make_train_step(cfg, mesh, tcfg)
        state = init_state(jax.random.PRNGKey(0))
        assert ("ef" in state["opt"]) == ef_on
        if ef_on:
            specs = state_specs(state)
            assert "ef" in specs["opt"]
            assert state["opt"]["ef"].ndim == 2
