"""Bounded-staleness local SGD: replicas diverge between syncs, converge at
sync points (the async-iterations idea applied to training)."""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import AxisType

    from repro.configs import registry
    from repro.data.pipeline import DataConfig, SyntheticPipeline
    from repro.distributed import step as step_lib
    from repro.optim.optimizer import OptimizerConfig

    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    tcfg = step_lib.TrainConfig(
        microbatches=1, remat="none", grad_sync="local_sgd", monitor=False,
        local_sync_every=4,
        optimizer=OptimizerConfig(lr=5e-3, schedule="const", warmup_steps=0))
    train_step, init_state, state_specs, _ = step_lib.make_train_step(cfg, mesh, tcfg)
    with mesh:
        state = init_state(jax.random.PRNGKey(0))
        state = jax.device_put(state, jax.tree.map(
            lambda s: NamedSharding(mesh, s), state_specs(state)))
        pipe = SyntheticPipeline(cfg, DataConfig(batch=8, seq_len=32, seed=0), mesh)
        js = jax.jit(train_step)
        losses = []
        for i in range(16):
            state, m = js(state, pipe.next_batch())
            losses.append(float(m["loss"]))
            # replica divergence across DP shards
            w = np.asarray(state["params"]["embed"], np.float32)  # [4, V, d]
            spread = np.abs(w - w[0]).max()
            synced = (i + 1) % 4 == 0
            if synced:
                assert spread < 1e-5, f"step {i}: replicas differ after sync ({spread})"
            print(f"step {i}: loss={losses[-1]:.3f} replica_spread={spread:.2e} synced={synced}")
        assert np.mean(losses[-4:]) < np.mean(losses[:4]) + 0.02, losses
    print("LOCAL-SGD-PASSED")
    """
)


@pytest.mark.slow
def test_local_sgd_bounded_staleness():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout[-3000:]}\nSTDERR:\n{proc.stderr[-5000:]}"
    assert "LOCAL-SGD-PASSED" in proc.stdout
