"""Differential coverage for the PR-3 ``interval`` (windowed) protocol:
device-executor ConvergenceMonitor == sim-executor protocol path, bit for
bit, across p in {2..9}.

The existing plans matrix proves device==sim for raw collectives
(schedule x op x transform); this closes the gap for the *windowed
protocol* layered on top — per-rank window latching
(``monitor_contribution``) composed with the staged non-blocking MRD
reduction — which is exactly the code the training loop runs on device
and the asynchrony engine runs in sim.
"""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=9"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AxisType
    from repro.asynchrony.protocols import (
        RES_INIT, ConvergenceMonitor, get_protocol)
    from repro.collectives import plans

    W = 4
    THR = 0.08
    rng = np.random.default_rng(0)

    for p in range(2, 10):
        mesh = jax.make_mesh((p,), ("r",), devices=jax.devices()[:p], axis_types=(AxisType.Auto,))
        mon = ConvergenceMonitor(axis_name="r", threshold=THR,
                                 mode="interval", window=W)
        cycle = plans.allreduce_plan(schedule="mrd", p=p, op="max").cycle_length()
        T = 5 * cycle + W + 8
        # per-rank metrics decay below THR so `done` flips inside the run
        metrics = (rng.uniform(0.8, 1.2, (T, p)) * (0.6 ** np.arange(T))[:, None]
                   ).astype(np.float32)

        # ---- device: the training-loop monitor inside shard_map ----
        mon0 = mon.init(varying=False)
        rows = jax.tree.map(lambda x: jnp.broadcast_to(x, (p,) + x.shape), mon0)

        def local(rows1, m1, i):
            st = jax.tree.map(lambda x: x[0], rows1)
            new, done, val = mon.step(st, m1[0], i)
            return jax.tree.map(lambda x: x[None], new), done[None], val[None]

        rspec = jax.tree.map(lambda _: P("r"), rows)
        dev_step = jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(rspec, P("r"), P()),
            out_specs=(rspec, P("r"), P("r")),
            axis_names={"r"}, check_vma=False))
        dev_done, dev_val = [], []
        with mesh:
            for i in range(T):
                rows, done, val = dev_step(
                    rows, jnp.asarray(metrics[i]), jnp.int32(i))
                dev_done.append(np.asarray(done))
                dev_val.append(np.asarray(val))

        # ---- sim: the same protocol over the stacked sim executor ----
        proto = get_protocol("interval")
        plan = plans.allreduce_plan(schedule="mrd", p=p, op="max")
        assert plan.cycle_length() == cycle
        mstate = {"win": jnp.full((p, W), RES_INIT, jnp.float32)}
        nb = plan.init(jnp.full((p,), RES_INIT, jnp.float32))
        value = jnp.full((p,), RES_INIT, jnp.float32)
        done = jnp.zeros((p,), jnp.bool_)

        @jax.jit
        def sim_step(mstate, nb, value, done, m, i):
            mstate, contrib = jax.vmap(
                lambda ms, mt: proto.monitor_contribution(ms, mt, i, cycle)
            )(mstate, m)
            nb = plan.step(nb, contrib)
            value = jnp.where(nb["flag"], nb["result"], value)
            done = done | (nb["flag"] & (value < THR))
            return mstate, nb, value, done

        sim_done, sim_val = [], []
        for i in range(T):
            mstate, nb, value, done = sim_step(
                mstate, nb, value, done, jnp.asarray(metrics[i]), jnp.int32(i))
            sim_done.append(np.asarray(done))
            sim_val.append(np.asarray(value))

        dev_done, dev_val = np.stack(dev_done), np.stack(dev_val)
        sim_done, sim_val = np.stack(sim_done), np.stack(sim_val)
        assert np.array_equal(dev_val, sim_val), (
            f"p={p} certified-value divergence: "
            f"max {np.abs(dev_val - sim_val).max()}")
        assert np.array_equal(dev_done, sim_done), f"p={p} done divergence"
        assert dev_done[-1].all(), f"p={p}: run too short to certify"
        print(f"p={p} interval device==sim OK (certified at "
              f"tick {int(np.argmax(dev_done[:, 0]))})")

    print("INTERVAL-DIFFERENTIAL-PASSED")
    """
)


@pytest.mark.slow
def test_interval_monitor_device_sim_bit_agreement():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "INTERVAL-DIFFERENTIAL-PASSED" in proc.stdout
