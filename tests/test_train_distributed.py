"""Distributed training integration: gspmd vs MRD-ZeRO-1 equivalence,
non-power-of-two DP groups, monitor detection — on an 8-device subprocess."""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import AxisType
    import dataclasses

    from repro.configs import registry
    from repro.data.pipeline import DataConfig, SyntheticPipeline
    from repro.distributed import step as step_lib
    from repro.optim.optimizer import OptimizerConfig

    cfg = registry.get_smoke_config("llama3.2-1b")

    def run_mode(mesh_shape, axis_names, grad_sync, steps=6, monitor=True, ndev=8,
                 bucket_bytes=32 * 2**20):
        mesh = jax.make_mesh(mesh_shape, axis_names,
                                devices=jax.devices()[:ndev],
                                axis_types=(AxisType.Auto,) * len(axis_names))
        tcfg = step_lib.TrainConfig(
            microbatches=2, remat="none", grad_sync=grad_sync, monitor=monitor,
            monitor_threshold=1e-6, bucket_bytes=bucket_bytes,
            optimizer=OptimizerConfig(lr=1e-2, schedule="const", warmup_steps=0,
                                      grad_clip=1.0),
        )
        train_step, init_state, state_specs, rules = step_lib.make_train_step(cfg, mesh, tcfg)
        with mesh:
            state = init_state(jax.random.PRNGKey(0))
            specs = state_specs(state)
            shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
            state = jax.device_put(state, shardings)
            pipe = SyntheticPipeline(cfg, DataConfig(batch=8, seq_len=32, seed=0), mesh)
            jstep = jax.jit(train_step)
            losses = []
            for _ in range(steps):
                batch = pipe.next_batch()
                state, metrics = jstep(state, batch)
                losses.append(float(metrics["loss"]))
        return losses, state, metrics

    # --- 1. gspmd baseline: loss decreases ---
    l_gspmd, st_g, _ = run_mode((4, 2), ("data", "model"), "gspmd")
    assert l_gspmd[-1] < l_gspmd[0], f"gspmd loss: {l_gspmd}"
    print("gspmd OK", [round(x,3) for x in l_gspmd])

    # --- 2. MRD-ZeRO-1: matches gspmd step-for-step (same math).  A small
    # bucket cap forces the multi-bucket pipelined RS/AG path. ---
    l_mrd, st_m, _ = run_mode((4, 2), ("data", "model"), "mrd_zero1",
                              bucket_bytes=1 << 15)
    np.testing.assert_allclose(l_gspmd, l_mrd, rtol=2e-2, atol=2e-2)
    print("mrd_zero1 == gspmd OK", [round(x,3) for x in l_mrd])

    # --- params agreement after N steps ---
    pg = jax.tree.leaves(st_g["params"]); pm = jax.tree.leaves(st_m["params"])
    for a, b in zip(pg, pm):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-2)
    print("param agreement OK")

    # --- 3. non-power-of-two DP (p=6: the paper's headline case) ---
    l_np2, _, _ = run_mode((6,), ("data",), "mrd_zero1", ndev=6)
    assert l_np2[-1] < l_np2[0], f"non-p2 loss: {l_np2}"
    print("non-p2 dp=6 OK", [round(x,3) for x in l_np2])

    # --- 4. compressed grad sync: converges (within quantization noise) ---
    l_cmp, _, _ = run_mode((4, 2), ("data", "model"), "compressed")
    assert l_cmp[-1] < l_cmp[0] + 0.05, f"compressed loss: {l_cmp}"
    print("compressed OK", [round(x,3) for x in l_cmp])

    # --- 5. monitor fires when threshold is lenient ---
    _, _, metrics = run_mode((4, 2), ("data", "model"), "gspmd", steps=8)
    # threshold 1e-6 won't fire in 8 steps; re-run with a huge threshold
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                            axis_types=(AxisType.Auto,) * 2)
    tcfg = step_lib.TrainConfig(
        microbatches=1, remat="none", grad_sync="gspmd", monitor=True,
        monitor_threshold=100.0,
        optimizer=OptimizerConfig(lr=1e-3, schedule="const", warmup_steps=0))
    train_step, init_state, state_specs, rules = step_lib.make_train_step(cfg, mesh, tcfg)
    with mesh:
        state = jax.device_put(init_state(jax.random.PRNGKey(0)),
            jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs(state := init_state(jax.random.PRNGKey(0)))))
        pipe = SyntheticPipeline(cfg, DataConfig(batch=8, seq_len=32, seed=0), mesh)
        jstep = jax.jit(train_step)
        fired = False
        from repro.core.nonblocking import cycle_length
        need = cycle_length(4) + 2
        for i in range(need + 2):
            state, metrics = jstep(state, pipe.next_batch())
            if bool(metrics["converged"]):
                fired = True
                break
    assert fired, "monitor never fired with lenient threshold"
    print(f"monitor fired at step {i} (cycle length {need-2}) OK")
    print("ALL-TRAIN-DIST-PASSED")
    """
)


@pytest.mark.slow
def test_distributed_training_modes():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env, timeout=1800,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout[-4000:]}\nSTDERR:\n{proc.stderr[-6000:]}"
    assert "ALL-TRAIN-DIST-PASSED" in proc.stdout
