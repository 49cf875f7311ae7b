"""Fault tolerance: checkpoint/restore, async save atomicity, failure
detection, and the elastic shrink path (dp=4 -> kill one -> dp=3, non-p2)."""

import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

from repro.runtime.fault_tolerance import FailureDetector, HeartbeatConfig


def test_failure_detector_timeout():
    det = FailureDetector([0, 1, 2], HeartbeatConfig(timeout_s=10))
    for w in (0, 1, 2):
        det.heartbeat(w, now=0.0)
    det.heartbeat(0, now=50.0)
    det.heartbeat(1, now=50.0)
    assert det.failed(now=55.0) == [2]


def test_straggler_detection():
    cfg = HeartbeatConfig(straggler_factor=3.0, evict_after_straggler_steps=2)
    det = FailureDetector([0, 1, 2, 3], cfg)
    for t in range(10):
        for w in (0, 1, 2):
            det.heartbeat(w, now=t, step_time=1.0)
        det.heartbeat(3, now=t, step_time=10.0)  # 10x median
    det.stragglers()
    assert 3 in det.stragglers()


def test_checkpointer_roundtrip_and_gc(tmp_path):
    import jax.numpy as jnp

    from repro.checkpoint.checkpointer import Checkpointer

    ck = Checkpointer(str(tmp_path), keep=2)
    state = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones(4, jnp.int32)}}
    for step in (1, 2, 3):
        ck.save(step, state, extra={"data": {"step": step}}, block=True)
    assert ck.latest_step() == 3
    assert ck.list_steps() == [2, 3]  # gc kept last 2
    out = ck.restore(3, state)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(state["a"]))
    assert ck.manifest(3)["extra"]["data"]["step"] == 3


_ELASTIC = textwrap.dedent(
    """
    import os, sys, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import AxisType

    from repro.configs import registry
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.data.pipeline import DataConfig, SyntheticPipeline
    from repro.distributed import step as step_lib
    from repro.optim.optimizer import OptimizerConfig
    from repro.runtime.fault_tolerance import shrink_mesh, recover

    cfg = registry.get_smoke_config("llama3.2-1b")
    tcfg = step_lib.TrainConfig(
        microbatches=1, remat="none", grad_sync="mrd_zero1", monitor=False,
        optimizer=OptimizerConfig(lr=5e-3, schedule="const", warmup_steps=0))

    ckdir = tempfile.mkdtemp()

    # ---- phase 1: dp=4 ----
    mesh4 = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4],
                          axis_types=(AxisType.Auto,))
    step4, init4, specs4, _ = step_lib.make_train_step(cfg, mesh4, tcfg)
    with mesh4:
        state = init4(jax.random.PRNGKey(0))
        state = jax.device_put(state, jax.tree.map(
            lambda s: NamedSharding(mesh4, s), specs4(state)))
        pipe = SyntheticPipeline(cfg, DataConfig(batch=12, seq_len=32, seed=0), mesh4)
        js = jax.jit(step4)
        losses = []
        for i in range(4):
            state, m = js(state, pipe.next_batch())
            losses.append(float(m["loss"]))
        ck = Checkpointer(ckdir)
        ck.save(int(state["step"]), state, extra={"data": pipe.state_dict()}, block=True)
    print("phase1 losses:", [round(x,3) for x in losses])

    # ---- failure: device 0 dies -> shrink to dp=3 (non-power-of-two!) ----
    mesh3, kept = shrink_mesh(mesh4, {0}, "data")
    assert mesh3.shape["data"] == 3, mesh3.shape

    # MRD-ZeRO-1 state is dp-major: rebuild step fns for the new mesh; the
    # flat opt shards are re-derived from the restored params (simplest safe
    # elastic policy: params + data position survive; moments restart).
    step3, init3, specs3, _ = step_lib.make_train_step(cfg, mesh3, tcfg)
    with mesh3:
        template = init3(jax.random.PRNGKey(0))
        shardings = jax.tree.map(lambda s: NamedSharding(mesh3, s), specs3(template))
        # restore params + step from checkpoint; re-init opt for new dp extent
        full = Checkpointer(ckdir).restore(
            Checkpointer(ckdir).latest_step(),
            jax.tree.map(lambda x: np.zeros(x.shape, x.dtype), # noqa
                {"params": template["params"], "step": template["step"]}),
        )
        state3 = init3(jax.random.PRNGKey(0))
        state3["params"] = full["params"]
        state3["step"] = jnp.asarray(full["step"])
        # re-seed masters from restored params (bucketed repack for dp=3,
        # matching make_zero1's shard layout)
        from repro.distributed.step import zero1_masters_from_params
        state3["opt"]["master"] = zero1_masters_from_params(
            full["params"], mesh3, ("data",), bucket_bytes=tcfg.bucket_bytes)
        state3 = jax.device_put(state3, shardings)

        pipe3 = SyntheticPipeline(cfg, DataConfig(batch=12, seq_len=32, seed=0), mesh3)
        pipe3.load_state_dict(Checkpointer(ckdir).manifest(
            Checkpointer(ckdir).latest_step())["extra"]["data"])
        js3 = jax.jit(step3)
        losses3 = []
        for i in range(4):
            state3, m3 = js3(state3, pipe3.next_batch())
            losses3.append(float(m3["loss"]))
    print("phase2 (dp=3) losses:", [round(x,3) for x in losses3])
    # training continues from where it left off: loss stays on trend
    assert losses3[0] < losses[0], (losses, losses3)
    assert losses3[-1] <= losses3[0] + 0.05
    print("ELASTIC-RESTART-PASSED")
    """
)


@pytest.mark.slow
def test_elastic_shrink_restart():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _ELASTIC],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout[-3000:]}\nSTDERR:\n{proc.stderr[-5000:]}"
    assert "ELASTIC-RESTART-PASSED" in proc.stdout
