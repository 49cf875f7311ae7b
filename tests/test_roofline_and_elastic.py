"""Roofline HLO parser units + elastic-trainer end-to-end (subprocess)."""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch import roofline as R


def test_parse_collective_bytes_simple():
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(%x), replica_groups={}
  %ar = f32[1024]{0} all-reduce(%y), to_apply=%sum
  %cp = bf16[64,64]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
  %rs = f32[256]{0} reduce-scatter(%w), dimensions={0}
  %a2a.1 = s8[32]{0} all-to-all(%v), dimensions={0}
"""
    out = R.parse_collective_bytes(hlo)
    assert out["all-gather"] == 8 * 128 * 2
    assert out["all-reduce"] == 1024 * 4
    assert out["collective-permute"] == 64 * 64 * 2
    assert out["reduce-scatter"] == 256 * 4
    assert out["all-to-all"] == 32 * 1


def test_parse_collective_start_variants():
    hlo = "%s = f32[100]{0} all-reduce-start(%x), to_apply=%sum"
    out = R.parse_collective_bytes(hlo)
    assert out["all-reduce"] == 400


def test_roofline_report_terms():
    rep = R.RooflineReport(
        arch="x", shape="train_4k", mesh="single", chips=256,
        hlo_flops=197e12, hlo_bytes=819e9, collective_bytes={"all-reduce": 50_000_000_000},
        model_flops=197e12 * 256 * 0.5,
    )
    assert abs(rep.t_compute - 1.0) < 1e-9
    assert abs(rep.t_memory - 1.0) < 1e-9
    assert abs(rep.t_collective - 1.0) < 1e-9
    assert abs(rep.useful_flops_ratio - 0.5) < 1e-9
    assert abs(rep.roofline_fraction - 0.5) < 1e-9


_ELASTIC = textwrap.dedent(
    """
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.configs import registry
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.data.pipeline import DataConfig, SyntheticPipeline
    from repro.distributed import step as step_lib
    from repro.optim.optimizer import OptimizerConfig
    from repro.runtime.elastic import ElasticConfig, ElasticTrainer
    from jax.sharding import AxisType

    cfg = registry.get_smoke_config("llama3.2-1b")
    tcfg = step_lib.TrainConfig(
        microbatches=1, remat="none", grad_sync="mrd_leaf", monitor=False,
        optimizer=OptimizerConfig(lr=5e-3, schedule="const", warmup_steps=0))

    mesh = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4],
                         axis_types=(AxisType.Auto,))
    trainer = ElasticTrainer(
        mesh,
        step_fn_factory=lambda m: step_lib.make_train_step(cfg, m, tcfg),
        pipe_factory=lambda m: SyntheticPipeline(
            cfg, DataConfig(batch=12, seq_len=32, seed=0), m),
        checkpointer=Checkpointer(tempfile.mkdtemp()),
        cfg=ElasticConfig(ckpt_every=3),
    )
    state = trainer.init_or_restore(jax.random.PRNGKey(0))
    # fail device 0 at step 5: shrink 4 -> 3 (non-power-of-two, MRD handles it)
    state, losses = trainer.run(state, 10, fail_at={5: {0}})
    assert trainer.mesh.shape["data"] == 3, trainer.mesh.shape
    assert trainer.restarts == 1
    assert len(losses) >= 8
    assert np.mean(losses[-3:]) < np.mean(losses[:3]) + 0.05, losses
    print("ELASTIC-TRAINER-PASSED", [round(x, 3) for x in losses])
    """
)


@pytest.mark.slow
def test_elastic_trainer_end_to_end():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _ELASTIC],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout[-3000:]}\nSTDERR:\n{proc.stderr[-5000:]}"
    assert "ELASTIC-TRAINER-PASSED" in proc.stdout
