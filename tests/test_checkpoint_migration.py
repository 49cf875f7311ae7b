"""Versioned checkpoint layout migration (DESIGN.md S12 satellite).

PR 3 broke restore of older checkpoints twice: compressed runs gained an
``opt/ef`` leaf, and the ConvergenceMonitor's per-protocol policy state
moved under ``m/`` (``monitor/latched`` -> ``monitor/m/latched``).  The
checkpointer now stamps ``layout_version`` in the manifest and migrates
older layouts on restore; both breaks are covered here against a *real*
compressed+monitored train state.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import (
    LAYOUT_VERSION,
    Checkpointer,
    migrate_layout,
)
from repro.configs import registry
from repro.distributed import step as step_lib
from repro.optim.optimizer import OptimizerConfig


def _real_state():
    """A genuine compressed + exact-monitor train state (dp=1, in-process):
    has the 'opt/ef' leaf and the 'monitor/m/latched' key — exactly the two
    PR-3 layout breaks."""
    cfg = registry.get_smoke_config("llama3.2-1b")
    tcfg = step_lib.TrainConfig(
        microbatches=1, remat="none", grad_sync="compressed",
        monitor=True, monitor_mode="exact", monitor_threshold=1e-6,
        optimizer=OptimizerConfig(lr=1e-3, schedule="const", warmup_steps=0),
    )
    from jax.sharding import AxisType

    mesh = jax.make_mesh(
        (1,), ("data",), devices=jax.devices()[:1],
        axis_types=(AxisType.Auto,),
    )
    _, init_state, _, _ = step_lib.make_train_step(cfg, mesh, tcfg)
    with mesh:
        state = init_state(jax.random.PRNGKey(0))
    # make the migrated-through values recognizably non-default
    state["opt"]["ef"] = state["opt"]["ef"] + 0.0  # exists (compressed + EF)
    state["monitor"]["m"]["latched"] = jnp.full((1,), 7.5, jnp.float32)
    state["step"] = jnp.asarray(11, jnp.int32)
    return state


def _downgrade_to_v1(ckdir: str, step: int):
    """Rewrite a fresh checkpoint as a pre-PR-3 (v1) one: drop 'opt/ef',
    move 'monitor/m/*' keys to the old top-level spot, stamp no version."""
    d = os.path.join(ckdir, f"step_{step}")
    with np.load(os.path.join(d, "arrays.npz")) as z:
        flat = {k: z[k] for k in z.files}
    old = {}
    for k, v in flat.items():
        if k.startswith("opt/ef"):
            continue  # pre-PR-3 compressed runs carried no residual
        parts = k.split("/")
        if "m" in parts:
            i = parts.index("m")
            k = "/".join(parts[:i] + parts[i + 1 :])
        old[k] = v
    np.savez(os.path.join(d, "arrays.npz"), **old)
    mpath = os.path.join(d, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    del manifest["layout_version"]  # v1 predates the field entirely
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    return old


@pytest.fixture(scope="module")
def state():
    return _real_state()


def test_current_layout_roundtrips_and_is_stamped(tmp_path, state):
    ck = Checkpointer(str(tmp_path))
    ck.save(11, state, block=True)
    assert ck.manifest(11)["layout_version"] == LAYOUT_VERSION
    out = ck.restore(11, jax.device_get(state))
    np.testing.assert_array_equal(
        np.asarray(out["monitor"]["m"]["latched"]), [7.5]
    )
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_v1_checkpoint_migrates_both_breaks(tmp_path, state):
    ck = Checkpointer(str(tmp_path))
    ck.save(11, state, block=True)
    _downgrade_to_v1(str(tmp_path), 11)

    out = ck.restore(11, jax.device_get(state))
    # break 1: the missing EF residual is synthesized as a fresh (zero) carry
    np.testing.assert_array_equal(
        np.asarray(out["opt"]["ef"]), np.zeros_like(np.asarray(state["opt"]["ef"]))
    )
    # break 2: the old top-level 'monitor/latched' lands under 'm/'
    np.testing.assert_array_equal(
        np.asarray(out["monitor"]["m"]["latched"]), [7.5]
    )
    # everything else restores bit-identically
    np.testing.assert_array_equal(
        np.asarray(out["opt"]["master"]), np.asarray(state["opt"]["master"])
    )
    assert int(out["step"]) == 11


def test_migrate_layout_reports_missing_keys():
    template = {"a": np.zeros((2,), np.float32), "b": np.zeros((3,), np.float32)}
    with pytest.raises(ValueError, match="missing 1 leaves.*'b'"):
        migrate_layout({"a": np.zeros((2,), np.float32)}, template, 1)


def test_migrate_layout_rejects_future_versions():
    with pytest.raises(ValueError, match="newer than this code"):
        migrate_layout({}, {}, LAYOUT_VERSION + 1)


def test_unknown_intermediate_version_raises():
    with pytest.raises(ValueError, match="no layout migration"):
        migrate_layout({}, {}, 0)
