"""The unified collectives plan layer: schedule x executor x transform x op.

Sim-executor coverage runs in-process for every p (non-powers-of-two are the
paper's headline case).  Device-executor bit-agreement runs in a subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count=17 so the main test
process keeps seeing exactly one device.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.collectives import (
    EXECUTORS,
    SCHEDULES,
    TRANSFORMS,
    plans,
)
from repro.collectives.schedules import pivot
from repro.collectives.transforms import dequantize, quantize

PS = [2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 16, 17]


def _stack(p, n, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((p, n)).astype(np.float32))


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------


def test_registries_are_populated():
    assert {"mrd", "rabenseifner", "hierarchical"} <= set(SCHEDULES)
    assert {"device", "device_fused", "sim"} <= set(EXECUTORS)
    assert {"identity", "int8"} <= set(TRANSFORMS)


def test_unknown_names_raise_with_known_lists():
    with pytest.raises(ValueError, match="mrd"):
        plans.allreduce_plan(schedule="nope", p=4).run(_stack(4, 8))
    with pytest.raises(ValueError, match="sim"):
        plans.CollectivePlan(executor="warp", p=4).run(_stack(4, 8))
    with pytest.raises(ValueError, match="identity"):
        plans.allreduce_plan(transform="zstd", p=4)


def test_reduce_scatter_rejects_indivisible_lengths():
    """Mis-sized buffers must raise, not silently corrupt (old-API parity)."""
    with pytest.raises(ValueError, match="len % 4"):
        plans.reduce_scatter_plan(p=4).run(_stack(4, 6))
    with pytest.raises(ValueError, match="len % 4"):
        plans.allreduce_plan(schedule="rabenseifner", p=4).run(_stack(4, 6))
    with pytest.raises(ValueError, match="len % 1024"):
        plans.reduce_scatter_plan(p=4, transform="int8").run(_stack(4, 512))


def test_plan_binding_validation():
    with pytest.raises(ValueError, match="exactly one"):
        plans.CollectivePlan(axes=("data",), p=4)
    with pytest.raises(ValueError, match="exactly one"):
        plans.CollectivePlan()
    with pytest.raises(ValueError, match="sum"):
        plans.allreduce_plan(p=4, transform="int8", op="max")
    with pytest.raises(ValueError, match=">= 2 axes"):
        plans.allreduce_plan(schedule="hierarchical", p=4).run(_stack(4, 8))


# ---------------------------------------------------------------------------
# Sim executor: p sweep x schedule x op (identity transform)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", PS)
@pytest.mark.parametrize("op", ["sum", "max", "min"])
@pytest.mark.parametrize("schedule", ["mrd", "rabenseifner"])
def test_sim_allreduce_matches_reference(p, op, schedule):
    if schedule == "rabenseifner" and op != "sum":
        pytest.skip("one op suffices for the RS+AG composition")
    plan = plans.allreduce_plan(schedule=schedule, p=p, op=op)
    n = 4 * plan.pad_quantum()
    x = _stack(p, n, seed=p)
    out = np.asarray(plan.run(x))
    ref = {"sum": x.sum(0), "max": x.max(0), "min": x.min(0)}[op]
    np.testing.assert_allclose(
        out, np.broadcast_to(np.asarray(ref), (p, n)), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("p", PS)
def test_sim_reduce_scatter_and_allgather_roundtrip(p):
    p0, _, _ = pivot(p)
    n = p0 * 3
    x = _stack(p, n, seed=p + 100)
    seg = plans.reduce_scatter_plan(p=p).run(x)
    ref = np.asarray(x.sum(0))
    for i in range(p0):
        np.testing.assert_allclose(
            np.asarray(seg)[i], ref[i * 3 : (i + 1) * 3], rtol=1e-5, atol=1e-4
        )
    full = plans.allgather_plan(p=p).run(seg)
    np.testing.assert_allclose(
        np.asarray(full), np.broadcast_to(ref, (p, n)), rtol=1e-5, atol=1e-4
    )


@pytest.mark.parametrize("p", [2, 3, 5, 6, 8, 12, 13, 17])
def test_sim_int8_transform_reduce_scatter(p):
    """int8 wire format: result within per-stage quantization bounds."""
    p0, _, _ = pivot(p)
    plan = plans.reduce_scatter_plan(p=p, transform="int8")
    n = plan.pad_quantum()
    assert n == p0 * 256
    x = _stack(p, n, seed=p + 200)
    out = np.asarray(plan.run(x))
    ref = np.asarray(x.sum(0))
    m = n // p0
    for i in range(p0):
        np.testing.assert_allclose(
            out[i], ref[i * m : (i + 1) * m], rtol=0.1, atol=0.3
        )


@pytest.mark.parametrize("p", [3, 5, 8, 12])
def test_sim_int8_allreduce_blocking(p):
    plan = plans.allreduce_plan(schedule="mrd", p=p, transform="int8", op="sum")
    n = plan.pad_quantum()
    x = _stack(p, n, seed=p + 300)
    out = np.asarray(plan.run(x))
    ref = np.broadcast_to(np.asarray(x.sum(0)), (p, n))
    np.testing.assert_allclose(out, ref, rtol=0.1, atol=0.4)
    # the allreduce contract: every rank ends with the *same* value, even
    # though the wire format is lossy (butterfly combines canonical views)
    np.testing.assert_array_equal(out, np.broadcast_to(out[:1], out.shape))


# ---------------------------------------------------------------------------
# Non-blocking step() == blocking run() after one cycle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", PS)
@pytest.mark.parametrize("op", ["sum", "max", "min"])
def test_nonblocking_equals_blocking_identity(p, op):
    plan = plans.allreduce_plan(schedule="mrd", p=p, op=op)
    x = _stack(p, 8, seed=p + 400)
    staged = np.asarray(plan.run_blocking(x))
    blocking = np.asarray(plan.run(x))
    np.testing.assert_array_equal(staged, blocking)  # bit-exact
    # flag fires exactly on the completing call
    st = plan.init(x)
    for i in range(plan.cycle_length()):
        st = plan.step(st, x)
        assert bool(st["flag"]) == (i == plan.cycle_length() - 1)
    assert int(st["cycles"]) == 1


@pytest.mark.parametrize("p", [3, 5, 8, 13])
def test_nonblocking_equals_blocking_int8(p):
    plan = plans.allreduce_plan(schedule="mrd", p=p, transform="int8", op="sum")
    x = _stack(p, plan.pad_quantum(), seed=p + 500)
    staged = np.asarray(plan.run_blocking(x))
    blocking = np.asarray(plan.run(x))
    # identical math; lax.switch may re-associate fp ops vs the unrolled loop
    np.testing.assert_allclose(staged, blocking, rtol=1e-5, atol=1e-5)


def test_nonblocking_rejects_non_allreduce_plans():
    with pytest.raises(ValueError, match="allreduce-only"):
        plans.reduce_scatter_plan(p=4).cycle_length()


def test_cycle_length_matches_paper():
    for p, expect in [(1, 1), (2, 1), (4, 2), (5, 4), (8, 3), (12, 5), (16, 4)]:
        assert plans.allreduce_plan(schedule="mrd", p=p).cycle_length() == expect


# ---------------------------------------------------------------------------
# Fused (Pallas mrd_combine) executor combine == unfused math
# ---------------------------------------------------------------------------


def test_fused_combine_matches_unfused():
    from repro.collectives.executors import DeviceBackend, FusedDeviceBackend

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal(1024), jnp.float32)
    g = jnp.asarray(rng.standard_normal(1024), jnp.float32)
    q, s = quantize(g)
    plain = DeviceBackend("r").combine_quantized(x, q, s, 256)
    ref = x + dequantize(q, s)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(ref), rtol=1e-6)
    fused = FusedDeviceBackend("r").combine_quantized(x, q, s, 256)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# Grad-sync registry
# ---------------------------------------------------------------------------


def test_grad_sync_registry():
    from repro.distributed import gradsync

    assert {
        "gspmd", "mrd_paper", "mrd_leaf", "mrd_zero1", "compressed", "local_sgd"
    } <= set(gradsync.GRAD_SYNC)
    with pytest.raises(ValueError, match="mrd_zero1"):
        gradsync.get("adamw_ring")


# ---------------------------------------------------------------------------
# Device executor: bit-agreement with sim (subprocess, 17 host devices)
# ---------------------------------------------------------------------------

_DEVICE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=17"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AxisType
    from repro.collectives import plans
    from repro.collectives.schedules import pivot

    rng = np.random.default_rng(0)

    def run_device(plan_dev, x, mesh):
        def local(v):
            return plan_dev.run(v[0])[None]
        return jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=P("r"), out_specs=P("r"), check_vma=False))(x)

    for p in [2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 16, 17]:
        mesh = jax.make_mesh((p,), ("r",), devices=jax.devices()[:p], axis_types=(AxisType.Auto,))
        for schedule in ["mrd", "rabenseifner"]:
            for op in ["sum", "max", "min"]:
                if schedule == "rabenseifner" and op != "sum":
                    continue
                sim = plans.allreduce_plan(schedule=schedule, p=p, op=op)
                dev = plans.allreduce_plan(schedule=schedule, axes=("r",), op=op)
                n = 2 * sim.pad_quantum()
                x = jnp.asarray(rng.standard_normal((p, n)).astype(np.float32))
                out_d = np.asarray(run_device(dev, x, mesh))
                out_s = np.asarray(sim.run(x))
                assert np.array_equal(out_d, out_s), (
                    f"device/sim mismatch p={p} {schedule} {op}: "
                    f"{np.abs(out_d - out_s).max()}")
        print(f"p={p} identity OK")

    # int8 transform parity on a subset (wire format must be identical too)
    for p in [3, 6, 8, 13]:
        mesh = jax.make_mesh((p,), ("r",), devices=jax.devices()[:p], axis_types=(AxisType.Auto,))
        sim = plans.reduce_scatter_plan(p=p, transform="int8")
        dev = plans.reduce_scatter_plan(axes=("r",), transform="int8")
        n = sim.pad_quantum()
        x = jnp.asarray(rng.standard_normal((p, n)).astype(np.float32))
        out_d = np.asarray(run_device(dev, x, mesh))
        out_s = np.asarray(sim.run(x))
        p0, _, _ = pivot(p)
        assert np.allclose(out_d[:p0], out_s[:p0], rtol=1e-6, atol=1e-6), (
            f"int8 device/sim mismatch p={p}")
        print(f"p={p} int8 OK")

    print("DEVICE-PARITY-PASSED")
    """
)


@pytest.mark.slow
def test_device_sim_bit_agreement():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _DEVICE_SCRIPT],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "DEVICE-PARITY-PASSED" in proc.stdout
