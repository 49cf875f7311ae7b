"""Device-executor (shard_map + ppermute) equivalence with the sim executor.

These run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main test process keeps seeing exactly one device.
"""

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
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AxisType
    from repro.core import mrd, nonblocking, detection
    from repro.core.topology import pivot

    def shard_map(f, *, mesh, in_specs, out_specs):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)

    def mesh_for(p):
        return jax.make_mesh((p,), ("r",), devices=jax.devices()[:p],
                                axis_types=(AxisType.Auto,))

    rng = np.random.default_rng(0)

    # --- allreduce: device == sim == reference, all ops, non-p2 included ---
    for p in [1, 2, 3, 5, 6, 7, 8]:
        mesh = mesh_for(p)
        x = jnp.asarray(rng.standard_normal((p, 6)).astype(np.float32))
        for op in ["sum", "max", "min"]:
            dev = jax.jit(shard_map(
                lambda v: mrd.allreduce(v[0], "r", op=op)[None],
                mesh=mesh, in_specs=P("r"), out_specs=P("r")))(x)
            sim = mrd.sim_allreduce(x, op=op)
            np.testing.assert_allclose(np.asarray(dev), np.asarray(sim), rtol=1e-5)
    print("allreduce-equivalence OK")

    # --- rabenseifner + reduce_scatter/allgather ---
    for p in [2, 3, 5, 8]:
        p0, _, _ = pivot(p)
        n = p0 * 4
        mesh = mesh_for(p)
        x = jnp.asarray(rng.standard_normal((p, n)).astype(np.float32))
        dev = jax.jit(shard_map(
            lambda v: mrd.rabenseifner_allreduce(v[0], "r")[None],
            mesh=mesh, in_specs=P("r"), out_specs=P("r")))(x)
        np.testing.assert_allclose(
            np.asarray(dev), np.broadcast_to(np.asarray(x.sum(0)), (p, n)),
            rtol=1e-4, atol=1e-4)
    print("rabenseifner-device OK")

    # --- tree_allreduce_flat over a pytree (grad-sync path) ---
    p = 6
    mesh = mesh_for(p)
    tree = {"a": jnp.asarray(rng.standard_normal((p, 3, 2)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((p, 5)), jnp.float32)}
    dev = jax.jit(shard_map(
        lambda t: jax.tree.map(
            lambda l: l[None],
            mrd.tree_allreduce_flat(jax.tree.map(lambda l: l[0], t), "r")),
        mesh=mesh, in_specs=P("r"), out_specs=P("r")))(tree)
    np.testing.assert_allclose(np.asarray(dev["a"][0]), np.asarray(tree["a"].sum(0)), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dev["b"][3]), np.asarray(tree["b"].sum(0)), rtol=1e-4)
    print("tree-flat OK")

    # --- multi-bucket pipelined path on device == sim, bit-for-bit ---
    from repro.collectives import plans as plan_lib
    for schedule in ["mrd", "rabenseifner"]:
        dev_out = jax.jit(shard_map(
            lambda t: jax.tree.map(
                lambda l: l[None],
                mrd.tree_allreduce_flat(jax.tree.map(lambda l: l[0], t), "r",
                                        schedule=schedule, bucket_bytes=16)),
            mesh=mesh, in_specs=P("r"), out_specs=P("r")))(tree)
        sim_out = plan_lib.tree_allreduce(tree, schedule=schedule, p=p,
                                          bucket_bytes=16)
        for kd, ks in zip(jax.tree.leaves(dev_out), jax.tree.leaves(sim_out)):
            assert np.array_equal(np.asarray(kd), np.asarray(ks)), schedule
    print("tree-bucketed device==sim OK")

    # --- hierarchical allreduce over a 2D mesh (pod-aware) ---
    mesh2 = jax.make_mesh((2, 4), ("pod", "data"), devices=jax.devices()[:8],
                          axis_types=(AxisType.Auto,) * 2)
    n = 8
    x = jnp.asarray(rng.standard_normal((8, n)).astype(np.float32))
    def hier(v):
        return mrd.hierarchical_allreduce(v[0], "data", "pod")[None]
    dev = jax.jit(shard_map(
        hier, mesh=mesh2,
        in_specs=P(("pod", "data")),
        out_specs=P(("pod", "data"))))(x.reshape(8, n))
    np.testing.assert_allclose(
        np.asarray(dev), np.broadcast_to(np.asarray(x.sum(0)), (8, n)), rtol=1e-4)
    print("hierarchical OK")

    # --- non-blocking statechart on device ---
    p = 5
    mesh = mesh_for(p)
    x = jnp.arange(p, dtype=jnp.float32) + 1.0
    def drive(v):
        val = v[0]
        st = nonblocking.init(val)
        for _ in range(nonblocking.cycle_length(p)):
            st = nonblocking.step(st, val, axis_name="r", op="max")
        return st["result"][None], st["flag"][None]
    res, flag = jax.jit(shard_map(
        drive, mesh=mesh, in_specs=P("r"), out_specs=(P("r"), P("r"))))(x)
    assert np.allclose(np.asarray(res), float(p)), res
    assert np.all(np.asarray(flag)), flag
    print("nonblocking-device OK")

    # --- ConvergenceMonitor on device: decreasing metric detects ---
    mon = detection.ConvergenceMonitor(axis_name="r", threshold=1e-3, mode="inexact")
    def run_monitor(metrics):
        # metrics: [steps] per-rank series
        def body(carry, m_and_i):
            m, i = m_and_i
            st, done, val = mon.step(carry, m, i)
            return st, (done, val)
        st, (dones, vals) = jax.lax.scan(
            body, mon.init(),
            (metrics, jnp.arange(metrics.shape[0])))
        return dones[None], vals[None]
    steps = 40
    series = jnp.geomspace(1.0, 1e-6, steps, dtype=jnp.float32)
    series = jnp.broadcast_to(series, (p, steps))
    dones, vals = jax.jit(shard_map(
        lambda s: run_monitor(s[0]), mesh=mesh, in_specs=P("r"),
        out_specs=(P("r"), P("r"))))(series)
    assert bool(np.asarray(dones)[0, -1]), "monitor never detected"
    print("monitor-device OK")
    print("ALL-DEVICE-TESTS-PASSED")
    """
)


@pytest.mark.slow
def test_device_executor_equivalence():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "ALL-DEVICE-TESTS-PASSED" in proc.stdout
