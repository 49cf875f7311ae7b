"""Chaos suite for the elastic resize runtime (DESIGN.md S12).

In-process units cover the policy registry, the keep-map algebra, protocol
state migration across p, and script legality; the slow subprocess tests
drive scripted kill/join/stall sequences across non-power-of-two extents
and assert the chaotic run's params are **bit-identical** to uninterrupted
oracle runs at each intermediate extent (stitched by ``oracle_replay``).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chaos import ChaosScript, Join, Kill, Stall
from repro.asynchrony.protocols import (
    DETECTION_PROTOCOLS,
    RES_INIT,
    ConvergenceMonitor,
    get_protocol,
)
from repro.runtime import (
    ELASTIC_POLICIES,
    FailureDetector,
    HeartbeatConfig,
    StepClock,
    get_policy,
)
from repro.runtime.elastic import flat_keep_for_grow, flat_keep_for_shrink

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
HERE = os.path.dirname(__file__)


# ---------------------------------------------------------------------------
# Policies registry
# ---------------------------------------------------------------------------


def test_policy_registry_floor():
    assert {
        "static", "shrink_on_failure", "grow_on_join", "drain_straggler"
    } <= set(ELASTIC_POLICIES)
    with pytest.raises(ValueError, match="shrink_on_failure"):
        get_policy("scale_to_the_moon")


def _detector(workers, **hb):
    det = FailureDetector(list(workers), HeartbeatConfig(**hb))
    for w in workers:
        det.heartbeat(w, now=0.0, step_time=1.0)
    return det


def test_static_policy_aborts_on_failure():
    det = _detector([0, 1, 2, 3], timeout_s=5)
    det.mark_dead(2)
    d = get_policy("static").decide(det, 1.0, [], frozenset([0, 1, 2, 3]))
    assert d.action == "abort" and 2 in d.remove


def test_shrink_policy_ignores_joins_and_offmesh_failures():
    det = _detector([0, 1], timeout_s=5)
    pol = get_policy("shrink_on_failure")
    assert pol.decide(det, 1.0, [7], frozenset([0, 1])).action == "none"
    det.mark_dead(1)
    d = pol.decide(det, 1.0, [7], frozenset([0, 1]))
    assert d.action == "shrink" and d.remove == frozenset([1])
    # a worker that already left the mesh is not re-evicted
    assert pol.decide(det, 1.0, [], frozenset([0])).action == "none"


def test_grow_policy_prefers_shrink_then_admits():
    det = _detector([0, 1, 2], timeout_s=5)
    pol = get_policy("grow_on_join")
    d = pol.decide(det, 1.0, [5, 6], frozenset([0, 1, 2]))
    assert d.action == "grow" and set(d.admit) == {5, 6}
    det.mark_dead(0)
    assert pol.decide(det, 1.0, [5], frozenset([0, 1, 2])).action == "shrink"


def test_drain_straggler_policy_evicts_after_strikes():
    det = _detector([0, 1, 2, 3], straggler_factor=3.0,
                    evict_after_straggler_steps=2, timeout_s=1e9)
    pol = get_policy("drain_straggler")
    for t in range(1, 4):
        for w in (0, 1, 2):
            det.heartbeat(w, now=t, step_time=1.0)
        det.heartbeat(3, now=t, step_time=10.0)
        d = pol.decide(det, t, [], frozenset([0, 1, 2, 3]))
        if d.action != "none":
            break
    assert d.action == "shrink" and d.remove == frozenset([3])


# ---------------------------------------------------------------------------
# Keep-map algebra + detector/clock plumbing
# ---------------------------------------------------------------------------


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_flat_keep_shrink_and_grow_single_axis():
    mesh = _FakeMesh({"data": 4})
    assert flat_keep_for_shrink(mesh, ("data",), "data", [1, 2, 3]) == (1, 2, 3)
    assert flat_keep_for_grow(mesh, ("data",), "data", 2) == (0, 1, 2, 3, None, None)


def test_flat_keep_multi_axis_dp():
    mesh = _FakeMesh({"pod": 2, "data": 3})
    # drop data slice 1: flattened (pod-major) survivors follow their pods
    keep = flat_keep_for_shrink(mesh, ("pod", "data"), "data", [0, 2])
    assert keep == (0, 2, 3, 5)
    keep = flat_keep_for_grow(mesh, ("pod", "data"), "data", 1)
    assert keep == (0, 1, 2, None, 3, 4, 5, None)


def test_step_clock_and_detector_lifecycle():
    clk = StepClock(dt=2.0)
    assert clk.now() == 0.0 and clk.advance() == 2.0 and clk.now() == 2.0
    det = FailureDetector([0, 1], HeartbeatConfig(timeout_s=3.0), now=2.0)
    assert det.failed(now=4.0) == []  # fresh workers are not instantly dead
    det.mark_dead(0)
    assert det.failed(now=4.0) == [0]
    det.remove_worker(0)
    assert det.failed(now=4.0) == []
    det.add_worker(5, now=4.0)
    assert 5 in det.last


# ---------------------------------------------------------------------------
# Protocol state migration across p (sim states)
# ---------------------------------------------------------------------------


class _Cfg:
    max_delay = 3
    window = 0
    eps = 1e-6


@pytest.mark.parametrize("name", sorted(DETECTION_PROTOCOLS))
@pytest.mark.parametrize("keep", [(0, 2, 3), (0, 1, 2, 3, None, None)])
def test_protocol_migrate_shapes_and_latches(name, keep):
    proto = get_protocol(name)
    p_old, m = 4, 8
    st = proto.init(p_old, m, _Cfg())
    st["res_norm"] = jnp.float32(0.125)
    st["detected"] = jnp.bool_(True)
    new_p = len(keep)
    new = proto.init(new_p, m, _Cfg())  # shape reference
    migrated = proto.migrate(st, keep, new_p, m, _Cfg())
    assert jax.tree_util.tree_structure(migrated) == jax.tree_util.tree_structure(new)
    for a, b in zip(jax.tree.leaves(migrated), jax.tree.leaves(new)):
        assert a.shape == b.shape and a.dtype == b.dtype
    # the certified value and the detection latch survive the resize
    assert float(migrated["res_norm"]) == 0.125
    assert bool(migrated["detected"])


def test_inexact_migrate_carries_worker_latches():
    proto = get_protocol("inexact")
    st = proto.init(4, 8, _Cfg())
    st["res_loc"] = jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)
    out = proto.migrate(st, (0, 2, 3), 3, 8, _Cfg())
    np.testing.assert_array_equal(np.asarray(out["res_loc"]), [1.0, 3.0, 4.0])
    out = proto.migrate(st, (1, None, 3), 3, 8, _Cfg())
    np.testing.assert_array_equal(
        np.asarray(out["res_loc"]),
        np.asarray([2.0, RES_INIT, 4.0], np.float32),
    )
    # the in-flight staged reduction restarts from stage 0
    assert int(out["nb"]["stage"]) == 0 and not bool(out["nb"]["flag"])


def test_interval_migrate_moves_window_columns():
    proto = get_protocol("interval")
    cfg = _Cfg()
    st = proto.init(4, 8, cfg)
    W = st["win"].shape[0]
    st["win"] = jnp.broadcast_to(
        jnp.asarray([10.0, 20.0, 30.0, 40.0], jnp.float32), (W, 4)
    )
    out = proto.migrate(st, (3, 0, None), 3, 8, cfg)
    assert out["win"].shape == (W, 3)
    np.testing.assert_array_equal(
        np.asarray(out["win"][0]),
        np.asarray([40.0, 10.0, RES_INIT], np.float32),
    )
    # a joiner starts saturated: it cannot certify before filling a window
    assert float(jnp.max(out["win"][:, 2])) == float(jnp.float32(RES_INIT))


def test_exact_migrate_keeps_xbar_when_problem_size_unchanged():
    proto = get_protocol("exact")
    st = proto.init(4, 6, _Cfg())  # n = 24
    st["xbar"] = jnp.arange(24.0, dtype=jnp.float32)
    out = proto.migrate(st, (0, 1, 2), 3, 8, _Cfg())  # still n = 24
    np.testing.assert_array_equal(np.asarray(out["xbar"]), np.arange(24.0))
    assert int(out["mode"]) == 0 and not bool(out["snap"]["in_progress"])


def test_monitor_migrate_rows_selects_and_resets_nb():
    from repro.distributed.gradsync import common
    from repro.distributed.gradsync.common import TrainConfig

    mon = ConvergenceMonitor(axis_name="data", threshold=1e-3, mode="interval",
                             window=4)
    rows = common.monitor_rows_init(mon, 4)
    rows["value"] = jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)
    rows["done"] = jnp.asarray([False, True, False, True])
    rows["m"]["win"] = jnp.broadcast_to(
        jnp.asarray([[1.0], [2.0], [3.0], [4.0]], jnp.float32), (4, 4)
    )
    rows["nb"]["stage"] = jnp.asarray([1, 1, 1, 1], jnp.int32)
    out = mon.migrate_rows(rows, (1, 3, None))
    np.testing.assert_array_equal(
        np.asarray(out["value"]),
        np.asarray([2.0, 4.0, RES_INIT], np.float32),
    )
    np.testing.assert_array_equal(np.asarray(out["done"]), [True, True, False])
    np.testing.assert_array_equal(
        np.asarray(out["m"]["win"][:, 0]),
        np.asarray([2.0, 4.0, RES_INIT], np.float32),
    )
    np.testing.assert_array_equal(np.asarray(out["nb"]["stage"]), [0, 0, 0])


# ---------------------------------------------------------------------------
# Script DSL legality
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(8))
def test_random_scripts_are_legal(seed):
    script = ChaosScript.random(
        seed, n_steps=12, initial_devices=[0, 1, 2, 3],
        spare_devices=[4, 5], min_extent=2,
    )
    live = {0, 1, 2, 3}
    outside = {4, 5}
    for ev in script.events:
        if isinstance(ev, Kill):
            assert ev.device in live and len(live) > 2
            live.remove(ev.device)
            outside.add(ev.device)
        elif isinstance(ev, Join):
            assert set(ev.devices) <= outside
            outside -= set(ev.devices)
            live |= set(ev.devices)
    assert len(live) >= 2


def test_script_applies_each_event_once():
    class _T:
        killed = []

        def kill(self, d, silent=False):
            self.killed.append(d)

    script = ChaosScript([Kill(3, 7)])
    t = _T()
    script.apply(t, 2)
    assert t.killed == []
    script.apply(t, 3)
    script.apply(t, 3)
    assert t.killed == [7]


# ---------------------------------------------------------------------------
# Subprocess chaos runs: bit-identity vs the per-extent oracle replay
# ---------------------------------------------------------------------------

_PRELUDE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {here!r})
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.configs import registry
    from repro.data.pipeline import DataConfig, SyntheticPipeline
    from repro.distributed import step as step_lib
    from repro.optim.optimizer import OptimizerConfig
    from repro.runtime import ElasticConfig, ElasticTrainer, HeartbeatConfig
    from chaos import (ChaosScript, Kill, Join, Stall, oracle_replay,
                       assert_params_bit_identical)

    cfg = registry.get_smoke_config("llama3.2-1b")

    def make_tcfg(**kw):
        kw.setdefault("grad_sync", "mrd_zero1")
        kw.setdefault("monitor", True)
        kw.setdefault("monitor_mode", "interval")
        kw.setdefault("monitor_threshold", 1e-6)
        return step_lib.TrainConfig(
            microbatches=1, remat="none",
            optimizer=OptimizerConfig(lr=5e-3, schedule="const", warmup_steps=0),
            **kw)

    def run_chaos(tcfg, dcfg, dev_ids, script, steps, policy, hb=None):
        mesh = jax.make_mesh(
            (len(dev_ids),), ("data",),
            devices=[jax.devices()[i] for i in dev_ids],
            axis_types=(AxisType.Auto,))
        tr = ElasticTrainer(
            mesh, (cfg, tcfg),
            pipe_factory=lambda m: SyntheticPipeline(cfg, dcfg, m),
            checkpointer=None,
            cfg=ElasticConfig(policy=policy, heartbeat=hb or HeartbeatConfig()),
        )
        state = tr.init_or_restore(jax.random.PRNGKey(0))
        state, losses = tr.run(state, steps, events=script)
        return tr, state, losses

    def check_vs_oracle(tr, state, losses, tcfg, dcfg, dev_ids, steps, tag):
        o_state, o_losses = oracle_replay(
            cfg, tcfg, dcfg, dev_ids, tr.resizes, steps)
        assert losses == o_losses, (tag, losses, o_losses)
        assert_params_bit_identical(state["params"], o_state["params"], tag)
        assert_params_bit_identical(state["opt"], o_state["opt"], tag + ":opt")
        print(tag, "extents",
              [(e.kind, e.old_dp, e.new_dp, e.step) for e in tr.resizes],
              "OK")
    """
)


def _run(script_body: str, timeout=1200):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    proc = subprocess.run(
        [sys.executable, "-c", _PRELUDE.format(here=HERE) + textwrap.dedent(script_body)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert proc.returncode == 0, (
        f"STDOUT:\n{proc.stdout[-4000:]}\nSTDERR:\n{proc.stderr[-6000:]}"
    )
    return proc.stdout


@pytest.mark.slow
def test_chaos_kill_join_crossing_non_p2_extents():
    """The headline scenario: 4 -> 3 -> 5 -> 4, kills and joins interleaved,
    bit-identical to the chained per-extent oracle runs."""
    out = _run(
        """
        dcfg = DataConfig(batch=60, seq_len=8, seed=0)  # lcm(4,3,5) divides 60
        tcfg = make_tcfg()
        dev_ids = [0, 1, 2, 3]
        script = ChaosScript([
            Kill(2, 2),           # 4 -> 3 at step 2
            Join(4, (2, 4)),      # 3 -> 5 at step 4 (a killed worker rejoins)
            Kill(7, 1),           # 5 -> 4 at step 7
        ])
        steps = 10
        tr, state, losses = run_chaos(tcfg, dcfg, dev_ids, script, steps,
                                      "grow_on_join")
        assert [ (e.old_dp, e.new_dp) for e in tr.resizes ] == [(4,3),(3,5),(5,4)], tr.resizes
        assert [ e.step for e in tr.resizes ] == [2, 4, 7]
        assert tr.restores == 0
        assert not any(e.restored_from_checkpoint for e in tr.resizes)
        assert len(losses) == steps
        check_vs_oracle(tr, state, losses, tcfg, dcfg, dev_ids, steps, "kill-join")
        print("CHAOS-KILL-JOIN-PASSED")
        """
    )
    assert "CHAOS-KILL-JOIN-PASSED" in out


@pytest.mark.slow
def test_chaos_grow_3_to_5_without_checkpoint():
    """Acceptance: a grow 3 -> 5 resumes in place — no checkpointer exists,
    params arrive at the joiners over the MRD broadcast at p=5."""
    out = _run(
        """
        dcfg = DataConfig(batch=15, seq_len=16, seed=0)
        tcfg = make_tcfg(grad_sync="compressed")  # EF residual rides along
        dev_ids = [0, 1, 2]
        script = ChaosScript([Join(3, (3, 4))])
        steps = 7
        tr, state, losses = run_chaos(tcfg, dcfg, dev_ids, script, steps,
                                      "grow_on_join")
        assert [ (e.kind, e.old_dp, e.new_dp) for e in tr.resizes ] == [("grow", 3, 5)]
        assert tr.restores == 0 and tr.ck is None
        assert not tr.resizes[0].restored_from_checkpoint
        assert "ef" in state["opt"]
        check_vs_oracle(tr, state, losses, tcfg, dcfg, dev_ids, steps, "grow35")
        print("CHAOS-GROW-PASSED")
        """
    )
    assert "CHAOS-GROW-PASSED" in out


@pytest.mark.slow
def test_chaos_straggler_drain_and_silent_kill():
    """drain_straggler evicts a stalled worker after exactly
    evict_after_straggler_steps slow steps; a silent kill is detected
    exactly when the virtual heartbeat timeout elapses.  Both trajectories
    are bit-identical to their oracle replays."""
    out = _run(
        """
        dcfg = DataConfig(batch=12, seq_len=16, seed=0)
        tcfg = make_tcfg()
        dev_ids = [0, 1, 2, 3]

        # -- straggler drain: stall fires before step 1, two strikes evict
        hb = HeartbeatConfig(straggler_factor=3.0, evict_after_straggler_steps=2,
                             timeout_s=1e9)
        script = ChaosScript([Stall(1, 3, factor=10.0)])
        steps = 6
        tr, state, losses = run_chaos(tcfg, dcfg, dev_ids, script, steps,
                                      "drain_straggler", hb=hb)
        assert [ (e.kind, e.old_dp, e.new_dp) for e in tr.resizes ] == [("shrink", 4, 3)]
        assert "straggler" in tr.resizes[0].reason
        check_vs_oracle(tr, state, losses, tcfg, dcfg, dev_ids, steps, "drain")

        # -- silent kill: partition at step 1, timeout_s=2.5 on the injected
        #    clock -> detected before step 3 (heartbeats at now=step+1)
        hb2 = HeartbeatConfig(timeout_s=2.5)
        script2 = ChaosScript([Kill(1, 0, silent=True)])
        tr2, state2, losses2 = run_chaos(tcfg, dcfg, dev_ids, script2, steps,
                                         "shrink_on_failure", hb=hb2)
        assert [ (e.kind, e.old_dp, e.new_dp) for e in tr2.resizes ] == [("shrink", 4, 3)]
        # deterministic detection: last heartbeat at now=1, timeout 2.5,
        # heartbeats at now=step+1 -> first now - last > 2.5 is now=4 (step 3)
        assert tr2.resizes[0].step == 3, tr2.resizes
        check_vs_oracle(tr2, state2, losses2, tcfg, dcfg, dev_ids, steps, "silent")
        print("CHAOS-DRAIN-SILENT-PASSED")
        """
    )
    assert "CHAOS-DRAIN-SILENT-PASSED" in out


@pytest.mark.slow
def test_chaos_kill_join_with_overlap():
    """Elastic resize under the ready-bucket overlap path (DESIGN.md S16):
    kills and joins crossing non-power-of-two extents with ``overlap=True``
    must stay bit-identical both to the same chaotic run without overlap
    (pure-reordering invariant survives rebuilds) and to the per-extent
    oracle replay."""
    out = _run(
        """
        dcfg = DataConfig(batch=60, seq_len=8, seed=0)  # 4, 3, 5 all divide
        dev_ids = [0, 1, 2, 3]
        script = [Kill(2, 2), Join(4, (2, 4))]   # 4 -> 3 at 2, 3 -> 5 at 4
        steps = 8
        tcfg_o = make_tcfg(overlap=True)
        tr, state, losses = run_chaos(tcfg_o, dcfg, dev_ids,
                                      ChaosScript(list(script)), steps,
                                      "grow_on_join")
        assert [ (e.old_dp, e.new_dp) for e in tr.resizes ] == [(4, 3), (3, 5)]
        tcfg_b = make_tcfg(overlap=False)
        tr_b, state_b, losses_b = run_chaos(tcfg_b, dcfg, dev_ids,
                                            ChaosScript(list(script)), steps,
                                            "grow_on_join")
        assert losses == losses_b, ("overlap vs baseline", losses, losses_b)
        assert_params_bit_identical(state["params"], state_b["params"], "ovl")
        assert_params_bit_identical(state["opt"], state_b["opt"], "ovl:opt")
        check_vs_oracle(tr, state, losses, tcfg_o, dcfg, dev_ids, steps,
                        "overlap-chaos")
        print("CHAOS-OVERLAP-PASSED")
        """
    )
    assert "CHAOS-OVERLAP-PASSED" in out


@pytest.mark.slow
def test_chaos_random_seeded_scripts():
    """Seeded random legal kill/join sequences (the 'any legal sequence'
    clause): every one is bit-identical to its oracle replay."""
    out = _run(
        """
        dcfg = DataConfig(batch=60, seq_len=8, seed=0)  # extents 2..6 all divide
        tcfg = make_tcfg()
        dev_ids = [0, 1, 2, 3]
        steps = 9
        for seed in (1, 7):
            script = ChaosScript.random(
                seed, n_steps=steps, initial_devices=dev_ids,
                spare_devices=[4, 5], min_extent=2, max_events=3)
            tr, state, losses = run_chaos(tcfg, dcfg, dev_ids, script, steps,
                                          "grow_on_join")
            assert tr.restores == 0
            check_vs_oracle(tr, state, losses, tcfg, dcfg, dev_ids, steps,
                            f"rand{seed}")
        print("CHAOS-RANDOM-PASSED")
        """
    )
    assert "CHAOS-RANDOM-PASSED" in out
