"""Serving chaos suite (DESIGN.md S15): scripted kill/join/stall against
the :class:`repro.runtime.ElasticServeController` under Poisson arrivals.

The acceptance bar for elastic serving, crossing non-power-of-two
termination-agreement extents (4 → 3 → 5 → 4) with traffic live the whole
time:

- **zero lost requests** — every submitted request retires with a result;
- **zero re-prefills** — the LLM pool's slot state is replica-independent,
  so a resize migrates the control plane only (``workload.prefills`` counts
  exactly one admission per request);
- **bit-identical tokens** — each request's retired stream equals the
  uninterrupted oracle run of the same traffic, for both the contiguous
  and the paged (block-table + allocator broadcast) cache layouts;
- fixed-point traffic stays *certified*: every retirement across the
  resize trajectory still satisfies its true residual bound.

Events are matched against the engine's tick clock via
``ChaosScript.apply_due`` — the engine's tick jumps by up to
``steps_per_dispatch`` per fused call, so an event due mid-dispatch fires
at the next dispatch boundary, the first point a real control plane could
act.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from chaos import ChaosScript, Join, Kill, Stall, Unstall
from repro.configs import registry
from repro.runtime import ElasticServeController, HeartbeatConfig, StepClock
from repro.serving import Request, ServeConfig, ServeEngine, make_workload

import jax


def _mesh():
    return jax.make_mesh(
        (1,), ("data",), devices=jax.devices()[:1],
        axis_types=(AxisType.Auto,),
    )


def _poisson_arrivals(rng, n, mean_gap=3.0):
    """Arrival ticks with exponential inter-arrival gaps (Poisson process)."""
    gaps = rng.exponential(mean_gap, size=n)
    return np.floor(np.cumsum(gaps)).astype(int)


def _llm_requests(cfg, n=8, seed=3):
    rng = np.random.default_rng(seed)
    arrivals = _poisson_arrivals(rng, n)
    lens = rng.integers(3, 9, size=n)
    max_new = rng.integers(4, 8, size=n)
    return [
        Request(
            id=i, arrival=int(arrivals[i]),
            prompt=rng.integers(0, cfg.vocab, size=int(lens[i])),
            max_new=int(max_new[i]),
        )
        for i in range(n)
    ]


# the scripted trajectory: 4 -> 3 (fail-stop kill) -> 5 (two joiners)
# -> 4 (second kill), with a stall/unstall riding along (grow_on_join
# drains no stragglers — the stall only exercises the heartbeat path)
def _script():
    return ChaosScript([
        Kill(step=4, device=2),
        Stall(step=8, device=1, factor=10.0),
        Join(step=14, devices=(4, 5)),
        Unstall(step=20, device=1),
        Kill(step=24, device=0),
    ])


def _assert_trajectory(resizes):
    assert [(e.kind, e.old_dp, e.new_dp) for e in resizes] == [
        ("shrink", 4, 3), ("grow", 3, 5), ("shrink", 5, 4),
    ], resizes


# ---------------------------------------------------------------------------
# LLM decode (contiguous and paged) under chaos == oracle, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("workload", ["llm_decode", "llm_decode_paged"])
def test_llm_chaos_matches_oracle_no_reprefill(workload):
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    kw = {"block_size": 8} if workload == "llm_decode_paged" else {}
    wl = make_workload(
        workload, cfg=cfg, mesh=mesh, slots=2, max_len=24,
        max_prompt_len=12, seed=0, **kw,
    )
    n = 8

    # oracle: the same Poisson traffic, uninterrupted at dp=4
    oracle = ServeEngine(wl, ServeConfig(dp=4)).run(_llm_requests(cfg, n))
    assert len(oracle) == n
    assert wl.prefills == n

    wl.reset()
    eng = ServeEngine(wl, ServeConfig(dp=4, steps_per_dispatch=3))
    ctl = ElasticServeController(eng, policy="grow_on_join")
    script = _script()
    res = ctl.run(_llm_requests(cfg, n), events=script)

    assert len(script.fired) == 5, "chaos script did not fully fire"
    _assert_trajectory(ctl.resizes)
    assert eng.dp == 4
    assert len(res) == n, "request lost across kill/join"
    assert wl.prefills == n, "a resize re-prefilled a slot"
    assert eng.summary()["resizes"] == 3
    for i in range(n):
        np.testing.assert_array_equal(
            res[i].output, oracle[i].output,
            err_msg=f"{workload} request {i}: chaotic run != oracle",
        )
    if workload == "llm_decode_paged":
        # every retired request's blocks came back through the chaos
        assert wl.pool.allocator.used_blocks == 0
        wl.pool.allocator.check()


# ---------------------------------------------------------------------------
# Fixed-point traffic: certification survives the same trajectory
# ---------------------------------------------------------------------------


def test_fixedpoint_chaos_stays_certified():
    eps = 1e-6
    n_dim = 60  # divisible by every visited extent (4, 3, 5)
    wl = make_workload(
        "fixedpoint_solve", solver="d_iteration", n=n_dim, dp=4, slots=3,
        damping=0.7, seed=1,
    )
    eng = ServeEngine(wl, ServeConfig(
        termination="residual_interval", dp=4, eps=eps,
        steps_per_dispatch=3,
    ))
    ctl = ElasticServeController(eng, policy="grow_on_join")
    rng = np.random.default_rng(7)
    n = 8
    arrivals = _poisson_arrivals(rng, n, mean_gap=4.0)
    reqs = []
    for i in range(n):
        v = rng.random(n_dim).astype(np.float32)
        reqs.append(Request(id=i, arrival=int(arrivals[i]),
                            payload=v / v.sum(), max_new=800))
    script = _script()
    res = ctl.run(reqs, events=script)

    assert len(script.fired) == 5
    _assert_trajectory(ctl.resizes)
    assert len(res) == n
    for i, r in sorted(res.items()):
        assert r.converged, f"request {i} not certified under chaos"
        assert r.certified < eps
        v = jnp.asarray(reqs[i].payload)
        x = jnp.asarray(r.output)
        true_res = float(jnp.max(jnp.abs(wl.pool.param_map(x, v) - x)))
        assert true_res < eps, (i, true_res)


# ---------------------------------------------------------------------------
# Silent kill: detection waits for the virtual heartbeat timeout
# ---------------------------------------------------------------------------


def test_silent_kill_detected_on_virtual_clock():
    wl = make_workload(
        "fixedpoint_solve", solver="d_iteration", n=60, dp=3, slots=2,
        damping=0.7, seed=1,
    )
    eng = ServeEngine(wl, ServeConfig(
        termination="residual_inexact", dp=3, eps=1e-5,
        steps_per_dispatch=2,
    ))
    ctl = ElasticServeController(
        eng, policy="shrink_on_failure",
        heartbeat=HeartbeatConfig(timeout_s=5.0),
        clock=StepClock(dt=1.0),
    )
    ctl.kill(1, silent=True)  # partition: no crash report
    res = ctl.run([
        Request(id=i, arrival=3 * i, max_new=500) for i in range(4)
    ])
    assert len(res) == 4 and all(r.converged for r in res.values())
    assert [(e.kind, e.old_dp, e.new_dp) for e in ctl.resizes] == [
        ("shrink", 3, 2)
    ]
    # the shrink waited for the timeout on the *virtual* clock
    assert ctl.resizes[0].step > 0


# ---------------------------------------------------------------------------
# ChaosScript.apply_due fires events the coarse tick clock jumped over
# ---------------------------------------------------------------------------


def test_apply_due_fires_skipped_steps():
    fired = []

    class T:
        def kill(self, d, silent=False):
            fired.append(("kill", d))

        def join(self, ds):
            fired.append(("join", ds))

    s = ChaosScript([Kill(step=3, device=0), Join(step=7, devices=(9,))])
    s.apply_due(T(), 2)
    assert fired == []
    s.apply_due(T(), 5)  # tick jumped 2 -> 5: the step-3 event is due
    assert fired == [("kill", 0)]
    s.apply_due(T(), 50)
    assert fired == [("kill", 0), ("join", (9,))]
    s.apply_due(T(), 51)  # never re-fires
    assert len(s.fired) == 2
