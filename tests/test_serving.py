"""Continuous-batching serving subsystem (repro.serving, DESIGN.md S13).

Core claims under test:

1. **Bit-equivalence** — each request's greedy tokens under continuous
   batching (slot recycling, mixed admission, other slots mid-decode) are
   identical to decoding that request alone in a static batch, for a dense
   and a hybrid (SSM+attention) arch, with the termination agreement at
   dp ∈ {1, 2}.
2. **Termination agreement** — at non-power-of-two dp, a slot retires only
   when a full MRD agreement cycle certifies the *reduced* (max over
   replicas) view; one replica's locally-converged view never retires a
   slot early, and a request recycled into a slot mid-cycle can never be
   killed by its predecessor's latched done-bit.
3. **Certification soundness** — fixed-point requests retired by
   ``residual_interval`` / ``residual_inexact`` satisfy their residual
   bound at retirement (true ||f(x)-x||_inf < eps under the request's own
   payload), including at non-power-of-two dp; exhausted budgets retire
   as ``converged=False`` instead of certifying.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import registry
from repro.core import topology
from repro.distributed import step as step_lib
from repro.models import transformer
from repro.serving import (
    SCHEDULERS,
    TERMINATION,
    WORKLOADS,
    Request,
    ServeConfig,
    ServeEngine,
    TerminationConfig,
    get_scheduler,
    get_termination,
    make_workload,
)
from repro.serving.termination import make_signals


def _mesh():
    return jax.make_mesh(
        (1,), ("data",), devices=jax.devices()[:1],
        axis_types=(AxisType.Auto,),
    )


# ---------------------------------------------------------------------------
# Registry floors
# ---------------------------------------------------------------------------


def test_registry_floors():
    assert {"fcfs", "priority", "sla_edf"} <= set(SCHEDULERS)
    assert {"eos_maxlen", "residual_inexact", "residual_interval"} <= set(
        TERMINATION
    )
    assert {"llm_decode", "fixedpoint_solve"} <= set(WORKLOADS)


def test_scheduler_ordering():
    class R:
        def __init__(self, id, arrival, priority=0, sla=None):
            self.id, self.arrival = id, arrival
            self.priority, self.sla = priority, sla

    q = [R(0, 5), R(1, 2, priority=1), R(2, 3, sla=4), R(3, 1, sla=100)]
    fcfs = get_scheduler("fcfs").select(q, [0, 1, 2, 3], now=9)
    assert [r.id for r, _ in fcfs] == [3, 1, 2, 0]
    prio = get_scheduler("priority").select(q, [0, 1], now=9)
    assert [r.id for r, _ in prio] == [1, 3]  # high priority first, then FCFS
    edf = get_scheduler("sla_edf").select(q, [0, 1, 2], now=9)
    # deadlines: r2 at 7, r3 at 101, others inf (FCFS among themselves)
    assert [r.id for r, _ in edf] == [2, 3, 1]
    # slots assigned lowest-first, at most len(free)
    assert [s for _, s in edf] == [0, 1, 2]
    assert get_scheduler("fcfs").select(q, [], now=9) == []


# ---------------------------------------------------------------------------
# 1. Continuous batching == solo static decode, bit-exact tokens
# ---------------------------------------------------------------------------


def _solo_decode(cfg, mesh, params, prompt, max_new):
    """The request decoded alone in a static batch (the PR-4 serve path)."""
    serve_step, _ = step_lib.make_serve_step(cfg, mesh)
    prefill_step, _ = step_lib.make_cached_prefill_step(cfg, mesh)
    jstep, jprefill = jax.jit(serve_step), jax.jit(prefill_step)
    S = int(prompt.shape[0])
    with mesh:
        cache = transformer.init_cache(cfg, 1, S + max_new + 1)
        logits, cache = jprefill(params, jnp.asarray(prompt[None]), cache)
        toks = [int(jnp.argmax(logits, -1)[0])]
        for k in range(max_new - 1):
            logits, cache = jstep(
                params, jnp.asarray(toks[-1:], jnp.int32), cache,
                jnp.int32(S + k),
            )
            toks.append(int(jnp.argmax(logits, -1)[0]))
    return np.asarray(toks, np.int32)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "zamba2-2.7b"])
def test_continuous_matches_solo_decode(arch):
    cfg = registry.get_smoke_config(arch)
    mesh = _mesh()
    rng = np.random.default_rng(3)
    # 5 requests over 2 slots: recycling is forced, admissions land while
    # other slots are mid-decode, and lengths are mixed
    prompts = [rng.integers(0, cfg.vocab, size=L) for L in (3, 5, 8, 5, 3)]
    max_new = [6, 4, 7, 5, 6]
    workload = make_workload(
        "llm_decode", cfg=cfg, mesh=mesh, slots=2, max_len=24,
        max_prompt_len=8, seed=0,
    )
    solo = [
        _solo_decode(cfg, mesh, workload.params, p, m)
        for p, m in zip(prompts, max_new)
    ]
    for dp in (1, 2):
        workload.reset()
        eng = ServeEngine(workload, ServeConfig(
            scheduler="fcfs", termination="eos_maxlen", dp=dp,
        ))
        reqs = [
            Request(id=i, arrival=i, prompt=p, max_new=m)
            for i, (p, m) in enumerate(zip(prompts, max_new))
        ]
        res = eng.run(reqs)
        assert len(res) == len(reqs)
        for i, want in enumerate(solo):
            np.testing.assert_array_equal(
                res[i].output, want,
                err_msg=f"{arch} dp={dp} request {i}: continuous != solo",
            )


def test_eos_terminates_early():
    """A request whose EOS id appears in its solo stream retires right
    there, with the stream trimmed through the EOS token."""
    cfg = registry.get_smoke_config("llama3.2-1b")
    mesh = _mesh()
    workload = make_workload(
        "llm_decode", cfg=cfg, mesh=mesh, slots=2, max_len=24,
        max_prompt_len=8, seed=0,
    )
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab, size=5)
    solo = _solo_decode(cfg, mesh, workload.params, prompt, 8)
    eos = int(solo[3])  # pretend the 4th generated token is EOS
    want = solo[: int(np.nonzero(solo == eos)[0][0]) + 1]
    eng = ServeEngine(workload, ServeConfig(termination="eos_maxlen"))
    res = eng.run([Request(id=0, prompt=prompt, max_new=8, eos=eos)])
    np.testing.assert_array_equal(res[0].output, want)
    assert res[0].n_tokens == want.shape[0] < 8


# ---------------------------------------------------------------------------
# 2. Agreement at non-power-of-two dp (protocol-level, synthetic signals)
# ---------------------------------------------------------------------------


def _sig(dp, slots, *, tick, active, admit_tick, residual, eps=1e-3):
    return make_signals(
        tokens=jnp.zeros((slots,), jnp.int32),
        new_tokens=jnp.full((slots,), 5, jnp.int32),
        eos=jnp.full((slots,), -1, jnp.int32),
        max_new=jnp.full((slots,), 1000, jnp.int32),
        eps=jnp.full((slots,), eps, jnp.float32),
        active=jnp.asarray(active),
        admit_tick=jnp.asarray(admit_tick, jnp.int32),
        tick=jnp.int32(tick),
        residual=jnp.asarray(residual, jnp.float32),
    )


@pytest.mark.parametrize("dp", [3, 5, 6])
def test_residual_interval_waits_for_agreed_max(dp):
    """One replica's converged local view must not retire the slot: the
    agreed value is the max over replicas, reduced by a full MRD cycle."""
    term = get_termination("residual_interval")
    tcfg = TerminationConfig(dp=dp, eps=1e-3, window=1)
    slots = 2
    st = term.init(tcfg, slots)
    cyc = term.cycle_length(tcfg)
    assert cyc == len(topology.allreduce_schedule(dp))
    active = np.ones((slots,), bool)
    admit = np.zeros((slots,), np.int32)

    # replica 0 sees 1e-6 (locally converged), replica dp-1 sees 1.0
    mixed = np.full((dp, slots), 1e-6, np.float32)
    mixed[-1, :] = 1.0
    tick = 0
    for _ in range(3 * cyc):  # several full cycles of disagreement
        st, retire = term.tick(
            st, _sig(dp, slots, tick=tick, active=active, admit_tick=admit,
                     residual=mixed), tcfg,
        )
        assert not bool(np.asarray(retire).any()), "retired on a local view"
        tick += 1

    # all replicas below eps: certification lands exactly on the next
    # completed cycle (same tick for every replica, by construction)
    low = np.full((dp, slots), 1e-6, np.float32)
    seen = []
    for k in range(2 * cyc + 1):
        st, retire = term.tick(
            st, _sig(dp, slots, tick=tick, active=active, admit_tick=admit,
                     residual=low), tcfg,
        )
        r = np.asarray(retire)
        assert r.all() or not r.any(), "slots must retire together here"
        if r.any():
            seen.append(tick)
            break
        tick += 1
    assert seen, f"no certification within two cycles at dp={dp}"
    certified = np.asarray(st["certified"])
    assert (certified < tcfg.eps).all()


@pytest.mark.parametrize("dp", [1, 4])
def test_recycled_slot_survives_stale_cycle(dp):
    """eos_maxlen: a done-bit latched for the *previous* occupant of a slot
    must not retire the request admitted into that slot mid-cycle."""
    term = get_termination("eos_maxlen")
    tcfg = TerminationConfig(dp=dp)
    slots = 1
    st = term.init(tcfg, slots)
    cyc = term.cycle_length(tcfg)

    def sig(tick, new_tokens, max_new, admit_tick):
        return make_signals(
            tokens=jnp.zeros((slots,), jnp.int32),
            new_tokens=jnp.asarray([new_tokens], jnp.int32),
            eos=jnp.full((slots,), -1, jnp.int32),
            max_new=jnp.asarray([max_new], jnp.int32),
            eps=jnp.ones((slots,), jnp.float32),
            active=jnp.ones((slots,), bool),
            admit_tick=jnp.asarray([admit_tick], jnp.int32),
            tick=jnp.int32(tick),
            residual=jnp.zeros((dp, slots), jnp.float32),
        )

    # old request is done (budget hit) -> latched at cycle start (tick 0)
    retired_at = None
    for t in range(cyc):
        # at t >= 1, the slot has been recycled: a fresh request (admitted
        # at t=1, 1 token so far, budget 100) occupies it
        if t == 0:
            st, retire = term.tick(st, sig(t, 10, 10, admit_tick=0), tcfg)
        else:
            st, retire = term.tick(st, sig(t, 1 + t, 100, admit_tick=1), tcfg)
        if bool(np.asarray(retire)[0]):
            retired_at = t
    if dp == 1:
        # no lag at dp=1: the old request retires on its own tick
        assert retired_at == 0
    else:
        # the cycle completes with the old done-bit agreed, but the guard
        # (admit_tick > t_latch) protects the recycled slot
        assert retired_at is None


# ---------------------------------------------------------------------------
# 3. Fixed-point serving: certification soundness end to end
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dp", [1, 3, 5])
@pytest.mark.parametrize("protocol", ["residual_interval", "residual_inexact"])
def test_fixedpoint_certification_sound(protocol, dp):
    eps = 1e-6
    n = 60
    workload = make_workload(
        "fixedpoint_solve", solver="d_iteration", n=n, dp=dp, slots=3,
        damping=0.7, seed=1,
    )
    eng = ServeEngine(workload, ServeConfig(
        scheduler="fcfs", termination=protocol, dp=dp, eps=eps,
    ))
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(5):
        v = rng.random(n).astype(np.float32)
        reqs.append(Request(id=i, arrival=2 * i, payload=v / v.sum(),
                            max_new=800))
    res = eng.run(reqs)
    assert len(res) == len(reqs)
    for i, r in sorted(res.items()):
        assert r.converged, f"request {i} not certified"
        assert r.certified < eps
        # the residual bound at retirement: true ||f(x)-x||_inf under the
        # request's own payload is below eps (update magnitudes contract
        # monotonically, so the agreed window max dominates the truth)
        v = jnp.asarray(reqs[i].payload)
        x = jnp.asarray(r.output)
        true_res = float(jnp.max(jnp.abs(workload.pool.param_map(x, v) - x)))
        assert true_res < eps, (i, true_res)


def test_fixedpoint_budget_forces_unconverged_retirement():
    workload = make_workload(
        "fixedpoint_solve", solver="d_iteration", n=30, dp=2, slots=2,
        damping=0.9,
    )
    eng = ServeEngine(workload, ServeConfig(
        termination="residual_interval", dp=2, eps=1e-12,  # unreachably tight
    ))
    res = eng.run([Request(id=0, max_new=20)])
    assert not res[0].converged
    assert res[0].certified >= 1e-12  # never certified (RES_INIT or large)
    # the budget is exact: admission performs no iteration, so a forced
    # fixed-point request retires after exactly max_new pool iterations
    assert res[0].n_tokens == 20
    assert res[0].retire_tick - res[0].admit_tick == 19


def test_forced_retirement_does_not_inherit_recycled_certification():
    """A budget-forced request in a recycled slot must not report the
    certified residual of the slot's previous occupant."""
    workload = make_workload(
        "fixedpoint_solve", solver="d_iteration", n=30, dp=1, slots=1,
        damping=0.5,
    )
    eng = ServeEngine(workload, ServeConfig(
        termination="residual_inexact", dp=1, eps=1e-4,
    ))
    res = eng.run([
        Request(id=0, max_new=500),                 # certifies at < 1e-4
        Request(id=1, max_new=5, eps=1e-12),        # forced out, same slot
    ])
    assert res[0].converged and res[0].certified < 1e-4
    assert not res[1].converged
    assert res[1].certified >= 1e-4, "inherited the predecessor's residual"


def test_poisson1d_affine_payload_serves_distinct_rhs():
    """The affine-payload pool solves *different* systems per slot: each
    retired solution satisfies its own rhs, not the shared base one."""
    n, dp, eps = 32, 2, 1e-5  # above the float32 update-noise floor at |x|~1
    workload = make_workload(
        "fixedpoint_solve", solver="poisson1d", n=n, dp=dp, slots=2,
        shift=2.0,  # strongly diagonally dominant -> fast contraction
    )
    rng = np.random.default_rng(11)
    payloads = [rng.uniform(-5, 5, size=n).astype(np.float32) for _ in range(3)]
    eng = ServeEngine(workload, ServeConfig(
        termination="residual_interval", dp=dp, eps=eps,
    ))
    res = eng.run([
        Request(id=i, arrival=i, payload=p, max_new=3000)
        for i, p in enumerate(payloads)
    ])
    sols = []
    for i, r in sorted(res.items()):
        assert r.converged
        v = jnp.asarray(payloads[i])
        x = jnp.asarray(r.output)
        assert float(jnp.max(jnp.abs(workload.pool.param_map(x, v) - x))) < eps
        sols.append(r.output)
    assert not np.allclose(sols[0], sols[1])  # genuinely different systems


# ---------------------------------------------------------------------------
# Engine mechanics
# ---------------------------------------------------------------------------


def test_engine_rejects_residual_termination_for_llm():
    cfg = registry.get_smoke_config("llama3.2-1b")
    workload = make_workload(
        "llm_decode", cfg=cfg, mesh=_mesh(), slots=2, max_len=16,
        max_prompt_len=4,
    )
    with pytest.raises(ValueError, match="residual"):
        ServeEngine(workload, ServeConfig(termination="residual_interval"))


def test_summary_metrics_present():
    workload = make_workload(
        "fixedpoint_solve", solver="d_iteration", n=20, dp=1, slots=2,
        damping=0.5,
    )
    eng = ServeEngine(workload, ServeConfig(
        termination="residual_inexact", eps=1e-5,
    ))
    eng.run([Request(id=0, max_new=200), Request(id=1, arrival=3, max_new=200)])
    s = eng.summary()
    assert s["completed"] == 2 and s["converged"] == 2
    for k in ("throughput_tok_s", "ttft_p50_ms", "ttft_p95_ms",
              "tpot_p50_ms", "tpot_p95_ms", "occupancy", "wall_s"):
        assert np.isfinite(s[k]), k
    assert 0 < s["occupancy"] <= 1
